"""Engine layer: 95th percentile over the answered requests due in the
window of the program's ``serve.lock_wait`` span, the batch's wait for
its tenant's serve lock (held by another batch or by a deflate)."""
from bench.spans import span_percentile


def read(run):
    return span_percentile(run, "serve.lock_wait", 95)
