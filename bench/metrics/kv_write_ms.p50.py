"""KV and state cache layer: median over the answered requests due in the
window of the program's ``kv.write`` span, the prefill's KV and SSM state
written into pages and host units."""
from bench.spans import span_percentile


def read(run):
    return span_percentile(run, "kv.write", 50)
