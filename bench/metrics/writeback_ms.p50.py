"""KV and state cache layer: median over the answered requests due in the
window of the program's ``kv.writeback`` span, the decoded tokens' KV and
final state written back into pages after the batch's decode."""
from bench.spans import span_percentile


def read(run):
    return span_percentile(run, "kv.writeback", 50)
