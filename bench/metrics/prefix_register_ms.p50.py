"""KV and state cache layer: median over the answered requests due in the
window of the program's ``prefix.register`` span, a fresh prompt's pages
shared and written through to the prefix registry."""
from bench.spans import span_percentile


def read(run):
    return span_percentile(run, "prefix.register", 50)
