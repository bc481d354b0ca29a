"""Wake layer: median over the answered requests that woke their tenant
of the program's ``wake.read`` span, the wake's reads up to the point the
request could run (summed over the wake's threads)."""
from bench.spans import span_percentile


def read(run):
    return span_percentile(run, "wake.read", 50)
