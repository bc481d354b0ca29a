"""Wake layer: median over the answered requests that woke their tenant
of the program's ``wake.install`` span, the wake's decoding and installing
of units up to the point the request could run (summed over threads)."""
from bench.spans import span_percentile


def read(run):
    return span_percentile(run, "wake.install", 50)
