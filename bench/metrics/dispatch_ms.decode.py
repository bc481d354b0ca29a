"""Engine layer: median wall time of one decode dispatch in the window,
from the call to its result on the device (weight upload included)."""
from bench.stats import percentile


def read(run):
    return percentile([(t1 - t0) * 1e3 for kind, _, _, _, t0, t1
                       in run.probes.dispatches
                       if kind == "decode" and run.in_window(t0)], 50)
