"""Engine layer: host-resident weight bytes handed to one compiled step
(prefill or decode) in the window, a count that repeats exactly while the
weights live in host memory.  Mean over the window's dispatches."""


def read(run):
    rows = [host for _, _, _, host, t0, _ in run.probes.dispatches
            if run.in_window(t0)]
    return sum(rows) / len(rows) if rows else None
