"""Engine layer: mean over the answered requests due in the window of the
program's ``dispatch_inflight`` counter, the compiled steps in flight in
the process when each of the batch's dispatches started, itself included
(1 when no other worker's step overlaps)."""
from bench.spans import counters


def read(run):
    rows = [v for _, v in counters(run, "dispatch_inflight")]
    return sum(rows) / len(rows) if rows else None
