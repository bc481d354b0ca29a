"""Engine layer: backend compiles (persistent-cache reads included) while
the window's batches were served, from the program's ``compiles``
counter, once per batch (every response of a batch carries its count).
0 when the warm-up covered every shape."""
from bench.spans import counters


def read(run):
    per_batch = dict(counters(run, "compiles"))
    return sum(per_batch.values()) if per_batch else None
