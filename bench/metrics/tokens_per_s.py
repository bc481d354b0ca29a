"""Output tokens emitted inside the window, over the window."""


def read(run):
    n = sum(run.in_window(t) for r in run.records for t in r.token_times)
    return n / (run.t1 - run.t0) if n else None
