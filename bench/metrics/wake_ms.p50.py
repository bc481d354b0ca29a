"""Wake layer: median wall time of ``InstanceManager.ensure_awake`` called
by the engine for a request that found its tenant deflated (the wake's
critical path: with the pipelined wake it returns at the prefill-critical
prefix)."""
from bench.stats import percentile


def read(run):
    return percentile([(t1 - t0) * 1e3 for _, state, trig, t0, t1, _
                       in run.probes.wakes
                       if trig == "request" and run.in_window(t0)
                       and state in ("hibernate", "partial", "mmap_clean")],
                      50)
