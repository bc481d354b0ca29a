"""KV / state cache layer: median wall time of the engine's dense gather
(``_dense_cache``: pages and host units into the decode cache, uploaded)
per decode call in the window."""
from bench.stats import percentile


def read(run):
    return percentile([(t1 - t0) * 1e3 for t0, t1 in run.probes.kv_gathers
                       if run.in_window(t0)], 50)
