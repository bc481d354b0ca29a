"""KV and state cache layer: p95 over the window's batches of the
program's ``kv_held_bytes`` counter, in MB (1e6 bytes): what the tenant's
cache and the node's prefix registry still hold once a batch finished and
its closed sessions were freed.  It stays within a few sessions' worth
where closed sessions are reclaimed, and grows with the requests served
where they are kept."""
from bench.spans import counters
from bench.stats import percentile


def read(run):
    per_batch = dict(counters(run, "kv_held_bytes"))
    return percentile([v / 1e6 for v in per_batch.values()], 95)
