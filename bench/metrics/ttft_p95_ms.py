"""95th percentile over every request due in the window of the time from
its due time (scheduled in an open loop, sent in a closed one) to its
first token.  A request that failed, was refused or never answered ranks
slower than every answered one, at the whole wait the client gave it (to
the close plus the drain)."""
from bench.harness import DRAIN_S
from bench.stats import percentile


def read(run):
    ranked = [(0, (r.token_times[0] - r.due) * 1e3) if r.ok
              else (1, (run.t1 + DRAIN_S - r.due) * 1e3)
              for r in run.due()]
    p = percentile(ranked, 95)
    return None if p is None else p[1]
