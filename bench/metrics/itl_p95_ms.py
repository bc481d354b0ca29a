"""95th percentile over every gap between consecutive tokens of every
request due in the window."""
import numpy as np

from bench.stats import percentile


def read(run):
    gaps = []
    for r in run.due():
        gaps += list(np.diff(r.token_times) * 1e3)
    return percentile(gaps, 95)
