"""Order statistics the metric readers share."""
from __future__ import annotations

from typing import Optional, Sequence


def percentile(xs: Sequence[float], q: float) -> Optional[float]:
    """Nearest-rank percentile (q in [0, 100]); None for no samples."""
    if not xs:
        return None
    s = sorted(xs)
    return s[min(len(s) - 1, max(0, int(round(q / 100.0 * (len(s) - 1)))))]
