"""What metric readers take from the program's own spans and counters:
``Response.spans`` (seconds, by span name) and the counters beside it,
on the answered requests due in the window.  A program that records none
of them reads as nothing (``None``), never as an error."""
from __future__ import annotations

from typing import List, Optional

from bench.stats import percentile


def answered(run) -> list:
    """The responses of the answered requests due in the window."""
    return [r.resp for r in run.due() if r.ok]


def span_ms(run, name: str) -> List[float]:
    """``spans[name]`` in ms of each answered response that has it."""
    return [r.spans[name] * 1e3 for r in answered(run) if name in r.spans]


def span_percentile(run, name: str, q: float) -> Optional[float]:
    return percentile(span_ms(run, name), q)


def counters(run, name: str) -> list:
    """``(batch, value)`` of the counter ``name`` on each answered
    response; empty where the program does not count it."""
    return [(r.batch, getattr(r, name)) for r in answered(run)
            if hasattr(r, name) and hasattr(r, "batch")]
