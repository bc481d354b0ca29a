"""Memory accounting (PSS analogue of the paper's `pmap` methodology) and
the serving path's one tracing helper, :class:`span`.

A span is a ``jax.profiler.TraceAnnotation`` (on the profiler's clock,
on the thread that ran it, with ids such as ``tenant`` and ``batch`` as
event stats) whose duration is also added to ``Response.spans[name]`` of
each response it serves.  A span that repeats (a decode step) adds up.
"""
from __future__ import annotations

import time
from dataclasses import dataclass
from typing import Dict, Sequence

from jax.profiler import TraceAnnotation


def percentile(xs: Sequence[float], q: float) -> float:
    """Nearest-rank percentile (q in [0, 100]) — no numpy needed."""
    if not xs:
        return float("nan")
    s = sorted(xs)
    idx = min(len(s) - 1, max(0, int(round(q / 100.0 * (len(s) - 1)))))
    return s[idx]


@dataclass
class MemoryReport:
    instance_id: str
    state: str
    rung: str = ""               # deflation-ladder rung (warm/mmap_clean/...)
    weight_private: int = 0      # resident anonymous weight bytes
    weight_shared_pss: float = 0.0   # shared base weights / num sharers
    kv_rss: int = 0              # pool pages held (RSS)
    kv_pss: float = 0.0          # pool pages / refcount (prefix sharing)
    metadata: int = 0            # kept-alive host objects
    # disk tier (swap + REAP files) — the SwapStore's resident-vs-unique-
    # vs-compressed view.  logical: what verbatim per-sandbox files would
    # hold; stored_pss: fair-share on-disk bytes (dedup'd segments split
    # across referencing units, compressed sizes).
    disk_logical: int = 0
    disk_stored_pss: float = 0.0

    @property
    def pss_total(self) -> float:
        return (self.weight_private + self.weight_shared_pss
                + self.kv_pss + self.metadata)

    @property
    def rss_total(self) -> float:
        return (self.weight_private + self.weight_shared_pss
                + self.kv_rss + self.metadata)


def memory_report(inst, shared_registry=None) -> MemoryReport:
    nshare = 1
    shared_bytes = inst.shared_weight_bytes()
    if shared_registry is not None and inst.base_id:
        nshare = max(1, shared_registry.refcount(inst.base_id))
        if not shared_registry.is_loaded(inst.base_id):
            shared_bytes = 0
    sf = inst.swap_file
    disk_logical = (getattr(sf, "logical_bytes", None) or sf.file_bytes) \
        + inst.reap_file.file_bytes
    # for a StoreClient, file_bytes is already the fair-share (PSS-style)
    # compressed on-disk footprint; for a private SwapFile it is the file
    return MemoryReport(
        instance_id=inst.instance_id,
        state=inst.state.value,
        rung=inst.rung.name.lower(),
        weight_private=inst.weight_bytes(resident_only=True,
                                         include_shared=False),
        weight_shared_pss=shared_bytes / nshare,
        kv_rss=inst.kv_bytes(),
        kv_pss=(inst.pool.pss_bytes(inst.instance_id) if inst.pool else 0)
        + (inst.kv.host_bytes() if inst.kv is not None else 0),
        metadata=inst.metadata_bytes(),
        disk_logical=disk_logical,
        disk_stored_pss=sf.file_bytes + inst.reap_file.file_bytes,
    )


def per_rung_report(manager) -> Dict[str, Dict[str, float]]:
    """Deployment-wide per-rung accounting: how many tenants sit on each
    deflation-ladder rung and what they cost in memory and disk.

    Returns ``{rung: {instances, weight_private, weight_shared_pss,
    kv_rss, pss_total, disk_logical, disk_stored_pss}}`` — the
    ``MemoryReport`` columns aggregated by rung (see the README's
    "Memory governor" section for how to read them)."""
    with manager._lock:
        insts = list(manager.instances.values())
    out: Dict[str, Dict[str, float]] = {}
    for inst in insts:
        rep = memory_report(inst, manager.shared)
        row = out.setdefault(rep.rung, {
            "instances": 0, "weight_private": 0, "weight_shared_pss": 0.0,
            "kv_rss": 0, "pss_total": 0.0, "disk_logical": 0,
            "disk_stored_pss": 0.0})
        row["instances"] += 1
        row["weight_private"] += rep.weight_private
        row["weight_shared_pss"] += rep.weight_shared_pss
        row["kv_rss"] += rep.kv_rss
        row["pss_total"] += rep.pss_total
        row["disk_logical"] += rep.disk_logical
        row["disk_stored_pss"] += rep.disk_stored_pss
    return out


def cluster_report(nodes) -> Dict[str, Dict[str, float]]:
    """Per-node rollup for a cluster of :class:`~repro.cluster.node.Node`:
    tenants, governed bytes vs budget, rung mix, and the store's
    dedup'd on-disk footprint — the columns ``benchmarks/cluster_density``
    renders and the router's rebalance decisions act on."""
    out: Dict[str, Dict[str, float]] = {}
    for node in nodes:
        rungs = per_rung_report(node.manager)
        budget = node.governor.budget_bytes
        store = node.store
        reg = getattr(node.manager, "prefix_registry", None)
        pstats = reg.stats() if reg is not None else {}
        out[node.node_id] = {
            "tenants": sum(r["instances"] for r in rungs.values()),
            "governed_bytes": node.governed_bytes(),
            "budget_bytes": budget if budget is not None else float("inf"),
            "pressure_bytes": node.pressure_bytes(),
            "rungs": {r: int(v["instances"]) for r, v in rungs.items()},
            "disk_stored_bytes": store.live_bytes if store else 0,
            # prefix-registry surface the router's affinity term reads
            "prefix_entries": pstats.get("entries", 0),
            "prefix_resident_bytes": pstats.get("resident_bytes", 0),
            "prefix_adoptions": pstats.get("adoptions", 0),
        }
    return out


def charge(resps: Sequence, name: str, seconds: float) -> None:
    """Add ``seconds`` to ``spans[name]`` of each response."""
    for r in resps:
        r.spans[name] = r.spans.get(name, 0.0) + seconds


class span:
    """Trace the enclosed work as ``name`` and charge its wall time to
    ``resps``.  ``ids`` (str or int) become the annotation's stats.  With
    no profiler running the annotation costs about a microsecond."""

    __slots__ = ("name", "resps", "_note", "_t0")

    def __init__(self, name: str, resps: Sequence = (), **ids):
        self.name, self.resps = name, resps
        self._note = TraceAnnotation(name, **ids)

    def __enter__(self) -> "span":
        self._note.__enter__()
        self._t0 = time.monotonic()
        return self

    def __exit__(self, *exc) -> None:
        charge(self.resps, self.name, time.monotonic() - self._t0)
        self._note.__exit__(*exc)
