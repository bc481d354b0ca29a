"""Governor density: tenants-per-GB vs p99 TTFT under a shrinking budget.

The paper's economics are a spectrum between Warm and Hibernate; the
:class:`~repro.core.governor.MemoryGovernor` spends that spectrum against
a fixed node memory budget.  This suite drives a Poisson tenant mix (hot
/ medium / cold arrival rates) through one engine under four policies:

  always-warm     — no deflation: density is bounded by the warm PSS
                    footprint, latency is the floor.
  always-hib      — full deflate after every request: density is bounded
                    only by the peak of one inflated tenant, every
                    request pays a full REAP wake.
  governor@f%     — the rung ladder under a budget of f% of the warm
                    footprint: hot tenants stay high on the ladder
                    (EWMA next-arrival prediction), cold tenants sink
                    through MMAP_CLEAN/PARTIAL to HIBERNATED.

Tenants-per-GB is tenants divided by *provisioned* node memory: the warm
footprint for always-warm, the observed peak for always-hib, the enforced
budget for the governor rows.  A separate controlled micro-benchmark
measures the per-rung wake critical path (the same tenant deflated to
PARTIAL vs HIBERNATED, woken by a request) — the PARTIAL rung's reason to
exist is that its wake is measurably cheaper.

Arrival times are virtual (the governor's `now` is a parameter), so the
suite measures wake/serve cost, not wall-clock sleeps.
"""
from __future__ import annotations

import shutil
import time

import numpy as np

from benchmarks.common import (SHARED_PATHS, Table, build_factory, fmt_mb,
                               request_for, shared_loader_for)
from repro.core.governor import GovernorConfig
from repro.core.manager import InstanceManager, ManagerConfig
from repro.core.metrics import per_rung_report, percentile
from repro.serving.engine import ServingEngine
from repro.core.state import Rung

ARCH = "arctic-480b"         # MoE: expert units give the PARTIAL rung teeth
PROMPT_LEN = 24
HOT_GAP, MED_GAP, COLD_GAP = 0.5, 2.0, 8.0


def _make(spool: str, budget=None, governor_cfg=None):
    shutil.rmtree(spool, ignore_errors=True)
    factory = build_factory("tiny")
    mgr = InstanceManager(
        ManagerConfig(spool_dir=spool, wake_mode="reap",
                      share_base_weights=True,
                      memory_budget_bytes=budget,
                      governor_policy=governor_cfg),
        factory, shared_loader=shared_loader_for(factory))
    return ServingEngine(mgr), mgr


def _setup_tenants(eng, mgr, n):
    """Cold-start n tenants, warm the compile caches, record working sets.

    Each tenant keeps one long-lived "ctx" session open whose KV pages the
    recorded working set does NOT touch: cold deep-layer context — exactly
    the REAP-miss-ranked PARTIAL-rung victims.  Benchmark requests use
    fresh short sessions so serve shapes (and compile buckets) stay
    fixed."""
    for i in range(n):
        iid = f"t{i}"
        inst = eng.start_instance(iid, ARCH, shared_paths=SHARED_PATHS)
        eng.handle(request_for(inst.cfg, iid, "ctx", 64, 1, seed=i))
        inst.recorder.start()
        eng.handle(request_for(inst.cfg, iid, "probe", PROMPT_LEN, 1,
                               seed=100 + i, close_session=True))
        inst.recorder.stop()


def _gaps(n):
    """Per-tenant mean inter-arrival gap: 1/3 hot, 1/3 medium, 1/3 cold."""
    return [HOT_GAP if i < n // 3 else
            MED_GAP if i < 2 * n // 3 else COLD_GAP for i in range(n)]


def _schedule(n, events, seed=7):
    """Merged Poisson arrival schedule: [(t, tenant_idx)] sorted by t."""
    rng = np.random.default_rng(seed)
    gaps = _gaps(n)
    per = -(-events // n)
    evs = []
    for i in range(n):
        t = 0.0
        for _ in range(per):
            t += rng.exponential(gaps[i])
            evs.append((t, i))
    evs.sort()
    return evs[:events]


def _run(eng, mgr, n, events, policy, seed=7):
    """Drive the schedule; returns (ttfts, peak_resident, rung_counts)."""
    ttfts = []
    # peak is sampled after each event's policy+serve, not at entry: the
    # setup leaves every tenant warm, and charging the governor for
    # memory it has not yet been asked to reclaim would be noise
    peak = 0
    gov = mgr.governor
    for j, (t, i) in enumerate(_schedule(n, events, seed)):
        iid = f"t{i}"
        inst = mgr.instances[iid]
        gov.observe_arrival(iid, now=t)
        if policy == "governor":
            gov.step(now=t)
        t0 = time.monotonic()
        eng.handle(request_for(inst.cfg, iid, f"s{j}", PROMPT_LEN, 1,
                               seed=1000 + j, close_session=True))
        ttfts.append(time.monotonic() - t0)
        if inst.wake_pipeline is not None:
            inst.wake_pipeline.wait(60)
        inst.quiesce_bg()
        inst.last_used = t
        peak = max(peak, mgr.resident_bytes())
        if policy == "always-hib":
            mgr.descend(iid, Rung.HIBERNATED)
    return ttfts, peak, per_rung_report(mgr)


def _rung_wake_costs(eng, mgr, iid, cycles):
    """Controlled per-rung wake cost: deflate ONE tenant to PARTIAL vs
    HIBERNATED, wake it with a real request, average the measured
    critical-path seconds (WakeStats.rung distinguishes the ladders)."""
    inst = mgr.instances[iid]
    out = {"partial": [], "hibernated": []}
    for c in range(cycles):
        for rung in ("partial", "hibernated"):
            if rung == "partial":
                victims = [k for _, _, k in
                           mgr.governor._partial_candidates(inst)]
                mgr.descend(iid, Rung.PARTIAL, keys=victims)
            else:
                mgr.descend(iid, Rung.HIBERNATED)
            eng.handle(request_for(inst.cfg, iid, f"rw{c}{rung[0]}",
                                   PROMPT_LEN, 1, seed=500 + c,
                                   close_session=True))
            if inst.wake_pipeline is not None:
                inst.wake_pipeline.wait(60)
            inst.quiesce_bg()
            wakes = [s for op, _, s in mgr.hib.log if op == "wake"]
            assert wakes[-1].rung == rung, (wakes[-1].rung, rung)
            out[rung].append(wakes[-1].critical_path_seconds)
    return {r: float(np.mean(v)) for r, v in out.items()}


def _per_gb(n, bytes_):
    return n / (bytes_ / 2**30)


def main(quick: bool = False):
    n = 6 if quick else 9
    events = 36 if quick else 90
    fracs = (0.5, 0.3) if quick else (0.6, 0.4, 0.25)
    gov_cfg = GovernorConfig(min_partial_bytes=4 << 10, headroom=0.05)

    # warm footprint reference (also the always-warm run)
    eng, mgr = _make("/tmp/bench_governor/warm")
    _setup_tenants(eng, mgr, n)
    warm_bytes = mgr.resident_bytes()
    warm_tt, warm_peak, _ = _run(eng, mgr, n, events, "always-warm")
    rung_costs = _rung_wake_costs(eng, mgr, f"t{n - 1}", 3 if quick else 5)
    del eng, mgr

    rows = [("always-warm", warm_peak, warm_peak, warm_tt, None)]
    eng, mgr = _make("/tmp/bench_governor/hib")
    _setup_tenants(eng, mgr, n)
    for i in range(n):
        mgr.descend(f"t{i}", Rung.HIBERNATED)
    hib_tt, hib_peak, _ = _run(eng, mgr, n, events, "always-hib")
    rows.append(("always-hib", hib_peak, hib_peak, hib_tt, None))
    del eng, mgr

    budget_ok = True
    for f in fracs:
        budget = int(warm_bytes * f)
        eng, mgr = _make(f"/tmp/bench_governor/gov{int(f * 100)}",
                         budget=budget, governor_cfg=gov_cfg)
        _setup_tenants(eng, mgr, n)
        tt, peak, rungs = _run(eng, mgr, n, events, "governor")
        # enforcement: measured peak may transiently exceed the budget by
        # about one tenant's wake restore (the governor reclaims at the
        # next event), never by the whole fleet — a no-op governor would
        # sit at the warm footprint and fail this
        budget_ok &= peak <= budget + 2 * warm_bytes / n
        rows.append((f"governor@{int(f * 100)}%", max(budget, 1), peak,
                     tt, rungs))
        del eng, mgr

    # p99 TTFT budget: a fixed multiple of the warm floor (the "near-warm"
    # envelope a latency SLO would allow)
    warm_p99 = percentile(warm_tt, 99)
    tt_budget = max(3.0 * warm_p99, warm_p99 + 0.05)

    tab = Table(
        f"Governor density: {n} Poisson tenants ({ARCH}), shrinking budget; "
        f"p99 TTFT budget {tt_budget * 1e3:.0f} ms",
        ["policy", "provisioned MB", "peak MB", "tenants/GB", "ttft p50 ms",
         "ttft p99 ms", "within budget", "rungs at end"])
    densities = {}
    for name, prov, peak, tt, rungs in rows:
        p50, p99 = percentile(tt, 50), percentile(tt, 99)
        densities[name] = (_per_gb(n, prov), p99)
        rung_str = "-" if rungs is None else " ".join(
            f"{r}:{int(v['instances'])}" for r, v in sorted(rungs.items()))
        tab.add(name, fmt_mb(prov), fmt_mb(peak), f"{_per_gb(n, prov):.1f}",
                f"{p50 * 1e3:.1f}", f"{p99 * 1e3:.1f}",
                "yes" if p99 <= tt_budget else "NO", rung_str)
    print(tab.render())
    print(f"rung wake critical path: partial "
          f"{rung_costs['partial'] * 1e3:.2f} ms vs hibernated "
          f"{rung_costs['hibernated'] * 1e3:.2f} ms")

    warm_density = densities["always-warm"][0]
    gov_ok = [d for name, (d, p99) in densities.items()
              if name.startswith("governor") and p99 <= tt_budget]
    checks = [
        ("governor >=1.5x tenants-per-GB vs always-warm at fixed p99 TTFT",
         bool(gov_ok) and max(gov_ok) >= 1.5 * warm_density),
        ("partial wake critical path < hibernated wake critical path",
         rung_costs["partial"] < rung_costs["hibernated"]),
        # density rows are provisioned-budget based, so this is the claim
        # that makes them honest: the governor actually held the fleet to
        # the budget (modulo one tenant's transient wake restore)
        ("governor enforces budget (measured peak)", budget_ok),
    ]
    return tab, checks


if __name__ == "__main__":
    main()
