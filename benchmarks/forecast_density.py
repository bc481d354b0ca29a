"""Forecast density: predictive pre-inflate vs the reactive governor.

The reactive governor (PR 5) predicts each tenant's next arrival with a
memoryless inter-arrival EWMA — good for steady Poisson traffic, blind
to *structure*: a diurnal tenant quiet for most of the period looks
exactly like a dead one, and a flash crowd (hundreds of tenants hit in
the same few seconds, the paper's motivating burst) gives the EWMA no
warning at all.  The :class:`~repro.core.forecast.TrafficForecaster`
adds per-tenant seasonal phase bins plus a short/long-window burst
detector, and the :class:`~repro.core.forecast.ForecastDaemon` spends
those predictions as low-priority pre-inflates through the existing
wake pipeline.

This suite drives two virtual-time traces through one budgeted node,
each under two policies (reactive = ``GovernorConfig(forecast=None)``,
forecast = the same governor with a forecaster):

  diurnal      — tenants in four phase cohorts, each active only in its
                 quarter of the period (Poisson inside the window).
  flash-crowd  — sparse background arrivals, plus a cohort that slams
                 the node at the same phase every period.

Both traces run identical learning periods (arrivals observed, tenants
hibernated, no serving measured) before one measured period.  In the
forecast runs the daemon is stepped *before* each arrival is revealed,
so a pre-inflate only ever comes from the seasonal model / burst
detector, never from peeking at the event being measured; its wake cost
is paid off the request path, which is exactly the mechanism under
test.  Arrival times are virtual — the suite measures wake/serve cost,
not wall-clock sleeps.  Tenants-per-GB is tenants over the enforced
budget, identical for both policies by construction: the claim gated
here is that forecasting makes the burst land on pre-inflated tenants
(fewer deflated burst hits, lower burst-arrival TTFT) at *equal*
density, not that it changes the budget.
"""
from __future__ import annotations

import shutil
import time

import numpy as np

from benchmarks.common import (SHARED_PATHS, Table, build_factory, fmt_mb,
                               request_for, shared_loader_for)
from repro.core.forecast import ForecastConfig, ForecastDaemon
from repro.core.governor import GovernorConfig
from repro.core.manager import InstanceManager, ManagerConfig
from repro.core.metrics import percentile
from repro.core.state import ContainerState, Rung
from repro.serving.engine import ServingEngine

DEFLATED = (ContainerState.HIBERNATE, ContainerState.PARTIAL,
            ContainerState.MMAP_CLEAN)

ARCH = "arctic-480b"
PROMPT_LEN = 24
PERIOD_S = 60.0              # one virtual "day"
LEARN_PERIODS = 3            # observed-only periods before the measure one


def _forecast_cfg() -> ForecastConfig:
    return ForecastConfig(
        season_period_s=PERIOD_S, n_bins=12, min_periods=2,
        confidence_arrivals=8, preinflate_margin_s=6.0,
        preinflate_min_confidence=0.2, max_preinflates_per_pass=16,
        short_window_s=2.0, long_window_s=20.0,
        burst_ratio=3.0, burst_min_arrivals=4)


def _make(spool: str, budget, forecast: bool):
    shutil.rmtree(spool, ignore_errors=True)
    factory = build_factory("tiny")
    gov_cfg = GovernorConfig(
        min_partial_bytes=4 << 10, headroom=0.05,
        forecast=_forecast_cfg() if forecast else None)
    mgr = InstanceManager(
        ManagerConfig(spool_dir=spool, wake_mode="reap",
                      share_base_weights=True,
                      memory_budget_bytes=budget,
                      governor_policy=gov_cfg),
        factory, shared_loader=shared_loader_for(factory))
    return ServingEngine(mgr), mgr


def _setup_tenants(eng, mgr, n):
    """Cold-start n tenants with recorded working sets, then hibernate
    the whole fleet — both traces start from the deflated steady state
    the density numbers assume."""
    for i in range(n):
        iid = f"t{i}"
        inst = eng.start_instance(iid, ARCH, shared_paths=SHARED_PATHS)
        inst.recorder.start()
        eng.handle(request_for(inst.cfg, iid, "probe", PROMPT_LEN, 1,
                               seed=100 + i, close_session=True))
        inst.recorder.stop()
    for i in range(n):
        mgr.descend(f"t{i}", Rung.HIBERNATED)


def _diurnal_schedule(n, periods, seed):
    """[(t, tenant_idx, in_burst)]: four phase cohorts, each tenant
    Poisson-active only inside its quarter of every period (no burst
    cohort — ``in_burst`` is always False here)."""
    rng = np.random.default_rng(seed)
    evs = []
    win = PERIOD_S / 4.0
    for i in range(n):
        start = (i % 4) * win
        for p in range(periods):
            t = p * PERIOD_S + start
            end = t + win
            while True:
                t += rng.exponential(8.0)
                if t >= end:
                    break
                evs.append((t, i, False))
    evs.sort()
    return evs


def _flash_schedule(n, periods, seed):
    """[(t, tenant_idx, in_burst)]: the first quarter of the fleet is
    the crowd — quiet all day, then slamming the node together at phase
    0.6 every period (the paper's motivating burst); the rest is sparse
    Poisson background.  Crowd events carry ``in_burst=True`` so the
    run can score the wake storm separately from scattered background
    wakes; a memoryless EWMA sees one arrival per period from a crowd
    tenant and predicts it cold forever."""
    rng = np.random.default_rng(seed)
    evs = []
    crowd = max(1, n // 4)
    for i in range(crowd, n):
        t = 0.0
        while True:
            t += rng.exponential(PERIOD_S * 1.5)
            if t >= periods * PERIOD_S:
                break
            evs.append((t, i, False))
    for p in range(periods):
        base = p * PERIOD_S + 0.6 * PERIOD_S
        for i in range(crowd):
            evs.append((base + rng.uniform(0.0, 2.0), i, True))
    evs.sort()
    return evs


def _tick(mgr, daemon, t):
    """One control-plane tick: the daemon pre-inflates whoever the model
    says is due, and we absorb the wake cost *here* — off the request
    path, which is the whole point.  A governor pass follows in the same
    tick (as in the platform's policy daemon), so pre-inflating the next
    cohort displaces colder tenants immediately instead of letting the
    transient stack until the next arrival."""
    woke = daemon.step(t)
    for wid in woke:
        winst = mgr.instances.get(wid)
        if winst is not None and winst.wake_pipeline is not None:
            winst.wake_pipeline.wait(60)
    if woke:
        mgr.governor.step(now=t)


def _run(eng, mgr, schedule, *, measure_from, tick_s=1.0):
    """Drive the schedule: arrivals before ``measure_from`` only train
    the models; after it, every request is served and timed, with the
    forecast daemon ticking on a steady virtual cadence between events
    (like the platform's policy daemon, it never sees the unrevealed
    arrivals).  Returns a result dict — deflated-arrival counts are
    kept separately for burst-flagged events, because under a hard
    budget pre-inflating the crowd *displaces* warm background tenants:
    the claim is that the clustered wake storm lands warm, not that the
    total number of (scattered, cheap) wakes drops."""
    gov = mgr.governor
    daemon = ForecastDaemon(mgr) if gov.forecaster is not None else None
    ttfts, burst_ttfts = [], []
    deflated = burst_deflated = 0
    peak = 0
    clock = measure_from
    for j, (t, i, in_burst) in enumerate(schedule):
        iid = f"t{i}"
        if t < measure_from:
            gov.observe_arrival(iid, now=t)
            continue
        if daemon is not None:
            while clock < t:
                _tick(mgr, daemon, clock)
                clock += tick_s
            _tick(mgr, daemon, t)
        gov.observe_arrival(iid, now=t)
        gov.step(now=t)
        inst = mgr.instances[iid]
        was_deflated = inst.state in DEFLATED
        t0 = time.monotonic()
        eng.handle(request_for(inst.cfg, iid, f"s{j}", PROMPT_LEN, 1,
                               seed=1000 + j, close_session=True))
        dt = time.monotonic() - t0
        ttfts.append(dt)
        deflated += was_deflated
        if in_burst:
            burst_ttfts.append(dt)
            burst_deflated += was_deflated
        if inst.wake_pipeline is not None:
            inst.wake_pipeline.wait(60)
        inst.quiesce_bg()
        inst.last_used = t
        peak = max(peak, mgr.resident_bytes())
    return {
        "ttfts": ttfts, "burst_ttfts": burst_ttfts,
        "deflated": deflated, "burst_deflated": burst_deflated,
        "peak": peak,
        "prewarmed": daemon.prewarmed_tenants if daemon is not None else 0,
    }


def _per_gb(n, bytes_):
    return n / (bytes_ / 2**30)


def main(quick: bool = False):
    n = 24 if quick else 240
    seed = 7
    periods = LEARN_PERIODS + 1
    measure_from = LEARN_PERIODS * PERIOD_S

    traces = [
        ("diurnal", _diurnal_schedule(n, periods, seed)),
        ("flash-crowd", _flash_schedule(n, periods, seed + 1)),
    ]

    # budget reference: one warm fleet build (reused for its footprint
    # only — each measured run gets a fresh node)
    eng, mgr = _make("/tmp/bench_forecast/ref", None, forecast=False)
    _setup_tenants(eng, mgr, min(n, 6))
    per_tenant = mgr.resident_bytes() // min(n, 6)
    del eng, mgr
    budget = max(int(per_tenant * n * 0.35), 64 << 20)

    tab = Table(
        f"Forecast density: {n} tenants ({ARCH}), budget {fmt_mb(budget)} MB,"
        f" {LEARN_PERIODS} learning periods + 1 measured",
        ["trace", "policy", "tenants/GB", "ttft p50 ms", "ttft p99 ms",
         "burst mean ms", "deflated hits", "burst deflated", "prewarmed",
         "peak MB"])
    results = {}
    budget_ok = True
    for trace, schedule in traces:
        for policy in ("reactive", "forecast"):
            eng, mgr = _make(f"/tmp/bench_forecast/{trace}-{policy}",
                             budget, forecast=(policy == "forecast"))
            _setup_tenants(eng, mgr, n)
            r = _run(eng, mgr, schedule, measure_from=measure_from)
            # transient slack: wake restores may overshoot until the
            # next governor pass reclaims them
            budget_ok &= r["peak"] <= budget + max(3 * per_tenant,
                                                   budget // 8)
            tt, btt = r["ttfts"], r["burst_ttfts"]
            r["p99"] = percentile(tt, 99)
            r["burst_mean"] = sum(btt) / len(btt) if btt else 0.0
            results[(trace, policy)] = r
            tab.add(trace, policy, f"{_per_gb(n, budget):.1f}",
                    f"{percentile(tt, 50) * 1e3:.1f}",
                    f"{r['p99'] * 1e3:.1f}",
                    f"{r['burst_mean'] * 1e3:.1f}" if btt else "-",
                    f"{r['deflated']}/{len(tt)}",
                    f"{r['burst_deflated']}/{len(btt)}" if btt else "-",
                    str(r["prewarmed"]), fmt_mb(r["peak"]))
            del eng, mgr
    print(tab.render())

    flash_re, flash_fc = results[("flash-crowd", "reactive")], \
        results[("flash-crowd", "forecast")]
    diur_fc = results[("diurnal", "forecast")]
    checks = [
        # the headline: at identical tenants-per-GB the forecaster eats
        # the flash crowd's wake storm off the request path — the
        # *clustered* burst arrivals land on pre-inflated tenants.
        # (Total deflated count is NOT gated for this trace: under a
        # hard budget, pre-inflating the crowd displaces warm background
        # tenants into scattered — individually cheap — wakes.)
        ("flash-crowd: burst arrivals land on pre-inflated tenants "
         "(fewer deflated burst hits than reactive)",
         flash_fc["burst_deflated"] < flash_re["burst_deflated"]),
        ("flash-crowd: mean burst-arrival TTFT forecast < reactive",
         flash_fc["burst_mean"] < flash_re["burst_mean"]),
        # the diurnal trace is informative, not gated on counts: at a
        # budget ~3 tenants short of the warm fleet, cohort-transition
        # displacement is sensitive to wall-clock wake-cost EWMAs and
        # the deflated-hit count swings run to run — the deterministic
        # mechanism claim lives on the flash trace above
        ("forecast daemon actually pre-inflated tenants (flash-crowd)",
         flash_fc["prewarmed"] > 0),
        ("forecast daemon actually pre-inflated tenants (diurnal)",
         diur_fc["prewarmed"] > 0),
        ("governor enforces budget under both policies (measured peak)",
         budget_ok),
    ]
    return tab, checks


if __name__ == "__main__":
    main()
