"""Benchmark harness: one module per paper table/figure.

  latency_states   — Fig. 6 (request latency per container state)
  memory_states    — Fig. 7 (PSS per state, 10 instances, sharing on)
  density          — deployment-density conclusion
  governor_density — memory governor: tenants-per-GB vs p99 TTFT under a
                     shrinking budget (rung ladder vs warm/hibernate)
  forecast_density — predictive control plane: seasonal + flash-crowd
                     pre-inflate vs the reactive governor, p99 TTFT at
                     equal tenants-per-GB
  dedup_store      — content-addressed swap store: cross-tenant dedup,
                     zero-page elision, compression tiers
  wake_latency     — streamed wake pipeline: synchronous vs pipelined
                     time-to-first-token (p50/p99)
  swap_throughput  — §3.4 random-vs-sequential storage asymmetry
  sharing          — §3.5 runtime-binary (base-weight) sharing
  allocator        — §3.3 bitmap allocator vs free-list baseline
  concurrency      — AsyncPlatform: tenants x workers, wake storms,
                     vectored fault IO
  cluster_density  — cluster fabric: 4 nodes, skewed tenant pile,
                     migration-on vs migration-off tenants-per-GB
  prefix_density   — prefix registry: resident-KV dedup across tenants
                     and nodes, adopted vs prefilled TTFT, sharing on/off
  gateway_latency  — network front door: streaming TTFT per SLO class
                     and container state over loopback HTTP, overload 429s
  recovery         — failure domain: kill a node, re-home MTTR from
                     replicated segments, post-recovery wake p99
  zygote_cold_start— zygote pool: fork-admission vs cold-start TTFT for
                     brand-new tenants (dense/MoE/SSM), byte identity
  roofline         — brief: per-(arch x shape x mesh) roofline table

`python -m benchmarks.run [--quick] [--only NAME[,NAME...]]`
"""
from __future__ import annotations

import argparse
import json
import sys
import time


def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--quick", action="store_true")
    ap.add_argument("--only", default=None,
                    help="comma-separated suite names")
    # default deliberately NOT bench_results.json: that file is the
    # committed CI bench-regression baseline (conservative floor) and must
    # only be updated intentionally
    ap.add_argument("--out", default="bench_out.json")
    args = ap.parse_args(argv)

    from repro.utils.compile_cache import enable_compile_cache

    enable_compile_cache()

    from benchmarks import (allocator, cluster_density, concurrency,
                            dedup_store, density, forecast_density,
                            gateway_latency, governor_density,
                            latency_states, memory_states, prefix_density,
                            reap_ablation, recovery, roofline, sharing,
                            swap_throughput, wake_latency,
                            zygote_cold_start)
    suites = [
        ("allocator", allocator),
        ("swap_throughput", swap_throughput),
        ("wake_latency", wake_latency),
        ("latency_states", latency_states),
        ("memory_states", memory_states),
        ("density", density),
        ("governor_density", governor_density),
        ("forecast_density", forecast_density),
        ("cluster_density", cluster_density),
        ("prefix_density", prefix_density),
        ("gateway_latency", gateway_latency),
        ("recovery", recovery),
        ("zygote_cold_start", zygote_cold_start),
        ("dedup_store", dedup_store),
        ("sharing", sharing),
        ("reap_ablation", reap_ablation),
        ("concurrency", concurrency),
        ("roofline", roofline),
    ]
    only = set(args.only.split(",")) if args.only else None
    if only:
        unknown = only - {n for n, _ in suites}
        if unknown:
            ap.error(f"unknown suite(s): {sorted(unknown)}")
    results = {}
    all_checks = []
    for name, mod in suites:
        if only and name not in only:
            continue
        print(f"\n===== {name} =====", flush=True)
        t0 = time.monotonic()
        tab, checks = mod.main(quick=args.quick)
        dt = time.monotonic() - t0
        print(f"({name}: {dt:.1f}s)")
        results[name] = {"table": tab.to_dict(),
                         "checks": [(c[0], bool(all(c[1:]))) for c in checks],
                         "seconds": dt}
        all_checks += [(name, c[0], bool(all(c[1:]))) for c in checks]

    print("\n===== claim checks =====")
    n_bad = 0
    for suite, claim, ok in all_checks:
        print(f"  [{'PASS' if ok else 'FAIL'}] {suite}: {claim}")
        n_bad += (not ok)
    with open(args.out, "w") as f:
        json.dump(results, f, indent=1)
    print(f"\n{len(all_checks) - n_bad}/{len(all_checks)} claim checks pass"
          f" -> {args.out}")
    return 0 if n_bad == 0 else 1


if __name__ == "__main__":
    sys.exit(main())
