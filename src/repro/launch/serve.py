"""Serving launcher: a hibernating multi-tenant node under a request trace.

Two modes:
  * ``--dry-run``: lower+compile serve_step (decode_32k) for the
    production mesh via launch.dryrun (on the CPU, 512 placeholder devices).
  * default: run a REAL trace on JAX's default device: Poisson-ish
    arrivals over N tenants served by the AsyncPlatform worker pool
    (bursts of ``--burst`` requests run concurrently), keep-alive
    deflation, REAP or pagefault wakes.  Prints each response's
    breakdown from its spans (queue, serve-lock wait, wake, prefill,
    decode, KV writeback, in ms) beside its end-to-end time, with the
    host bytes its compiled steps uploaded; then per-state latency
    percentiles and final memory per tenant.  ``--scale`` picks the model
    size: ``tiny``/``scaled`` are reduced float32 variants for the CPU,
    ``full`` is the arch's published config in bf16.

  PYTHONPATH=src python -m repro.launch.serve --tenants 4 --requests 24
  PYTHONPATH=src python -m repro.launch.serve --scale full --tenants 1 \
      --requests 4 --workers 1

Until WARM weights live on the device, every dispatch uploads the
tenant's whole model, so at ``--scale full`` each parallel worker holds
its own copy in device memory: keep ``--workers 1`` on one chip.
"""
from __future__ import annotations

import argparse
import shutil
import subprocess
import sys
import time

#: ``Response.spans`` keys printed per response, under their column names
BREAKDOWN = (("queue", "serve.queue"), ("lock", "serve.lock_wait"),
             ("wake", "wake"), ("prefill", "serve.prefill"),
             ("decode", "serve.decode"), ("writeback", "kv.writeback"))


def breakdown(resp) -> str:
    """One response's spans in ms and its host-to-device bytes."""
    parts = [f"{col}={resp.spans.get(key, 0.0) * 1e3:.0f}"
             for col, key in BREAKDOWN]
    return " ".join(parts) + f" h2d={resp.h2d_bytes / 1e6:.1f}MB"


def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="phi4-mini-3.8b")
    ap.add_argument("--scale", choices=("tiny", "scaled", "full"),
                    default="tiny")
    ap.add_argument("--tenants", type=int, default=3)
    ap.add_argument("--requests", type=int, default=18)
    ap.add_argument("--wake-mode", choices=("reap", "pagefault"),
                    default="reap")
    ap.add_argument("--workers", type=int, default=4)
    ap.add_argument("--burst", type=int, default=3,
                    help="requests submitted concurrently between policy "
                         "passes")
    ap.add_argument("--keep-warm-s", type=float, default=0.0)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--spool", default="/tmp/repro_launch_serve")
    ap.add_argument("--dry-run", action="store_true")
    ap.add_argument("--mesh", choices=("single", "multi"), default="single")
    args = ap.parse_args(argv)

    if args.dry_run:
        return subprocess.call(
            [sys.executable, "-m", "repro.launch.dryrun", "--arch",
             args.arch, "--shape", "decode_32k", "--mesh", args.mesh])

    import numpy as np
    import jax

    from repro.utils.compile_cache import enable_compile_cache

    enable_compile_cache()

    from repro.configs import get_config, scaled_config, tiny_config
    from repro.core.manager import InstanceManager, ManagerConfig
    from repro.core.metrics import memory_report
    from repro.models import model
    from repro.serving import (AsyncPlatform, PlatformPolicy, Request,
                               ServingEngine)

    shutil.rmtree(args.spool, ignore_errors=True)

    scale_cfg = {"tiny": tiny_config, "scaled": scaled_config,
                 "full": lambda cfg: cfg}[args.scale]

    init = jax.jit(model.init_params, static_argnums=1)

    def factory(arch):
        # no params cache: the instance copies them to the host, and a
        # kept device copy would double the model's device footprint
        cfg = scale_cfg(get_config(arch))
        return cfg, init(jax.random.PRNGKey(args.seed), cfg)

    mgr = InstanceManager(
        ManagerConfig(spool_dir=args.spool, wake_mode=args.wake_mode),
        factory)
    eng = ServingEngine(mgr)
    tenants = {f"fn{i}": args.arch for i in range(args.tenants)}
    # the driver runs the policy pass between bursts; idle the daemon
    plat = AsyncPlatform(eng, PlatformPolicy(keep_warm_s=args.keep_warm_s,
                                             tick_interval_s=3600.0),
                         tenants, workers=args.workers)

    rng = np.random.default_rng(args.seed)
    lat_by_state: dict = {}
    with plat:
        for b0 in range(0, args.requests, args.burst):
            burst = []
            for r_i in range(b0, min(b0 + args.burst, args.requests)):
                tenant = f"fn{rng.integers(args.tenants)}"
                fut = plat.submit(Request(
                    tenant, f"s{r_i}",
                    rng.integers(0, 256, 6).astype(np.int32),
                    max_new_tokens=4, close_session=True))
                burst.append((r_i, tenant, fut))
            for r_i, tenant, fut in burst:
                resp = fut.result()
                lat_by_state.setdefault(resp.state_before, []).append(
                    resp.spans["e2e"])
                print(f"  req{r_i:03d} {tenant:5s} {resp.state_before:9s}->"
                      f"{resp.state_after:6s} "
                      f"{resp.spans['e2e'] * 1e3:7.0f}ms "
                      f"[{breakdown(resp)}] faults={resp.faults}",
                      flush=True)
            for iid in plat.policy_pass():
                print(f"    [policy] deflated {iid}")
            # REAP-record each tenant once it has served
            for _, tenant, _ in burst:
                inst = mgr.instances.get(tenant)
                if inst is not None and not inst.recorder.working_set:
                    eng.record_sample(tenant, Request(
                        tenant, "probe",
                        rng.integers(0, 256, 4).astype(np.int32),
                        max_new_tokens=2, close_session=True))

    print("\nper-state latency (ms):")
    for st, xs in sorted(lat_by_state.items()):
        xs = sorted(xs)
        print(f"  {st:9s} n={len(xs):3d} p50={xs[len(xs) // 2] * 1e3:7.0f} "
              f"max={xs[-1] * 1e3:7.0f}")
    print("tenant memory:")
    for iid, inst in mgr.instances.items():
        rep = memory_report(inst, mgr.shared)
        print(f"  {iid:5s} state={rep.state:9s} "
              f"pss={rep.pss_total / 2**20:7.2f} MB")
    return 0


if __name__ == "__main__":
    sys.exit(main())
