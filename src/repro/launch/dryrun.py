import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=512"
os.environ["JAX_PLATFORMS"] = "cpu"

"""Multi-pod dry-run: lower + compile every (arch x shape x mesh) case.

The lines above MUST precede any other import (jax locks the device
count at first init), which is why this module sets XLA_FLAGS before its
docstring.  The placeholders are CPU devices: a dry-run never takes an
accelerator, so it is safe beside a process that holds the chip.  Do not
import this module from tests or benchmarks — they are supposed to see
one device.

Usage:
  PYTHONPATH=src python -m repro.launch.dryrun --arch llama3.2-3b \
      --shape decode_32k --mesh single
  PYTHONPATH=src python -m repro.launch.dryrun --all --out dryrun.json
"""
import argparse
import json
import sys
import time
import traceback

import jax

from repro.configs import ARCH_IDS, SHAPES, get_config, get_shape
from repro.launch import analysis, sharding as shd, specs
from repro.launch.mesh import make_production_mesh, refine_mesh
from repro.utils.dist import ShardingRules, use_rules


def run_case(arch: str, shape_name: str, mesh_name: str,
             verbose: bool = True) -> dict:
    cfg = get_config(arch)
    shape = get_shape(shape_name)
    if not specs.applicable(cfg, shape):
        return {"arch": arch, "shape": shape_name, "mesh": mesh_name,
                "status": "skipped", "reason": cfg.long_context_mode}
    prod = make_production_mesh(multi_pod=(mesh_name == "multi"))
    mesh = refine_mesh(prod, cfg.tp, cfg.sp)
    chips = mesh.devices.size

    t0 = time.monotonic()
    fn, args, donate = specs.build_case(cfg, shape, mesh)
    mode = {"train": "train", "prefill": "prefill",
            "decode": "decode"}[shape.kind]
    rules = ShardingRules(mesh, shd.activation_rules(
        cfg, mode, mesh, shape.global_batch))
    with use_rules(rules):
        jitted = jax.jit(fn, donate_argnums=donate)
        lowered = jitted.lower(*args)
        t_lower = time.monotonic() - t0
        compiled = lowered.compile()
        t_compile = time.monotonic() - t0 - t_lower

    roof = analysis.analyse(compiled, cfg, shape, mesh_name, chips)
    mem = compiled.memory_analysis()
    out = roof.to_dict()
    out.update({
        "status": "ok",
        "lower_s": round(t_lower, 1),
        "compile_s": round(t_compile, 1),
        "memory_analysis": {
            "argument_bytes": getattr(mem, "argument_size_in_bytes", None),
            "output_bytes": getattr(mem, "output_size_in_bytes", None),
            "temp_bytes": getattr(mem, "temp_size_in_bytes", None),
            "alias_bytes": getattr(mem, "alias_size_in_bytes", None),
        },
    })
    if verbose:
        gb = (out["bytes_per_device"] or 0) / 2**30
        print(f"[{arch} x {shape_name} x {mesh_name}] OK "
              f"chips={chips} bytes/dev={gb:.2f}GiB "
              f"flops/dev={out['device_flops']:.3e} "
              f"compute={out['compute_s']*1e3:.2f}ms "
              f"memory={out['memory_s']*1e3:.2f}ms "
              f"collective={out['collective_s']*1e3:.2f}ms "
              f"bottleneck={out['bottleneck']} "
              f"useful={out['useful_flops_frac']:.2f} "
              f"(lower {t_lower:.0f}s compile {t_compile:.0f}s)",
              flush=True)
    return out


def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", choices=ARCH_IDS)
    ap.add_argument("--shape", choices=sorted(SHAPES))
    ap.add_argument("--mesh", choices=("single", "multi"), default="single")
    ap.add_argument("--all", action="store_true",
                    help="every (arch x shape) for --mesh")
    ap.add_argument("--out", default=None, help="append JSONL here")
    args = ap.parse_args(argv)

    cases = []
    if args.all:
        for a in ARCH_IDS:
            for s in SHAPES:
                cases.append((a, s, args.mesh))
    else:
        if not (args.arch and args.shape):
            ap.error("--arch and --shape (or --all) required")
        cases.append((args.arch, args.shape, args.mesh))

    failures = 0
    for a, s, m in cases:
        try:
            res = run_case(a, s, m)
        except Exception as e:
            failures += 1
            res = {"arch": a, "shape": s, "mesh": m, "status": "error",
                   "error": f"{type(e).__name__}: {e}"}
            print(f"[{a} x {s} x {m}] FAILED: {e}", flush=True)
            traceback.print_exc()
        if args.out:
            with open(args.out, "a") as f:
                f.write(json.dumps(res) + "\n")
    return 1 if failures else 0


if __name__ == "__main__":
    sys.exit(main())
