"""Training launcher.

Two modes:
  * ``--dry-run``: delegate to launch.dryrun for the production mesh
    (lower + compile only; needs no hardware).
  * default: run REAL steps at a CPU-feasible scale (tiny/scaled variant
    of the selected arch) with the full substrate: synthetic pipeline,
    AdamW + schedule, remat, checkpointing.

  PYTHONPATH=src python -m repro.launch.train --arch yi-6b --steps 50
  PYTHONPATH=src python -m repro.launch.train --arch yi-6b --dry-run
"""
from __future__ import annotations

import argparse
import subprocess
import sys
import time


def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="llama3.2-3b")
    ap.add_argument("--steps", type=int, default=50)
    ap.add_argument("--scale", choices=("tiny", "scaled"), default="scaled")
    ap.add_argument("--batch", type=int, default=8)
    ap.add_argument("--seq", type=int, default=128)
    ap.add_argument("--lr", type=float, default=1e-3)
    ap.add_argument("--ckpt", default="/tmp/repro_launch_train/ckpt")
    ap.add_argument("--dry-run", action="store_true",
                    help="lower+compile train_4k on the production mesh")
    ap.add_argument("--mesh", choices=("single", "multi"), default="single")
    args = ap.parse_args(argv)

    if args.dry_run:
        # dryrun.py must own the process (XLA_FLAGS before jax import)
        return subprocess.call(
            [sys.executable, "-m", "repro.launch.dryrun", "--arch",
             args.arch, "--shape", "train_4k", "--mesh", args.mesh])

    import jax
    import jax.numpy as jnp

    from repro.utils.compile_cache import enable_compile_cache

    enable_compile_cache()

    from repro.configs import get_config, scaled_config, tiny_config
    from repro.data import DataConfig, SyntheticPipeline
    from repro.models import model
    from repro.training import (AdamWConfig, checkpoint, init_state,
                                make_train_step)

    cfg = get_config(args.arch)
    cfg = tiny_config(cfg) if args.scale == "tiny" else scaled_config(cfg)
    params = model.init_params(jax.random.PRNGKey(0), cfg)
    opt_cfg = AdamWConfig(lr=args.lr, warmup_steps=max(2, args.steps // 10),
                          total_steps=args.steps)
    opt = init_state(params)
    step_fn = jax.jit(make_train_step(cfg, opt_cfg, remat=True))
    pipe = SyntheticPipeline(
        DataConfig(cfg.vocab_size, args.seq, args.batch, seed=0),
        frontend=cfg.frontend)

    t0 = time.monotonic()
    for step in range(args.steps):
        batch = {k: jnp.asarray(v) for k, v in pipe.batch(step).items()}
        params, opt, m = step_fn(params, opt, batch)
        if step % 10 == 0 or step == args.steps - 1:
            print(f"step {step:4d}  loss {float(m['loss']):.4f}  "
                  f"lr {float(m['lr']):.2e}  "
                  f"gnorm {float(m['grad_norm']):.2f}", flush=True)
    checkpoint.save(args.ckpt, params, step=args.steps)
    dt = time.monotonic() - t0
    print(f"{args.steps} steps in {dt:.1f}s -> {args.ckpt}.npz")
    return 0


if __name__ == "__main__":
    sys.exit(main())
