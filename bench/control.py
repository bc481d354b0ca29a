"""The correctness check's two readings for one cell, on the chip.

    python3 bench/control.py --workload <cell> --seconds <s> --seeds 1,2,3

For each seed, in one process: one run of the cell (``harness.run_cell``)
at its own size and load, with its end-to-end metrics, then, over the
same sample of answered requests,

* ``program_gap``: the widest gap between the f32 reference's best logit
  and the served token's logit (the number ``correct`` compares);
* ``control_gap``: the same reference put in the program's place in fp8
  (``float8_e4m3fn`` operands, per-tensor scale): at each position the
  token fp8 puts first, and its gap under the f32 reference.

Each gap goes through :func:`bench.oracle.passes` with the cell's limit
(``check.logit_gap_limit`` in the mix file): ``program_correct`` has to
come out true and ``control_correct`` false.  The limit is set above the
largest ``program_gap`` over a dozen seeds or more and below the smallest
``control_gap``.  The benchmark's own runs do not run the control.
"""
import time

T_START = time.monotonic()

import argparse  # noqa: E402
import json  # noqa: E402
import sys  # noqa: E402
from pathlib import Path  # noqa: E402

ROOT = Path(__file__).resolve().parents[1]


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--seeds", required=True, help="comma-separated")
    args = ap.parse_args(argv)
    sys.path[:0] = [str(ROOT), str(ROOT / "src")]
    from bench import harness, oracle

    harness.use_compile_cache()
    for seed in [int(s) for s in args.seeds.split(",")]:
        result, run = harness.run_cell(args.workload, seed, args.seconds,
                                       False, time.monotonic())
        recs = oracle.sample(run)
        gaps, control, _ = oracle.reference_gaps(run, recs, with_control=True)
        limit = run.spec.mix["check"]["logit_gap_limit"]

        def correct(gap):
            return oracle.passes({"value": float(gap.max()), "limit": limit})
        print(json.dumps({
            "seed": seed, "tokens": int(len(gaps)), "requests": len(recs),
            "program_gap": float(gaps.max()),
            "control_gap": float(control.max()), "limit": limit,
            "program_correct": correct(gaps) and result["correct"],
            "control_correct": correct(control),
            "control_positions_over_program_max": int(
                (control > gaps.max()).sum()),
            "checks": result["checks"], "metrics": result["metrics"],
            "device": result["device"]}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
