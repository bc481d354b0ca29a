"""Find the highest open-loop rate a cell sustains without a growing
backlog: one set-up, then one window per rate, in one process.

    python3 bench/sweep.py --workload <cell> --seed <n> --seconds <s> \\
        --rates 1,2,4,8

For each rate it prints the requests due and answered, the backlog left
when the window closed, the median time to first token of the window's
first and last thirds (a backlog that grows shows as a last third much
slower than the first), and the decode batch sizes the engine ran.  A
cell's rate is then set as a number in its mix file; the benchmark's own
runs never search for it.
"""
import time

T_START = time.monotonic()

import argparse  # noqa: E402
import copy  # noqa: E402
import json  # noqa: E402
import sys  # noqa: E402
from pathlib import Path  # noqa: E402

ROOT = Path(__file__).resolve().parents[1]


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--rates", required=True, help="comma-separated req/s")
    args = ap.parse_args(argv)
    sys.path[:0] = [str(ROOT), str(ROOT / "src")]
    from bench import harness, traffic
    from bench.stats import percentile

    harness.use_compile_cache()
    spec = harness.load_spec(args.workload)
    device = harness.require_chip(spec)
    run = harness.Run(spec, args.seed, args.seconds, T_START, device=device)
    base = traffic.Traffic(spec.mix, spec.conf["vocab_size"], args.seed)
    system = harness.System(run, ROOT / ".bench_spool")
    try:
        harness.set_up(run, system, base)
        system.plat.start()
        for rate in [float(r) for r in args.rates.split(",")]:
            mix = copy.deepcopy(spec.mix)
            mix["arrivals"]["rate_per_s"] = rate
            tfc = traffic.Traffic(mix, spec.conf["vocab_size"], args.seed)
            w = harness.Run(spec, args.seed, args.seconds, T_START,
                            device=device, probes=run.probes)
            futs = harness.window(w, system, tfc)
            backlog = sum(not f.done() for f in futs)
            harness.drain(w, futs)
            due = w.due()
            third = max(1, len(due) // 3)
            ttft = [(r.token_times[0] - r.due) * 1e3 if r.ok else None
                    for r in due]

            def med(xs):
                return percentile([x for x in xs if x is not None], 50)
            batches = sorted({B for k, B, _, _, t0, _ in run.probes.dispatches
                              if k == "decode" and w.in_window(t0)})
            print(json.dumps({
                "rate": rate, "due": len(due),
                "answered": sum(r.ok for r in due), "backlog_at_close": backlog,
                "ttft_ms_first_third_p50": med(ttft[:third]),
                "ttft_ms_last_third_p50": med(ttft[-third:]),
                "ttft_ms_p95": percentile([x for x in ttft if x is not None],
                                          95),
                "decode_batches": batches}), flush=True)
        system.plat.stop(drain=False, timeout=harness.DRAIN_S)
    finally:
        system.close()
    return 0


if __name__ == "__main__":
    sys.exit(main())
