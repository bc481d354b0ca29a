"""Model step: model FLOPs of the prompt and output tokens the window
processed, over the window times the chip's bf16 peak, in %.  A prompt
counts when its first token came in the window; each later token is one
decode position."""


def read(run):
    conf, fl = run.conf, run.flops
    total = 0.0
    for r in run.due():
        S = len(r.plan.prompt)
        for k, t in enumerate(r.token_times):
            if not run.in_window(t):
                continue
            if k == 0:
                total += sum(fl.token_flops(conf, p) for p in range(S))
            else:
                total += fl.token_flops(conf, S + k - 1)
            total += fl.head_flops(conf)
    if not total:
        return None
    return 100.0 * total / ((run.t1 - run.t0) * run.peaks["bf16_flops_per_s"])
