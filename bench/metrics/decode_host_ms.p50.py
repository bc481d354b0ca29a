"""Engine layer: median over the answered requests due in the window of
the host time of one decode step outside its dispatch, (``decode.step`` -
``decode.dispatch``) / ``decode_steps``: embedding faults, token emission
and the next step's token upload."""
from bench.spans import answered
from bench.stats import percentile


def read(run):
    return percentile([
        (r.spans["decode.step"] - r.spans["decode.dispatch"]) * 1e3
        / r.decode_steps for r in answered(run)
        if getattr(r, "decode_steps", 0) and "decode.step" in r.spans], 50)
