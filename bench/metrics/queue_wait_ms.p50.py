"""Platform layer: median of submit-to-serve wait, the client's time from
submit to the answer minus the engine's ``spans["e2e"]`` (serve_batch)."""
from bench.stats import percentile


def read(run):
    return percentile([((r.done - r.sent) - r.resp.spans["e2e"]) * 1e3
                       for r in run.due() if r.ok], 50)
