"""Manager / ladder layer: share of answered requests whose tenant was
hibernated or partially deflated when the request was served, in %."""


def read(run):
    ok = [r for r in run.due() if r.resp is not None]
    if not ok:
        return None
    woke = sum(r.resp.state_before in ("hibernate", "partial") for r in ok)
    return 100.0 * woke / len(ok)
