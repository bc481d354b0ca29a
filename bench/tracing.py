"""From a profiler trace to device busy time, idle share and breakdown.

``load`` reads the ``.xplane.pb`` that ``jax.profiler`` wrote: the
operations on each device plane's ``XLA Ops`` line, and the benchmark's
own host spans (``bench.*`` annotations) per host thread.  ``reduce``
works on those plain lists, so it is tested on a small recorded trace:

* busy: the union of the operation intervals inside the ``bench.window``
  span, per device, averaged over the devices;
* ``device_ops``: the operations that took most device time in total;
* ``idle_gaps``: the longest intervals with no operation on the device,
  each named by the innermost ``bench.*`` spans open on the host at its
  middle (one per thread, joined by ``+``), or ``host-idle``.
"""
from __future__ import annotations

import glob
from pathlib import Path
from typing import Dict, List, Tuple

WINDOW_SPAN = "bench.window"
TOP = 10


def start(directory: Path) -> None:
    import jax
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0          # host spans only, no Python calls
    jax.profiler.start_trace(str(directory), profiler_options=opts)


def stop() -> None:
    import jax
    jax.profiler.stop_trace()


def load(directory: Path) -> Tuple[Dict[str, List[tuple]], List[tuple]]:
    """({device plane: [(op, start_ns, end_ns)]}, [(span, thread, start_ns,
    end_ns)]) from the newest trace under ``directory``."""
    from jax.profiler import ProfileData

    paths = sorted(glob.glob(f"{directory}/**/*.xplane.pb", recursive=True))
    if not paths:
        raise FileNotFoundError(f"no .xplane.pb under {directory}")
    data = ProfileData.from_file(paths[-1])
    devices: Dict[str, List[tuple]] = {}
    spans: List[tuple] = []
    for plane in data.planes:
        if plane.name.startswith("/device:"):
            ops = [(op_name(e.name), e.start_ns, e.start_ns + e.duration_ns)
                   for line in plane.lines if line.name == "XLA Ops"
                   for e in line.events]
            if ops:
                devices[plane.name] = ops
        elif plane.name.startswith("/host:"):
            # threads share a line name; the line's place tells them apart
            for i, line in enumerate(plane.lines):
                spans += [(e.name, f"{i}:{line.name}", e.start_ns,
                           e.start_ns + e.duration_ns)
                          for e in line.events if e.name.startswith("bench.")]
    return devices, spans


def op_name(text: str) -> str:
    """``%fusion.39`` of an HLO instruction's text (``%fusion.39 = ...``)."""
    return text.split(" = ", 1)[0]


def union(intervals: List[Tuple[float, float]]) -> List[Tuple[float, float]]:
    out: List[List[float]] = []
    for a, b in sorted(intervals):
        if out and a <= out[-1][1]:
            out[-1][1] = max(out[-1][1], b)
        else:
            out.append([a, b])
    return [(a, b) for a, b in out]


def label(spans: List[tuple], t: float) -> str:
    """Innermost benchmark span open at ``t`` on each host thread."""
    inner: Dict[str, tuple] = {}
    for name, thread, a, b in spans:
        if name != WINDOW_SPAN and a <= t < b:
            if thread not in inner or a > inner[thread][0]:
                inner[thread] = (a, name)
    names = sorted({n[len("bench."):] for _, n in inner.values()})
    return "+".join(names) or "host-idle"


def reduce(devices: Dict[str, List[tuple]], spans: List[tuple],
           n_devices: int = 1) -> dict:
    """busy_s, window_s, device_ops and idle_gaps (see the module doc)."""
    win = [(a, b) for name, _, a, b in spans if name == WINDOW_SPAN]
    if not win:
        raise ValueError(f"no {WINDOW_SPAN} span in the trace")
    w0, w1 = win[0]
    busy, per_op, gaps = 0.0, {}, []
    for ops in devices.values():
        clipped = [(max(a, w0), min(b, w1)) for _, a, b in ops
                   if b > w0 and a < w1]
        merged = union(clipped)
        busy += sum(b - a for a, b in merged)
        for name, a, b in ops:
            if b > w0 and a < w1:
                per_op[name] = per_op.get(name, 0.0) + min(b, w1) - max(a, w0)
        edges = [w0] + [x for iv in merged for x in iv] + [w1]
        gaps += [(edges[i], edges[i + 1]) for i in range(0, len(edges), 2)
                 if edges[i + 1] > edges[i]]
    gaps.sort(key=lambda g: g[0] - g[1])
    return {
        "busy_s": busy / max(n_devices, 1) / 1e9,
        "window_s": (w1 - w0) / 1e9,
        "device_ops": [[n, s / 1e9] for n, s in
                       sorted(per_op.items(), key=lambda kv: -kv[1])[:TOP]],
        "idle_gaps": [[label(spans, (a + b) / 2), (b - a) / 1e9]
                      for a, b in gaps[:TOP]],
    }
