"""The trace reduction, on a recorded slice and on a fresh CPU trace."""
import json

import numpy as np
import pytest

from bench_tiny import REPO

from bench import tracing

SLICE = json.loads((REPO / "tests/bench/data/trace_slice.json").read_text())


def brute_busy(ops, w0, w1, step=1000):
    t = np.arange(w0, w1, step, dtype=np.float64) + step / 2
    busy = np.zeros(t.shape, bool)
    for _, a, b in ops:
        busy |= (t >= a) & (t < b)
    return busy.sum() * step


def test_reduce_on_a_recorded_slice():
    devices = {k: [tuple(o) for o in v] for k, v in SLICE["devices"].items()}
    spans = [tuple(s) for s in SLICE["spans"]]
    out = tracing.reduce(devices, spans)
    (w0, w1), = [(a, b) for n, _, a, b in spans if n == tracing.WINDOW_SPAN]
    assert out["window_s"] == pytest.approx((w1 - w0) / 1e9)
    (ops,) = devices.values()
    assert out["busy_s"] == pytest.approx(brute_busy(ops, w0, w1) / 1e9,
                                          rel=2e-3)
    assert 0 < out["busy_s"] < out["window_s"]
    # the longest idle gap is the upload between two prefill dispatches
    name, secs = out["idle_gaps"][0]
    assert name.startswith("dispatch.prefill") and secs > 0.01
    assert len(out["device_ops"]) == tracing.TOP
    assert out["device_ops"][0][1] >= out["device_ops"][-1][1]
    assert sum(s for _, s in out["idle_gaps"]) <= \
        out["window_s"] - out["busy_s"] + 1e-9


def test_union_and_labels():
    assert tracing.union([(0, 2), (1, 3), (5, 6)]) == [(0, 3), (5, 6)]
    spans = [("bench.window", "0:a", 0, 10), ("bench.wake", "1:b", 2, 8),
             ("bench.dispatch.decode", "1:b", 3, 4),
             ("bench.kv_gather", "2:c", 1, 9)]
    assert tracing.label(spans, 3.5) == "dispatch.decode+kv_gather"
    assert tracing.label(spans, 9.5) == "host-idle"


def test_load_reads_a_cpu_trace(tmp_path):
    import jax
    import jax.numpy as jnp

    f = jax.jit(lambda x: (x @ x).sum())
    x = jnp.ones((128, 128))
    f(x).block_until_ready()
    tracing.start(tmp_path)
    try:
        with jax.profiler.TraceAnnotation(tracing.WINDOW_SPAN):
            with jax.profiler.TraceAnnotation("bench.dispatch.decode"):
                f(x).block_until_ready()
    finally:
        tracing.stop()
    devices, spans = tracing.load(tmp_path)
    names = {s[0] for s in spans}
    assert {tracing.WINDOW_SPAN, "bench.dispatch.decode"} <= names
    out = tracing.reduce(devices, spans)
    assert out["window_s"] > 0
