"""The readers of the program's own spans and counters: known values on a
synthetic run, nothing (not an error) from a program without them, and a
number from each on a real run of a tiny cell."""
import time
from types import SimpleNamespace

import numpy as np
import pytest

import bench_tiny

from bench import harness, traffic
from repro.serving import Request, Response

#: the readers with a BENCHMARK.json entry, and those kept as files for
#: the wake cell
LISTED = ("kv_write_ms.p50", "prefix_register_ms.p50", "writeback_ms.p50",
          "decode_host_ms.p50", "dispatch_inflight.mean",
          "compiles_in_window")
FILES_ONLY = ("lock_wait_ms.p95", "wake_read_ms.p50", "wake_install_ms.p50")


def response(batch, steps, spans, **counters):
    req = Request("t0", f"s{batch}", np.zeros(4, np.int32),
                  max_new_tokens=steps + 1)
    return Response(req, tokens=[0] * (steps + 1), spans=dict(spans),
                    batch=batch, decode_steps=steps, **counters)


def synthetic_run(resps, late=()):
    """A window [0, 10] holding ``resps``, plus ``late`` ones due after
    it closed, and one request that failed inside it."""
    run = harness.Run(spec=None, seed=0, seconds=10.0, t_start=0.0,
                      t0=0.0, t1=10.0)

    def rec(resp, due):
        plan = traffic.Planned(0, np.zeros(4, np.int32),
                               len(resp.tokens) if resp else 2)
        return harness.Rec(plan, due=due, resp=resp,
                           error=None if resp else "RuntimeError: lost")

    run.records = [rec(r, 1.0 + i) for i, r in enumerate(resps)]
    run.records += [rec(r, 11.0) for r in late] + [rec(None, 2.0)]
    return run


#: two requests of one batch (2 and 4 ms spans, batch counters shared)
#: and one of another
RESPS = [
    response(7, 3, {"kv.write": 0.002, "prefix.register": 0.001,
                    "kv.writeback": 0.004, "decode.step": 0.030,
                    "decode.dispatch": 0.024, "serve.lock_wait": 0.0,
                    "wake": 0.5, "wake.read": 0.2, "wake.install": 0.1},
             dispatch_inflight=2.0, compiles=3),
    response(7, 3, {"kv.write": 0.004, "prefix.register": 0.003,
                    "kv.writeback": 0.004, "decode.step": 0.030,
                    "decode.dispatch": 0.024, "serve.lock_wait": 0.0},
             dispatch_inflight=2.0, compiles=3),
    response(8, 1, {"kv.write": 0.006, "prefix.register": 0.005,
                    "kv.writeback": 0.001, "decode.step": 0.012,
                    "decode.dispatch": 0.010, "serve.lock_wait": 0.9},
             dispatch_inflight=1.0, compiles=0),
]
LATE = [response(9, 1, {"kv.write": 9.0, "serve.lock_wait": 9.0},
                 dispatch_inflight=4.0, compiles=5)]
EXPECTED = {
    "kv_write_ms.p50": 4.0,
    "prefix_register_ms.p50": 3.0,
    "writeback_ms.p50": 4.0,
    "decode_host_ms.p50": 2.0,            # (30 - 24) / 3 and (12 - 10) / 1
    "dispatch_inflight.mean": 5.0 / 3,
    "compiles_in_window": 3,              # batch 7 once, batch 8 none
    "lock_wait_ms.p95": 900.0,
    "wake_read_ms.p50": 200.0,            # only the request that woke
    "wake_install_ms.p50": 100.0,
}


@pytest.mark.parametrize("name", LISTED + FILES_ONLY)
def test_reader_reads_known_spans_and_counters(name):
    value = harness.load_reader(name)(synthetic_run(RESPS, LATE))
    assert value == pytest.approx(EXPECTED[name])


@pytest.mark.parametrize("name", LISTED + FILES_ONLY)
def test_reader_reads_nothing_from_a_program_without_them(name):
    """A program whose responses carry only ``e2e`` and the older
    counters reads None, so a traced run leaves the metric out."""
    old = SimpleNamespace(tokens=[0, 0], spans={"e2e": 0.1}, faults=0,
                          state_before="warm")
    assert harness.load_reader(name)(synthetic_run([old])) is None


def test_listed_readers_are_in_benchmark_json_and_the_rest_are_not():
    import json
    bench = json.loads((bench_tiny.REPO / "BENCHMARK.json").read_text())
    names = {m["name"] for m in bench["per_layer"]}
    assert set(LISTED) <= names and not set(FILES_ONLY) & names


def test_readers_read_a_number_from_a_tiny_run(tmp_path, monkeypatch):
    root = bench_tiny.make_root(tmp_path)
    h = bench_tiny.on_cpu(monkeypatch)
    result, run = h.run_cell(bench_tiny.HYBRID_CELL, 2**31 + 79, 1.5, False,
                             time.monotonic(), root=root)
    assert result["attempted"] > 0 and result["failed"] == 0
    values = {n: h.load_reader(n, root)(run) for n in LISTED}
    assert all(v is not None for v in values.values()), values
    assert values["dispatch_inflight.mean"] >= 1.0
    assert values["kv_write_ms.p50"] > 0 and values["decode_host_ms.p50"] > 0
    assert isinstance(values["compiles_in_window"], int)
