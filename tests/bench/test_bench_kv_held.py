"""The reader of the program's ``kv_held_bytes`` counter: the p95 over
the window's batches in MB, nothing from a program without the counter,
and a number from a run of a tiny cell."""
import json
import time
from types import SimpleNamespace

import numpy as np
import pytest

import bench_tiny

from bench import harness, traffic
from repro.serving import Request, Response

NAME = "kv_held_mb.p95"


def response(batch, held):
    req = Request("t0", f"s{batch}", np.zeros(4, np.int32), max_new_tokens=2)
    return Response(req, tokens=[0, 0], batch=batch, kv_held_bytes=held)


def synthetic_run(resps, late=()):
    """A window [0, 10] holding ``resps``, plus ``late`` ones due after it
    closed, and one request that failed inside it."""
    run = harness.Run(spec=None, seed=0, seconds=10.0, t_start=0.0,
                      t0=0.0, t1=10.0)

    def rec(resp, due):
        plan = traffic.Planned(0, np.zeros(4, np.int32), 2)
        return harness.Rec(plan, due=due, resp=resp,
                           error=None if resp else "RuntimeError: lost")

    run.records = [rec(r, 1.0 + i * 0.1) for i, r in enumerate(resps)]
    run.records += [rec(r, 11.0) for r in late] + [rec(None, 2.0)]
    return run


def test_reads_the_p95_over_batches_in_mb():
    """Batch 1's ten responses count once: over batches the values are
    1, 5, 6, 7 MB and the p95 is 7; counted per response it would be 6.
    The late response's 900 MB is outside the window."""
    resps = [response(1, 1_000_000) for _ in range(10)]
    resps += [response(b, mb * 1_000_000)
              for b, mb in ((2, 5), (3, 6), (4, 7))]
    run = synthetic_run(resps, late=[response(5, 900_000_000)])
    assert harness.load_reader(NAME)(run) == pytest.approx(7.0)


def test_reads_nothing_from_a_program_without_the_counter():
    """The parent's responses carry no ``kv_held_bytes``: the reader gives
    None, so a traced run leaves the metric out."""
    old = SimpleNamespace(tokens=[0, 0], spans={"e2e": 0.1}, faults=0,
                          batch=3, compiles=0, state_before="warm")
    assert harness.load_reader(NAME)(synthetic_run([old])) is None
    assert harness.load_reader(NAME)(synthetic_run([])) is None


def test_is_listed_for_the_resident_memory_it_moves():
    bench = json.loads((bench_tiny.REPO / "BENCHMARK.json").read_text())
    (m,) = [m for m in bench["per_layer"] if m["name"] == NAME]
    assert (m["unit"], m["better"], m["source"], m["moves"]) == \
        ("MB", "lower", "program_counter", "resident_gb")
    assert m["layer"] == "KV and state cache" and "workloads" not in m


def test_reads_a_number_from_a_tiny_run(tmp_path, monkeypatch):
    root = bench_tiny.make_root(tmp_path)
    h = bench_tiny.on_cpu(monkeypatch)
    result, run = h.run_cell(bench_tiny.HYBRID_CELL, 2**31 + 83, 1.5, False,
                             time.monotonic(), root=root)
    assert result["attempted"] > 0 and result["failed"] == 0
    value = h.load_reader(NAME, root)(run)
    assert value is not None and value >= 0
