"""The serving path's own spans and counters: ``Response.spans`` holds a
per-request breakdown at every layer boundary, the counters beside it add
up, and the same spans reach the profiler on the serving threads."""
import glob
import os
import sys
import threading
import time

import jax
import numpy as np
import pytest

from repro.core.manager import InstanceManager, ManagerConfig
from repro.core.state import Rung
from repro.serving import AsyncPlatform, PlatformPolicy, Request, ServingEngine

ARCH = "llama3.2-3b"
#: each parent span and the spans nested inside it
CHILDREN = {
    "e2e": ("serve.prefill", "serve.decode"),
    "serve.prefill": ("prefill.fault", "prefill.dispatch", "kv.write",
                      "prefix.register"),
    "serve.decode": ("decode.fault", "kv.gather", "decode.step",
                     "kv.writeback"),
    "decode.step": ("decode.dispatch",),
}
SPANS = {"serve.queue", "serve.lock_wait"} | set(CHILDREN) | {
    c for cs in CHILDREN.values() for c in cs}


def _platform(tiny_factory, spool_dir, tenants=("t0",)):
    mgr = InstanceManager(ManagerConfig(spool_dir=spool_dir), tiny_factory)
    eng = ServingEngine(mgr)
    for t in tenants:
        eng.start_instance(t, ARCH)
    plat = AsyncPlatform(eng, PlatformPolicy(keep_warm_s=1e9),
                         {t: ARCH for t in tenants}, workers=2)
    return mgr, eng, plat


def _req(sid, first, n=8, new=4, tenant="t0"):
    # distinct prompts: a repeated one would adopt the registered prefix
    return Request(tenant, sid, np.arange(first, first + n, dtype=np.int32),
                   max_new_tokens=new, close_session=True)


def _host_weight_bytes(inst):
    return sum(x.nbytes for x in jax.tree.leaves(inst.params_pytree())
               if isinstance(x, np.ndarray))


def test_warm_request_carries_every_span_and_its_counters(tiny_factory,
                                                          spool_dir):
    mgr, eng, plat = _platform(tiny_factory, spool_dir)
    with plat:
        first = plat.submit(_req("a", 1)).result(timeout=300)
        repeat = plat.submit(_req("b", 11)).result(timeout=300)
    weights = _host_weight_bytes(mgr.instances["t0"])
    for r in (first, repeat):
        assert SPANS <= set(r.spans), SPANS - set(r.spans)
        for parent, kids in CHILDREN.items():
            for k in kids:
                assert r.spans[k] <= r.spans[parent], (k, parent)
            assert sum(r.spans[k] for k in kids) <= r.spans[parent]
        assert r.decode_steps == len(r.tokens) - 1 == 3
        # one prefill and one decode dispatch per step, each handed the
        # tenant's host weights
        assert r.h2d_bytes == weights * (1 + r.decode_steps)
        assert r.dispatch_inflight >= 1.0
    assert first.batch != repeat.batch
    assert first.compiles >= 1      # a new tenant's steps compile
    assert repeat.compiles == 0     # the same shapes again: nothing does


def test_lock_wait_times_a_tenant_held_by_another_thread(tiny_factory,
                                                         spool_dir):
    mgr, eng, plat = _platform(tiny_factory, spool_dir)
    with plat:
        plat.submit(_req("warm", 1)).result(timeout=300)
        with eng.instance_lock("t0"):
            fut = plat.submit(_req("held", 21))
            time.sleep(0.3)
        resp = fut.result(timeout=300)
    assert resp.spans["serve.lock_wait"] >= 0.2
    # e2e starts once the lock is held: the wait is not part of it
    assert resp.spans["e2e"] < resp.spans["serve.lock_wait"]


def test_hibernated_tenant_reads_its_wake_stages(tiny_factory, spool_dir):
    mgr, eng, plat = _platform(tiny_factory, spool_dir)
    eng.record_sample("t0", _req("probe", 1, new=2))
    mgr.descend("t0", Rung.HIBERNATED)
    with plat:
        resp = plat.submit(_req("woken", 31)).result(timeout=300)
    assert resp.state_before == "hibernate"
    for name in ("wake", "wake.read", "wake.install"):
        assert resp.spans[name] > 0, name


def test_profiler_sees_the_spans_on_the_serving_threads(tiny_factory,
                                                        spool_dir, tmp_path):
    from jax.profiler import ProfileData

    mgr, eng, plat = _platform(tiny_factory, spool_dir)
    with plat:
        plat.submit(_req("warm", 1)).result(timeout=300)
        jax.profiler.start_trace(str(tmp_path))
        try:
            with jax.profiler.TraceAnnotation("test.caller"):
                resp = plat.submit(_req("traced", 41)).result(timeout=300)
        finally:
            jax.profiler.stop_trace()
    (path,) = glob.glob(f"{tmp_path}/**/*.xplane.pb", recursive=True)
    events = []                         # (name, line, start, end, stats)
    for plane in ProfileData.from_file(path).planes:
        if plane.name.startswith("/host:"):
            for i, line in enumerate(plane.lines):
                events += [(e.name, i, e.start_ns, e.start_ns + e.duration_ns,
                            dict(e.stats)) for e in line.events]
    (caller,) = [e for e in events if e[0] == "test.caller"]
    ours = [e for e in events
            if e[0].split(".")[0] in ("serve", "prefill", "prefix", "decode",
                                      "kv")]
    traced = {e[0] for e in ours}
    assert SPANS - {"e2e", "serve.queue"} <= traced
    for name, line, a, b, stats in ours:
        assert line != caller[1], name          # on a serving thread
        assert stats["tenant"] == "t0" and stats["batch"] == resp.batch
        assert caller[2] <= a <= b <= caller[3], name   # one clock
    assert {e[4].get("session") for e in ours
            if e[0].startswith("prefill.")} == {"traced"}


@pytest.mark.parametrize("rung", [Rung.HIBERNATED, Rung.MMAP_CLEAN])
def test_deflate_is_traced_without_a_request(tiny_factory, spool_dir,
                                             tmp_path, rung):
    from jax.profiler import ProfileData

    mgr, eng, _ = _platform(tiny_factory, spool_dir)
    eng.record_sample("t0", _req("probe", 1, new=2))
    jax.profiler.start_trace(str(tmp_path))
    try:
        mgr.descend("t0", rung)
    finally:
        jax.profiler.stop_trace()
    (path,) = glob.glob(f"{tmp_path}/**/*.xplane.pb", recursive=True)
    stats = [dict(e.stats) for plane in ProfileData.from_file(path).planes
             for line in plane.lines for e in line.events
             if e.name == "ladder.deflate"]
    assert stats == [{"tenant": "t0", "rung": rung.name.lower()}]


def test_concurrent_batches_count_each_other_in_flight(tiny_factory,
                                                       spool_dir):
    """Two tenants served on two workers at once: each batch's responses
    carry only their own batch id, and the in-flight mean stays within
    the number of workers."""
    mgr, eng, plat = _platform(tiny_factory, spool_dir, ("t0", "t1"))
    barrier = threading.Barrier(2)
    futs = {}

    def go(t, first):
        barrier.wait()
        futs[t] = plat.submit(_req(f"c{t}", first, tenant=t))

    with plat:
        ths = [threading.Thread(target=go, args=(t, f))
               for t, f in (("t0", 51), ("t1", 61))]
        for th in ths:
            th.start()
        for th in ths:
            th.join()
        resps = {t: f.result(timeout=300) for t, f in futs.items()}
    assert resps["t0"].batch != resps["t1"].batch
    for r in resps.values():
        assert 1.0 <= r.dispatch_inflight <= 2.0
        assert r.decode_steps == 3


def test_in_flight_count_holds_under_many_threads(tiny_factory, spool_dir):
    """More threads than cores entering and leaving dispatches at a short
    switch interval: the process-wide count returns to zero, and no
    dispatch saw fewer than itself or more than every thread in flight."""
    from repro.serving.engine import _Batch

    eng = ServingEngine(InstanceManager(ManagerConfig(spool_dir=spool_dir),
                                        tiny_factory))
    n, rounds = 2 * len(os.sched_getaffinity(0)), 300
    batches = [_Batch("t0", i, []) for i in range(n)]

    def work(b):
        for _ in range(rounds):
            with eng._dispatch(b, "decode.dispatch", (), b.ids()):
                pass

    old = sys.getswitchinterval()
    sys.setswitchinterval(1e-6)
    try:
        ths = [threading.Thread(target=work, args=(b,)) for b in batches]
        for th in ths:
            th.start()
        for th in ths:
            th.join(timeout=60)
    finally:
        sys.setswitchinterval(old)
    assert not any(th.is_alive() for th in ths)
    assert eng._inflight == 0
    counts = [c for b in batches for c in b.inflight]
    assert len(counts) == n * rounds
    assert min(counts) >= 1 and max(counts) <= n
