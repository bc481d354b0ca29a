"""Streamed wake pipeline: critical-prefix contract, concurrent
wake/fault/deflate races, lookahead-prefetch correctness, and the
chunk-granular streaming readers it is built on.

The invariant under every interleaving: restored state is byte-identical
to the synchronous wake path.
"""
import threading

import numpy as np
import pytest

from repro.core.inflate import (InflatorPool, critical_wake_keys,
                                is_critical_key)
from repro.core.manager import InstanceManager, ManagerConfig
from repro.core.pool import PagePool
from repro.core.reap import ReapRecorder
from repro.core.state import ContainerState
from repro.core.swap import SwapFile
from repro.serving.engine import Request, ServingEngine
from repro.core.state import Rung

S = ContainerState


def _mk(tiny_factory, spool_dir, *, pipelined=True, chunk=16 << 10,
        dedup=True, lookahead=True):
    mgr = InstanceManager(
        ManagerConfig(spool_dir=spool_dir, wake_mode="reap",
                      pipelined_wake=pipelined, wake_chunk_bytes=chunk,
                      dedup_store=dedup, lookahead=lookahead), tiny_factory)
    return ServingEngine(mgr), mgr


def _req(iid, sid, toks, n=1, **kw):
    return Request(iid, sid, np.asarray(toks, np.int32),
                   max_new_tokens=n, **kw)


def _record_everything(eng, inst):
    """Fatten the REAP file: working set = every unit + all live KV."""
    inst.recorder.start()
    inst.recorder.record_many(inst.units)
    if inst.kv is not None:
        for sid in inst.kv.sessions:
            inst.recorder.record_many(inst.kv.keys_for(sid))
    inst.recorder.stop()


# ---------------------------------------------------------------- recorder

def test_recorder_preserves_first_touch_order():
    r = ReapRecorder()
    r.start()
    for k in ("c", "a", "b", "a"):
        r.record(k)
    r.stop()
    assert r.ordered_working_set == ("c", "a", "b")
    assert isinstance(r.working_set, frozenset)
    # a later session appends new keys but never reorders old ones
    r.start()
    r.record_many(["x", "a"])
    r.stop()
    assert r.ordered_working_set == ("c", "a", "b", "x")


def test_reap_file_written_in_touch_order(tiny_factory, spool_dir):
    eng, mgr = _mk(tiny_factory, spool_dir)
    inst = eng.start_instance("i0", "llama3.2-3b")
    eng.record_sample("i0", _req("i0", "probe", [1, 2, 3],
                                 close_session=True))
    mgr.descend("i0", Rung.HIBERNATED)
    order = {k: i for i, k in
             enumerate(inst.recorder.ordered_working_set)}
    file_keys = [k for k in inst.reap_file.extents if k in order]
    assert file_keys == sorted(file_keys, key=order.__getitem__)


# ---------------------------------------------------------------- contract

def test_critical_prefix_resident_at_wake_return(tiny_factory, spool_dir):
    """``wake()`` (pipelined) returns with every prefill-critical unit
    resident; the tail drains to exactly the synchronous restore."""
    eng, mgr = _mk(tiny_factory, spool_dir)
    inst = eng.start_instance("i0", "arctic-480b")
    before = {k: v.copy() for k, v in inst.weights.items()}
    eng.record_sample("i0", _req("i0", "probe", [1, 2, 3, 4],
                                 close_session=True))
    _record_everything(eng, inst)
    mgr.descend("i0", Rung.HIBERNATED)

    st = mgr.ensure_awake("i0", trigger="sigcont", priority="high")
    assert st is not None and st.pipelined
    crit = critical_wake_keys(inst)
    assert crit and all(k in inst.resident for k in crit)
    assert st.critical_path_seconds > 0
    # expert units are tail, not critical
    assert any(not is_critical_key(k) for k in inst.reap_file.extents)

    pipe = inst.wake_pipeline
    assert pipe is not None and pipe.wait(60)
    # after the tail drains, every weight unit in the REAP file is resident
    assert all(k in inst.resident
               for k in inst.reap_file.extents if k[0] == "w")
    inst.ensure_all_resident()
    for k, v in before.items():
        np.testing.assert_array_equal(inst.weights[k], v)
    stats = pipe.stats
    assert stats.io_seconds > 0 and stats.inflate_seconds > 0


def test_wake_storm_mid_stream(tiny_factory, spool_dir):
    """A storm against one tenant mid-stream: one pipeline, every request
    served correctly, restored weights bit-exact."""
    eng, mgr = _mk(tiny_factory, spool_dir, chunk=4 << 10)
    inst = eng.start_instance("i0", "arctic-480b")
    before = {k: v.copy() for k, v in inst.weights.items()}
    eng.record_sample("i0", _req("i0", "probe", [1, 2, 3],
                                 close_session=True))
    _record_everything(eng, inst)

    # baseline tokens from the synchronous path
    eng_s, mgr_s = _mk(tiny_factory, spool_dir + "/sync", pipelined=False)
    eng_s.start_instance("i0", "arctic-480b")
    want = eng_s.handle(_req("i0", "s0", [7, 8, 9])).tokens

    mgr.descend("i0", Rung.HIBERNATED)
    n = 6
    barrier = threading.Barrier(n)
    resps = [None] * n

    def hit(i):
        barrier.wait()
        resps[i] = eng.handle(_req("i0", f"s{i}", [7, 8, 9],
                                   close_session=True))

    threads = [threading.Thread(target=hit, args=(i,)) for i in range(n)]
    for t in threads:
        t.start()
    for t in threads:
        t.join()

    assert mgr.wakes_performed == 1
    assert all(r.tokens == want for r in resps)
    if inst.wake_pipeline is not None:
        assert inst.wake_pipeline.wait(60)
    inst.ensure_all_resident()
    for k, v in before.items():
        np.testing.assert_array_equal(inst.weights[k], v)


def test_deflate_mid_stream_drains_safely(tiny_factory, spool_dir):
    """Deflate while the tail is still inflating: the stream cancels,
    drains, and NO working-set byte is lost across the re-deflate."""
    eng, mgr = _mk(tiny_factory, spool_dir, chunk=2 << 10)
    inst = eng.start_instance("i0", "arctic-480b")
    before = {k: v.copy() for k, v in inst.weights.items()}
    eng.record_sample("i0", _req("i0", "probe", [1, 2],
                                 close_session=True))
    _record_everything(eng, inst)
    mgr.descend("i0", Rung.HIBERNATED)

    # low-priority anticipatory wake -> immediately deflate mid-stream
    mgr.predictive_wake("i0")
    pipe = inst.wake_pipeline
    assert pipe is not None
    mgr.descend("i0", Rung.HIBERNATED)                        # cancels + drains + restores
    assert not pipe.active
    assert inst.wake_pipeline is None
    assert inst.state == S.HIBERNATE

    # everything must still be restorable, bit-exact
    mgr.hib.wake(inst, mode="reap", trigger="sigcont")
    inst.ensure_all_resident()
    for k, v in before.items():
        np.testing.assert_array_equal(inst.weights[k], v)


def test_partial_residency_deflate_loses_nothing(tiny_factory, spool_dir):
    """White-box leftover restore: deflate an instance whose REAP file
    holds units that were never re-inflated (the deterministic analogue
    of a cancelled stream) — the rewrite must not drop them."""
    eng, mgr = _mk(tiny_factory, spool_dir)
    inst = eng.start_instance("i0", "llama3.2-3b")
    before = {k: v.copy() for k, v in inst.weights.items()}
    _record_everything(eng, inst)
    mgr.descend("i0", Rung.HIBERNATED)
    assert inst.reap_file.extents

    # wake WITHOUT restoring (pagefault-style), fault in only a few units
    mgr.hib.wake(inst, mode="pagefault", trigger="sigcont")
    some = list(inst.reap_file.extents)[:2]
    inst.fault_in([k for k in some if k[0] == "w"])
    assert len(inst.resident) < len(inst.units)

    mgr.descend("i0", Rung.HIBERNATED)                        # must restore leftovers first
    mgr.hib.wake(inst, mode="reap", trigger="sigcont")
    inst.ensure_all_resident()
    for k, v in before.items():
        np.testing.assert_array_equal(inst.weights[k], v)


def test_lookahead_prefetch_matches_synchronous(tiny_factory, spool_dir):
    """Lookahead prefetch (mid-decode fault -> async next-layer pull) must
    leave faulted array contents identical to the synchronous path —
    tokens and final KV stream included."""
    outs = {}
    for name, pipelined in (("sync", False), ("pipe", True)):
        eng, mgr = _mk(tiny_factory, spool_dir + f"/{name}",
                       pipelined=pipelined, chunk=4 << 10,
                       lookahead=pipelined)
        inst = eng.start_instance("i0", "llama3.2-3b")
        # a session with history: its pages fault (and look ahead) on resume
        eng.handle(_req("i0", "chat", list(range(1, 24)), n=2))
        eng.record_sample("i0", _req("i0", "probe", [1, 2],
                                     close_session=True))
        _record_everything(eng, inst)
        mgr.descend("i0", Rung.HIBERNATED)
        r = eng.handle(_req("i0", "chat", [30, 31], n=3))
        if inst.wake_pipeline is not None:
            assert inst.wake_pipeline.wait(60)
        inst.quiesce_bg()
        kv = inst.kv
        sess = kv.sessions["chat"]
        mgr.hib.fault(inst, kv.keys_for("chat"))   # everything resident
        stream = np.concatenate(
            [kv.read_tokens("chat", lyr, sess.num_tokens)
             for lyr in range(inst.cfg.num_layers)])
        outs[name] = (r.tokens, stream)
    assert outs["sync"][0] == outs["pipe"][0]
    np.testing.assert_array_equal(outs["sync"][1], outs["pipe"][1])


def test_demand_pull_from_another_thread(tiny_factory, spool_dir):
    """A fault arriving mid-stream demand-pulls exactly its chunk and
    returns correct bytes while the streamer owns the rest."""
    eng, mgr = _mk(tiny_factory, spool_dir, chunk=2 << 10)
    inst = eng.start_instance("i0", "arctic-480b")
    before = {k: v.copy() for k, v in inst.weights.items()}
    _record_everything(eng, inst)
    mgr.descend("i0", Rung.HIBERNATED)
    mgr.predictive_wake("i0")                # low priority: slow stream
    pipe = inst.wake_pipeline
    tail = [k for k in inst.reap_file.extents if not is_critical_key(k)]
    assert tail
    st = mgr.hib.fault(inst, tail[:4])
    assert all(k in inst.resident for k in tail[:4])
    assert st.faulted_bytes >= 0
    assert pipe.wait(60)
    inst.ensure_all_resident()
    for k, v in before.items():
        np.testing.assert_array_equal(inst.weights[k], v)


# ---------------------------------------------------------------- plumbing

def test_swap_file_streaming_iter(tmp_path):
    f = SwapFile(str(tmp_path / "x.swap"))
    rng = np.random.default_rng(0)
    items = [((i,), rng.standard_normal(64).astype(np.float32))
             for i in range(16)]
    f.write_units(items)
    keys = [k for k, _ in items]
    whole = f.read_units(keys)
    seen = {}
    chunks = 0
    for batch in f.read_units_iter(keys, chunk_bytes=512):
        seen.update(batch)
        chunks += 1
    assert chunks > 1
    assert set(seen) == set(whole)
    for k in keys:
        np.testing.assert_array_equal(seen[k], whole[k])
    f.delete()


def test_store_client_streaming_iter(tiny_factory, spool_dir):
    eng, mgr = _mk(tiny_factory, spool_dir)
    inst = eng.start_instance("i0", "llama3.2-3b")
    mgr.descend("i0", Rung.HIBERNATED)                         # no working set -> all store
    keys = list(inst.swap_file.extents)
    whole = inst.swap_file.read_units(keys)
    seen = {}
    for batch in inst.swap_file.read_units_iter(keys, chunk_bytes=8 << 10):
        seen.update(batch)
    assert set(seen) == set(whole)
    for k in keys:
        np.testing.assert_array_equal(seen[k], whole[k])


@pytest.fixture()
def tpu_interpret():
    """The CPU has no Mosaic backend: run the pool's Pallas kernel in
    interpret mode."""
    from jax.experimental.pallas import tpu as pltpu
    with pltpu.force_tpu_interpret_mode():
        yield


def test_pool_scatter_kernel_matches_numpy(tpu_interpret):
    pool = PagePool(256, np.float32, capacity_pages=64)
    pages = pool.alloc(8, "t0")
    rng = np.random.default_rng(1)
    rows = rng.standard_normal((8, 256)).astype(np.float32)
    pool.scatter(pages, rows)                       # numpy path
    np_data = pool.data.copy()
    pool.data[:] = 0
    pool.scatter(pages, rows, use_kernel=True)      # Pallas kernel path
    np.testing.assert_array_equal(pool.data, np_data)
    assert pool.scatter_calls == 2


def test_inflator_pool_runs_and_sheds_idle_workers():
    pool = InflatorPool(max_workers=2, idle_s=0.1)
    futs = [pool.submit(lambda x: x * x, i) for i in range(8)]
    assert [f.result(10) for f in futs] == [i * i for i in range(8)]
    import time
    deadline = time.monotonic() + 5.0
    while pool._workers and time.monotonic() < deadline:
        time.sleep(0.05)
    assert pool._workers == 0

    err = pool.submit(lambda: 1 / 0)
    with pytest.raises(ZeroDivisionError):
        err.result(10)
