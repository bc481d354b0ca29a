"""Vectored swap IO: preadv batch reads vs per-unit random reads, pwritev
batch writes, and the ftruncate fix for shrinking REAP rewrites."""
import os

import numpy as np
import pytest

from repro.core.manager import InstanceManager, ManagerConfig
from repro.core.swap import ReapFile, SwapFile
from repro.core.state import Rung


def _units(n, sz=256, seed=0):
    rng = np.random.default_rng(seed)
    return [((i,), rng.standard_normal(sz).astype(np.float32))
            for i in range(n)]


def test_swapfile_vectored_equals_per_unit(spool_dir):
    """read_units must return bit-identical data to read_unit, key by key."""
    f = SwapFile(f"{spool_dir}/v.swap")
    items = _units(64)
    f.write_units(items)
    per_unit = {k: f.read_unit(k) for k, _ in items}
    reads0 = f.reads
    batched = f.read_units([k for k, _ in items])
    assert set(batched) == set(per_unit)
    for k in per_unit:
        np.testing.assert_array_equal(batched[k], per_unit[k])
    # 64 contiguous extents merge into far fewer syscalls than 64 preads
    assert (f.reads - reads0) * 4 <= len(items)
    f.delete()


def test_reapfile_vectored_equals_per_unit(spool_dir):
    f = ReapFile(f"{spool_dir}/v.reap")
    items = _units(32, seed=1)
    f.write_batch(items)
    keys = [k for k, _ in items]
    batched = f.read_units(keys)
    for k, a in items:
        np.testing.assert_array_equal(batched[k], a)
        np.testing.assert_array_equal(f.read_unit(k), a)
    f.delete()


def test_vectored_read_of_gapped_subset(spool_dir):
    """Non-adjacent extents split into runs but stay correct."""
    f = SwapFile(f"{spool_dir}/g.swap")
    items = _units(30, seed=2)
    f.write_units(items)
    subset = [items[i][0] for i in range(0, 30, 3)]
    out = f.read_units(subset)
    assert set(out) == set(subset)
    for i in range(0, 30, 3):
        np.testing.assert_array_equal(out[items[i][0]], items[i][1])
    f.delete()


def test_vectored_read_unsorted_keys(spool_dir):
    """Keys arrive in arbitrary order; extents are sorted before merging."""
    f = SwapFile(f"{spool_dir}/u.swap")
    items = _units(16, seed=3)
    f.write_units(items)
    keys = [k for k, _ in items][::-1]
    reads0 = f.reads
    out = f.read_units(keys)
    assert f.reads - reads0 == 1          # still one merged run
    for k, a in items:
        np.testing.assert_array_equal(out[k], a)
    f.delete()


@pytest.mark.parametrize("tier", ["reap", "swap", "store"])
def test_short_writes_are_retried(spool_dir, monkeypatch, tier):
    """Linux writes at most ~2 GiB per call: every tier must loop until
    the whole unit is on disk (here each call is capped at 100 bytes)."""
    from repro.core.store import SwapStore

    real_v, real = os.pwritev, os.pwrite

    def capped_v(fd, bufs, offset):
        head, left = [], 100
        for b in bufs:
            if not left:
                break
            head.append(memoryview(b)[:left])
            left -= len(head[-1])
        return real_v(fd, head, offset)

    monkeypatch.setattr(os, "pwritev", capped_v)
    monkeypatch.setattr(os, "pwrite",
                        lambda fd, b, offset: real(fd, b[:100], offset))
    items = _units(8, seed=6)
    if tier == "reap":
        f = ReapFile(f"{spool_dir}/s.reap")
        f.write_batch(items)
    elif tier == "swap":
        f = SwapFile(f"{spool_dir}/s.swap")
        f.write_units(items)
    else:
        store = SwapStore(f"{spool_dir}/s.cas", salt=b"short-writes")
        f = store.client("t")
        f.write_units(items)
    out = f.read_units([k for k, _ in items])
    for k, a in items:
        np.testing.assert_array_equal(out[k], a)


def test_reap_shrinking_rewrite_truncates(spool_dir):
    """A smaller rewrite must not leave stale trailing bytes on disk:
    file_bytes tracks the real footprint the memory benchmarks report."""
    f = ReapFile(f"{spool_dir}/t.reap")
    f.write_batch(_units(32, seed=4))
    big = os.path.getsize(f.path)
    assert f.file_bytes == big
    f.write_batch(_units(4, seed=5))
    assert f.file_bytes == os.path.getsize(f.path) < big
    # and an empty working set clears the file entirely
    f.write_batch([])
    assert f.file_bytes == os.path.getsize(f.path) == 0
    assert not f.extents
    f.delete()


def test_instance_fault_path_is_vectored(tiny_factory, spool_dir):
    """HibernationManager.fault coalesces the whole fault set: restoring
    every unit of a deflated instance takes >=4x fewer syscalls than one
    pread per unit (the acceptance bar for the inflate path)."""
    mgr = InstanceManager(
        ManagerConfig(spool_dir=spool_dir, wake_mode="pagefault"),
        tiny_factory)
    inst = mgr.cold_start("i0", "llama3.2-3b")
    before = {k: v.copy() for k, v in inst.weights.items()}
    mgr.descend("i0", Rung.HIBERNATED)
    reads0 = inst.swap_file.reads + inst.reap_file.reads
    st = mgr.hib.fault(inst, inst.nonresident_keys())
    syscalls = inst.swap_file.reads + inst.reap_file.reads - reads0
    assert st.faults == len(inst.units)
    assert syscalls * 4 <= st.faults, \
        f"{syscalls} syscalls for {st.faults} faulted units"
    for k, v in before.items():
        np.testing.assert_array_equal(inst.weights[k], v)
