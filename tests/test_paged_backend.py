"""Kernel-over-pool equivalence: the Pallas paged_attention kernel, fed
directly from bitmap-allocator pages, matches the engine's dense-gather
decode attention on live session state — including after a hibernate/wake
cycle (pages re-allocated at different physical ids)."""
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core.manager import InstanceManager, ManagerConfig
from repro.models.attention import decode_attention
from repro.serving import Request, ServingEngine
from repro.serving.paged_backend import paged_decode
from repro.core.state import Rung


@pytest.fixture()
def served_instance(tiny_factory, spool_dir):
    mgr = InstanceManager(ManagerConfig(spool_dir=spool_dir), tiny_factory)
    eng = ServingEngine(mgr)
    inst = eng.start_instance("i0", "llama3.2-3b")
    for j, n in enumerate((5, 9, 17)):
        eng.handle(Request("i0", f"s{j}", np.arange(n) % inst.cfg.vocab_size,
                           max_new_tokens=3))
    return eng, mgr, inst


def _dense_reference(inst, sids, layer, q):
    kv = inst.kv
    cfg = inst.cfg
    B = len(sids)
    S = max(kv.sessions[s].num_tokens for s in sids)
    Hkv, D = cfg.num_kv_heads, cfg.head_dim
    k = np.zeros((B, S, Hkv, D), np.float32)
    v = np.zeros((B, S, Hkv, D), np.float32)
    pos = np.full((B, S), -1, np.int32)
    lengths = np.zeros((B,), np.int32)
    for b, sid in enumerate(sids):
        n = kv.sessions[sid].num_tokens
        data = kv.read_tokens(sid, layer, n).reshape(n, 2, Hkv, D)
        k[b, :n], v[b, :n] = data[:, 0], data[:, 1]
        pos[b, :n] = np.arange(n)
        lengths[b] = n
    return decode_attention(q, jnp.asarray(k), jnp.asarray(v),
                            jnp.asarray(pos), jnp.asarray(lengths))


def test_kernel_matches_dense_on_pool(served_instance):
    eng, mgr, inst = served_instance
    sids = ["s0", "s1", "s2"]
    cfg = inst.cfg
    rng = np.random.default_rng(0)
    q = jnp.asarray(rng.standard_normal(
        (3, cfg.num_heads, cfg.head_dim)), jnp.float32)
    for layer in (0, cfg.num_layers - 1):
        out = paged_decode(inst.kv, sids, layer, q, interpret=True)
        ref = _dense_reference(inst, sids, layer, q)
        np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                                   rtol=2e-5, atol=2e-5)


def test_kernel_survives_hibernation(served_instance):
    """After deflate + fault-in, physical page ids change but the kernel's
    page-table view must produce identical attention."""
    eng, mgr, inst = served_instance
    sids = ["s0", "s1", "s2"]
    cfg = inst.cfg
    q = jnp.asarray(np.random.default_rng(1).standard_normal(
        (3, cfg.num_heads, cfg.head_dim)), jnp.float32)
    before = paged_decode(inst.kv, sids, 0, q, interpret=True)
    mgr.descend("i0", Rung.HIBERNATED)
    keys = [k for s in sids for k in inst.kv.keys_for(s)]
    mgr.hib.fault(inst, inst.kv.nonresident_keys(keys))
    after = paged_decode(inst.kv, sids, 0, q, interpret=True)
    np.testing.assert_allclose(np.asarray(after), np.asarray(before),
                               rtol=1e-6, atol=1e-6)
