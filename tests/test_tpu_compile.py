"""Compile the main path's kernels and phi4-mini-3.8b's serving steps for a
described TPU v5e chip.

Nothing runs: the TPU compiler refuses here what the chip would refuse
(unaligned blocks, unsupported Mosaic ops, too much VMEM or HBM), at no
chip time.  The topology is described inside a fixture, never at import,
so every pytest-xdist worker collects the same tests and only the worker
given this file loads the TPU library.
"""
import os

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.configs import get_config
from repro.core.manager import ManagerConfig
from repro.kernels.page_copy import ops as pc_ops
from repro.kernels.paged_attention import ops as pa_ops
from repro.kernels.ssd_scan import ops as ssd_ops
from repro.models import model
from repro.serving.engine import _make_decode, _make_prefill

HBM_BYTES = 16 * 10**9          # one v5e chip


@pytest.fixture(scope="module")
def one_chip():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    # a compile for a described chip is written to the persistent cache
    # but cannot be read back without the chip: keep the cache out of it
    from jax.experimental.compilation_cache import compilation_cache as cc
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    cc.reset_cache()
    yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", was)
    cc.reset_cache()


def _spec(sharding, shape, dtype):
    return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)


def _on(sharding, tree):
    return jax.tree.map(lambda a: _spec(sharding, a.shape, a.dtype), tree)


def _compile(fn, *args):
    return jax.jit(fn).lower(*args).compile()


def _pool_tile():
    """(rows, 128) tile of one pool page at the manager's page size."""
    return ManagerConfig().pool_page_elems // pc_ops.LANE, pc_ops.LANE


@pytest.mark.parametrize("op", ["gather", "scatter"])
def test_page_copy_compiles(one_chip, op):
    P, n = 1024, 64
    R, L = _pool_tile()
    pool = _spec(one_chip, (P, R, L), jnp.float32)
    idx = _spec(one_chip, (n,), jnp.int32)
    if op == "gather":
        c = _compile(pc_ops.gather_pages, pool, idx)
    else:
        c = _compile(pc_ops.scatter_pages, pool, idx,
                     _spec(one_chip, (n, R, L), jnp.float32))
    assert "tpu_custom_call" in c.as_text()


def test_paged_attention_compiles_at_phi4_decode_widths(one_chip):
    cfg = get_config("phi4-mini-3.8b")
    H, Hkv, D, T = cfg.num_heads, cfg.num_kv_heads, cfg.head_dim, 16
    assert (H, Hkv, D) == (24, 8, 128)
    B, P, pps = 8, 512, 32
    c = _compile(
        pa_ops.paged_decode_attention,
        _spec(one_chip, (B, H, D), jnp.bfloat16),
        _spec(one_chip, (Hkv, P, T, D), jnp.bfloat16),
        _spec(one_chip, (Hkv, P, T, D), jnp.bfloat16),
        _spec(one_chip, (B, pps), jnp.int32),
        _spec(one_chip, (B,), jnp.int32))
    assert "tpu_custom_call" in c.as_text()


def test_ssd_scan_compiles_at_mamba2_widths(one_chip):
    cfg = get_config("mamba2-130m")
    s = cfg.ssm
    H, P, N, Q = cfg.ssm_heads, s.head_dim, s.state_dim, s.chunk_size
    assert (H, P, N, Q) == (24, 64, 128, 256)
    B, S = 1, 2 * Q
    c = _compile(
        lambda *a: ssd_ops.ssd(*a, chunk_size=Q),
        _spec(one_chip, (B, S, H, P), jnp.bfloat16),
        _spec(one_chip, (B, S, H), jnp.float32),
        _spec(one_chip, (H,), jnp.float32),
        _spec(one_chip, (B, S, N), jnp.bfloat16),
        _spec(one_chip, (B, S, N), jnp.bfloat16),
        _spec(one_chip, (H,), jnp.float32))
    assert "tpu_custom_call" in c.as_text()


@pytest.mark.parametrize("step", ["prefill", "decode"])
def test_phi4_serving_step_fits_one_chip(one_chip, step):
    """The engine's own jitted steps at phi4-mini-3.8b's published widths
    (bf16): the compiler must accept them, and arguments plus scratch
    must fit one chip's HBM."""
    cfg = get_config("phi4-mini-3.8b")
    params = _on(one_chip, jax.eval_shape(
        lambda k: model.init_params(k, cfg), jax.random.PRNGKey(0)))
    if step == "prefill":
        c = _compile(_make_prefill(cfg, None), params,
                     _spec(one_chip, (1, 64), jnp.int32), None, None)
    else:
        cache = _on(one_chip, jax.eval_shape(
            lambda: model.init_cache(cfg, 1, 64)))
        c = _compile(_make_decode(cfg, None), params,
                     _spec(one_chip, (1,), jnp.int32), cache)
    mem = c.memory_analysis()
    used = mem.argument_size_in_bytes + mem.temp_size_in_bytes
    assert used < HBM_BYTES, used
