"""Pallas kernels vs pure-jnp oracles: shape/dtype sweeps.  The CPU has no
Mosaic backend, so every call asks for interpret mode itself."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.kernels.page_copy import ops as pc_ops, ref as pc_ref
from repro.kernels.paged_attention import ops as pa_ops, ref as pa_ref
from repro.kernels.ssd_scan import ops as ssd_ops, ref as ssd_ref

RNG = np.random.default_rng(42)


def _tol(dtype):
    return dict(rtol=2e-2, atol=2e-2) if dtype == jnp.bfloat16 \
        else dict(rtol=2e-5, atol=2e-5)


# ---------------------------------------------------------------------------
# page_copy
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16, jnp.int32])
@pytest.mark.parametrize("P,R,n", [(16, 1, 4), (64, 4, 64), (8, 2, 8)])
def test_page_gather(P, R, n, dtype):
    pool = jnp.asarray(RNG.integers(-100, 100, (P, R, 128)), dtype)
    idx = jnp.asarray(RNG.integers(0, P, (n,)), jnp.int32)
    out = pc_ops.gather_pages(pool, idx, interpret=True)
    np.testing.assert_array_equal(np.asarray(out),
                                  np.asarray(pc_ref.gather_pages(pool, idx)))


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
@pytest.mark.parametrize("P,R,n", [(16, 1, 4), (32, 4, 17)])
def test_page_scatter(P, R, n, dtype):
    pool = jnp.asarray(RNG.standard_normal((P, R, 128)), dtype)
    idx = jnp.asarray(RNG.choice(P, n, replace=False), jnp.int32)
    buf = jnp.asarray(RNG.standard_normal((n, R, 128)), dtype)
    expect = pc_ref.scatter_pages(pool, idx, buf)
    out = pc_ops.scatter_pages(pool, idx, buf, interpret=True)
    np.testing.assert_array_equal(np.asarray(out), np.asarray(expect))


def test_page_roundtrip_flat():
    pool = jnp.asarray(RNG.standard_normal((32, 512)), jnp.float32)
    expect = np.asarray(pool)                 # scatter donates the pool
    idx = jnp.asarray([3, 9, 27], jnp.int32)
    buf = pc_ops.gather_pages(pool, idx, interpret=True)
    out = pc_ops.scatter_pages(pool, idx, buf, interpret=True)       # scatter back = identity
    np.testing.assert_array_equal(np.asarray(out), expect)


# ---------------------------------------------------------------------------
# paged_attention
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
@pytest.mark.parametrize("B,H,Hkv,T,pps", [
    (2, 8, 2, 16, 4),      # GQA 4:1
    (1, 4, 4, 8, 3),       # MHA
    (3, 16, 2, 32, 2),     # GQA 8:1
    (2, 7, 1, 16, 5),      # odd head count (hymba-like 7:1)
])
def test_paged_attention_sweep(B, H, Hkv, T, pps, dtype):
    D, P = 128, 64
    q = jnp.asarray(RNG.standard_normal((B, H, D)), dtype)
    kp = jnp.asarray(RNG.standard_normal((Hkv, P, T, D)), dtype)
    vp = jnp.asarray(RNG.standard_normal((Hkv, P, T, D)), dtype)
    pt = jnp.asarray(RNG.integers(0, P, (B, pps)), jnp.int32)
    lengths = jnp.asarray(RNG.integers(1, pps * T + 1, (B,)), jnp.int32)
    out = pa_ops.paged_decode_attention(q, kp, vp, pt, lengths,
                                        interpret=True)
    exp = pa_ref.paged_decode_attention(q, kp, vp, pt, lengths)
    np.testing.assert_allclose(np.asarray(out, np.float32),
                               np.asarray(exp, np.float32), **_tol(dtype))


@pytest.mark.parametrize("window", [4, 12, 100])
def test_paged_attention_window(window):
    B, H, Hkv, D, T, pps, P = 2, 8, 2, 128, 8, 4, 32
    q = jnp.asarray(RNG.standard_normal((B, H, D)), jnp.float32)
    kp = jnp.asarray(RNG.standard_normal((Hkv, P, T, D)), jnp.float32)
    vp = jnp.asarray(RNG.standard_normal((Hkv, P, T, D)), jnp.float32)
    pt = jnp.asarray(RNG.integers(0, P, (B, pps)), jnp.int32)
    lengths = jnp.asarray([5, 30], jnp.int32)
    out = pa_ops.paged_decode_attention(q, kp, vp, pt, lengths,
                                        window=window, interpret=True)
    exp = pa_ref.paged_decode_attention(q, kp, vp, pt, lengths,
                                        window=window)
    np.testing.assert_allclose(np.asarray(out), np.asarray(exp),
                               rtol=2e-5, atol=2e-5)


def test_paged_attention_matches_dense_decode():
    """Against the engine's dense decode_attention on the same logical
    cache — the kernel and the engine must agree."""
    from repro.models.attention import decode_attention
    B, H, Hkv, D, T, pps = 2, 8, 4, 128, 16, 4
    S = pps * T
    P = 32
    kp = jnp.asarray(RNG.standard_normal((Hkv, P, T, D)), jnp.float32)
    vp = jnp.asarray(RNG.standard_normal((Hkv, P, T, D)), jnp.float32)
    q = jnp.asarray(RNG.standard_normal((B, H, D)), jnp.float32)
    pt = jnp.asarray(RNG.integers(0, P, (B, pps)), jnp.int32)
    lengths = jnp.asarray([S - 3, 20], jnp.int32)
    k_d = kp[:, pt].transpose(1, 2, 3, 0, 4).reshape(B, S, Hkv, D)
    v_d = vp[:, pt].transpose(1, 2, 3, 0, 4).reshape(B, S, Hkv, D)
    pos = jnp.broadcast_to(jnp.arange(S)[None], (B, S))
    dense = decode_attention(q, k_d, v_d, pos, lengths)
    paged = pa_ops.paged_decode_attention(q, kp, vp, pt, lengths,
                                          interpret=True)
    np.testing.assert_allclose(np.asarray(paged), np.asarray(dense),
                               rtol=2e-5, atol=2e-5)


# ---------------------------------------------------------------------------
# ssd_scan
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
@pytest.mark.parametrize("B,S,H,P,N,Q", [
    (2, 64, 4, 32, 16, 16),
    (1, 37, 2, 64, 8, 16),      # ragged: S % Q != 0
    (2, 128, 3, 16, 32, 32),
    (1, 16, 1, 128, 128, 16),   # full mamba2 state size
])
def test_ssd_scan_sweep(B, S, H, P, N, Q, dtype):
    x = jnp.asarray(RNG.standard_normal((B, S, H, P)) * 0.5, dtype)
    dt = jnp.asarray(RNG.uniform(0.01, 0.5, (B, S, H)), jnp.float32)
    A = jnp.asarray(-RNG.uniform(0.5, 2.0, (H,)), jnp.float32)
    Bm = jnp.asarray(RNG.standard_normal((B, S, N)) * 0.3, dtype)
    Cm = jnp.asarray(RNG.standard_normal((B, S, N)) * 0.3, dtype)
    D = jnp.asarray(RNG.standard_normal((H,)), jnp.float32)
    y, h = ssd_ops.ssd(x, dt, A, Bm, Cm, D, chunk_size=Q,
                       interpret=True)
    ye, he = ssd_ref.ssd(x, dt, A, Bm, Cm, D, chunk_size=Q)
    tol = _tol(dtype)
    np.testing.assert_allclose(np.asarray(y, np.float32),
                               np.asarray(ye, np.float32), **tol)
    np.testing.assert_allclose(np.asarray(h), np.asarray(he), **tol)


def test_ssd_scan_state_chaining():
    """Scanning two halves with carried state == scanning the whole."""
    B, S, H, P, N, Q = 1, 64, 2, 32, 16, 16
    x = jnp.asarray(RNG.standard_normal((B, S, H, P)) * 0.5, jnp.float32)
    dt = jnp.asarray(RNG.uniform(0.01, 0.5, (B, S, H)), jnp.float32)
    A = jnp.asarray(-RNG.uniform(0.5, 2.0, (H,)), jnp.float32)
    Bm = jnp.asarray(RNG.standard_normal((B, S, N)) * 0.3, jnp.float32)
    Cm = jnp.asarray(RNG.standard_normal((B, S, N)) * 0.3, jnp.float32)
    D = jnp.zeros((H,), jnp.float32)
    y_full, h_full = ssd_ops.ssd(x, dt, A, Bm, Cm, D, chunk_size=Q,
                       interpret=True)
    y1, h1 = ssd_ops.ssd(x[:, :32], dt[:, :32], A, Bm[:, :32], Cm[:, :32],
                         D, chunk_size=Q, interpret=True)
    y2, h2 = ssd_ops.ssd(x[:, 32:], dt[:, 32:], A, Bm[:, 32:], Cm[:, 32:],
                         D, chunk_size=Q, h0=h1, interpret=True)
    np.testing.assert_allclose(np.asarray(jnp.concatenate([y1, y2], 1)),
                               np.asarray(y_full), rtol=1e-4, atol=1e-4)
    np.testing.assert_allclose(np.asarray(h2), np.asarray(h_full),
                               rtol=1e-4, atol=1e-4)


def test_ssd_kernel_matches_model_block():
    """The kernel path must agree with the model's ssm_forward math on the
    exact contraction it replaces."""
    from repro.models.ssm import ssd_chunked
    B, S, H, P, N, Q = 2, 48, 4, 32, 16, 16
    x = jnp.asarray(RNG.standard_normal((B, S, H, P)), jnp.float32)
    dt = jnp.asarray(RNG.uniform(0.05, 0.3, (B, S, H)), jnp.float32)
    A = jnp.asarray([-1.0, -0.5, -2.0, -1.5], jnp.float32)
    Bm = jnp.asarray(RNG.standard_normal((B, S, N)), jnp.float32)
    Cm = jnp.asarray(RNG.standard_normal((B, S, N)), jnp.float32)
    D = jnp.asarray(RNG.standard_normal((H,)), jnp.float32)
    y_k, h_k = ssd_ops.ssd(x, dt, A, Bm, Cm, D, chunk_size=Q,
                       interpret=True)
    y_m, h_m = ssd_chunked(x, dt, A, Bm, Cm, D, chunk_size=Q)
    np.testing.assert_allclose(np.asarray(y_k), np.asarray(y_m),
                               rtol=1e-4, atol=1e-4)
    np.testing.assert_allclose(np.asarray(h_k), np.asarray(h_m),
                               rtol=1e-4, atol=1e-4)
