"""paged_attention Pallas kernel: GQA decode over bitmap-allocated KV pages.

The serving hot loop of the framework: one query token per sequence attends
to a KV cache that lives in *pool pages* (the Bitmap Page Allocator's unit),
reached through a per-sequence page table — compute never needs the cache
to be contiguous, which is what makes deflate/inflate cheap.

TPU mapping (DESIGN.md §6):
  * grid = (batch, kv_heads, pages_per_seq); the page dimension is the
    innermost (sequential) axis, so the online-softmax state for one
    (b, kv_head) lives in VMEM scratch across page steps;
  * the page table and sequence lengths are **scalar-prefetched** so Mosaic
    resolves every K/V block address before the grid starts (static DMA
    schedule, the paper's batched-io insight applied to HBM->VMEM);
  * all G = H/Hkv query heads of one kv head are processed together, so the
    MXU sees a (G, D) x (D, T) matmul per page;
  * K and V pages are (T, D) lane-aligned tiles (T = tokens/page, D = 128).

Out-of-range pages (beyond a sequence's length) are masked via the
position iota; a fully-masked page contributes nothing (the m/l state is
clamped, never NaN).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

NEG = -1e30


def _decode_kernel(pt_ref, len_ref,            # scalar-prefetched
                   q_ref, k_ref, v_ref,        # VMEM blocks
                   out_ref,                    # VMEM output block
                   m_ref, l_ref, acc_ref,      # VMEM scratch
                   *, page_tokens: int, scale: float, window: int):
    b = pl.program_id(0)
    p = pl.program_id(2)
    n_pages = pl.num_programs(2)

    @pl.when(p == 0)
    def _init():
        m_ref[...] = jnp.full_like(m_ref, NEG)
        l_ref[...] = jnp.zeros_like(l_ref)
        acc_ref[...] = jnp.zeros_like(acc_ref)

    q = q_ref[0, 0].astype(jnp.float32)            # (G, D)
    k = k_ref[0, 0].astype(jnp.float32)            # (T, D)
    v = v_ref[0, 0].astype(jnp.float32)            # (T, D)
    length = len_ref[b]

    s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                            preferred_element_type=jnp.float32) * scale
    pos = p * page_tokens + jax.lax.broadcasted_iota(
        jnp.int32, (1, page_tokens), 1)            # (1, T) global positions
    valid = pos < length
    if window > 0:
        valid &= pos > length - 1 - window
    s = jnp.where(valid, s, NEG)                   # (G, T)

    m_prev = m_ref[...]                            # (G, 1)
    m_cur = jnp.max(s, axis=-1, keepdims=True)
    m_new = jnp.maximum(m_prev, m_cur)
    pw = jnp.exp(s - m_new)
    pw = jnp.where(valid, pw, 0.0)
    corr = jnp.exp(m_prev - m_new)                 # (G, 1)
    l_ref[...] = l_ref[...] * corr + pw.sum(-1, keepdims=True)
    acc_ref[...] = acc_ref[...] * corr + jax.lax.dot_general(
        pw, v, (((1,), (0,)), ((), ())),
        preferred_element_type=jnp.float32)
    m_ref[...] = m_new

    @pl.when(p == n_pages - 1)
    def _finish():
        out_ref[0, 0] = (acc_ref[...]
                         / jnp.maximum(l_ref[...], 1e-30)).astype(out_ref.dtype)


def paged_decode_attention(q, k_pages, v_pages, page_table, lengths, *,
                           scale: float | None = None, window: int = 0,
                           interpret: bool = False):
    """q: (B, H, D); k_pages/v_pages: (Hkv, P, T, D);
    page_table: (B, pages_per_seq) int32 (entries past the sequence end may
    be any valid page id — they are masked); lengths: (B,) int32.
    Returns (B, H, D)."""
    B, H, D = q.shape
    Hkv, P, T, _ = k_pages.shape
    G = H // Hkv
    pages_per_seq = page_table.shape[1]
    scale = float(scale if scale is not None else 1.0 / (D ** 0.5))
    qg = q.reshape(B, Hkv, G, D)

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,
        grid=(B, Hkv, pages_per_seq),
        in_specs=[
            pl.BlockSpec((1, 1, G, D),
                         lambda b, h, p, pt, ln: (b, h, 0, 0)),
            pl.BlockSpec((1, 1, T, D),
                         lambda b, h, p, pt, ln: (h, pt[b, p], 0, 0)),
            pl.BlockSpec((1, 1, T, D),
                         lambda b, h, p, pt, ln: (h, pt[b, p], 0, 0)),
        ],
        out_specs=pl.BlockSpec((1, 1, G, D),
                               lambda b, h, p, pt, ln: (b, h, 0, 0)),
        scratch_shapes=[
            pltpu.VMEM((G, 1), jnp.float32),       # m
            pltpu.VMEM((G, 1), jnp.float32),       # l
            pltpu.VMEM((G, D), jnp.float32),       # acc
        ],
    )
    out = pl.pallas_call(
        functools.partial(_decode_kernel, page_tokens=T, scale=scale,
                          window=window),
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((B, Hkv, G, D), q.dtype),
        interpret=interpret,
    )(page_table.astype(jnp.int32), lengths.astype(jnp.int32),
      qg, k_pages, v_pages)
    return out.reshape(B, H, D)
