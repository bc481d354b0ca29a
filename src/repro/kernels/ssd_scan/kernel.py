"""ssd_scan Pallas kernel: Mamba2 state-space-duality chunked scan.

Grid = (batch, ssd_heads, chunks) with the chunk axis innermost and
*sequential*: the inter-chunk recurrent state h (N, P) lives in VMEM
scratch and is carried across chunk steps — the TPU-native shape of the
SSD algorithm (arXiv:2405.21060): the intra-chunk part is the quadratic
dual form (three MXU matmuls per chunk), the inter-chunk part is a scalar-
decay rank-N update.

Inputs are pre-arranged by ops.py into chunk-major layouts so every block
is a contiguous lane-aligned tile:

  logdec: (B, H, nc, 1, Q)     dt * A      (decay log per step; the unit
                                dim makes the block's last two dims equal
                                the array's, as the TPU tiling requires)
  dtx:    (B, H, nc, Q, P)     dt * x      (pre-scaled inputs)
  Bm/Cm:  (B, nc, Q, N)        shared across heads (single SSD group)
  h0:     (B, H, N, P)         initial state
  -> y:   (B, H, nc, Q, P), h_final: (B, H, N, P)
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


def _ssd_kernel(logdec_ref, dtx_ref, b_ref, c_ref, h0_ref,
                y_ref, hout_ref, h_ref):
    c = pl.program_id(2)
    nc = pl.num_programs(2)

    @pl.when(c == 0)
    def _init():
        h_ref[...] = h0_ref[0, 0].astype(jnp.float32)

    ld = logdec_ref[0, 0, 0].astype(jnp.float32)       # (1, Q)
    Q = ld.shape[-1]
    # prefix sums as matmuls with triangular ones masks: Mosaic has no
    # cumsum, and the column form avoids a (1, Q) -> (Q, 1) transpose
    row = jax.lax.broadcasted_iota(jnp.int32, (Q, Q), 0)
    col = jax.lax.broadcasted_iota(jnp.int32, (Q, Q), 1)
    causal = row >= col
    lower = causal.astype(jnp.float32)                 # [i, j] = j <= i
    hi = jax.lax.Precision.HIGHEST
    a_row = jax.lax.dot_general(ld, lower, (((1,), (1,)), ((), ())),
                                precision=hi,
                                preferred_element_type=jnp.float32)  # (1, Q)
    a_col = jax.lax.dot_general(lower, ld, (((1,), (1,)), ((), ())),
                                precision=hi,
                                preferred_element_type=jnp.float32)  # (Q, 1)
    a_tot = jnp.sum(ld, axis=-1, keepdims=True)        # (1, 1)
    Bq = b_ref[0, 0].astype(jnp.float32)               # (Q, N)
    Cq = c_ref[0, 0].astype(jnp.float32)               # (Q, N)
    xq = dtx_ref[0, 0, 0].astype(jnp.float32)          # (Q, P)
    h = h_ref[...]                                     # (N, P)

    # intra-chunk: masked decay kernel in the quadratic dual form
    CB = jax.lax.dot_general(Cq, Bq, (((1,), (1,)), ((), ())),
                             preferred_element_type=jnp.float32)   # (Q, Q)
    seg = a_col - a_row                                # (Q, Q) a_i - a_j
    M = jnp.where(causal, CB * jnp.exp(seg), 0.0)
    y_intra = jax.lax.dot_general(M, xq, (((1,), (0,)), ((), ())),
                                  preferred_element_type=jnp.float32)

    # inter-chunk: contribution of the carried state
    y_inter = jax.lax.dot_general(Cq, h, (((1,), (0,)), ((), ())),
                                  preferred_element_type=jnp.float32)
    y_inter = y_inter * jnp.exp(a_col)                 # (Q, P)

    # chunk-final state update
    w = jnp.exp(a_tot - a_col)                         # (Q, 1) decay to end
    S_chunk = jax.lax.dot_general(Bq, xq * w, (((0,), (0,)), ((), ())),
                                  preferred_element_type=jnp.float32)
    h_ref[...] = h * jnp.exp(a_tot) + S_chunk

    y_ref[0, 0, 0] = (y_intra + y_inter).astype(y_ref.dtype)

    @pl.when(c == nc - 1)
    def _finish():
        hout_ref[0, 0] = h_ref[...].astype(hout_ref.dtype)


def ssd_scan(logdec, dtx, Bm, Cm, h0, *, interpret: bool = False):
    """See module docstring for shapes.  Returns (y, h_final)."""
    B, H, nc, _, Q = logdec.shape
    P = dtx.shape[-1]
    N = Bm.shape[-1]
    grid = (B, H, nc)
    out = pl.pallas_call(
        _ssd_kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, 1, 1, 1, Q), lambda b, h, c: (b, h, c, 0, 0)),
            pl.BlockSpec((1, 1, 1, Q, P), lambda b, h, c: (b, h, c, 0, 0)),
            pl.BlockSpec((1, 1, Q, N), lambda b, h, c: (b, c, 0, 0)),
            pl.BlockSpec((1, 1, Q, N), lambda b, h, c: (b, c, 0, 0)),
            pl.BlockSpec((1, 1, N, P), lambda b, h, c: (b, h, 0, 0)),
        ],
        out_specs=[
            pl.BlockSpec((1, 1, 1, Q, P), lambda b, h, c: (b, h, c, 0, 0)),
            pl.BlockSpec((1, 1, N, P), lambda b, h, c: (b, h, 0, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((B, H, nc, Q, P), dtx.dtype),
            jax.ShapeDtypeStruct((B, H, N, P), jnp.float32),
        ],
        scratch_shapes=[pltpu.VMEM((N, P), jnp.float32)],
        interpret=interpret,
    )(logdec, dtx, Bm, Cm, h0)
    return out[0], out[1]
