"""page_copy Pallas kernel: batched page gather / scatter.

The TPU-native analogue of the paper's ``pwritev``/``preadv`` insight
(§3.4.2): a *scattered* set of pool pages is converted to/from one
*contiguous* buffer, so host<->device IO for deflate/inflate is a single
sequential DMA stream instead of per-page random access.

  gather : out[i]          = pool[idx[i]]   (deflate compaction, pre-D2H)
  scatter: pool[idx[i]]    = buf[i]         (inflate distribution, post-H2D)

The page indices are *scalar-prefetched* (``PrefetchScalarGridSpec``) so
Mosaic knows every block address before the grid runs — the DMA schedule
is fully static, exactly the io-vector batching of the paper.

Pages are viewed as (rows, 128) lane-aligned tiles; one grid step copies
one page through VMEM.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

LANE = 128


def _copy_kernel(idx_ref, src_ref, dst_ref):
    del idx_ref                      # consumed by the index maps
    dst_ref[...] = src_ref[...]


def _scatter_kernel(idx_ref, buf_ref, pool_ref, out_ref):
    del idx_ref, pool_ref            # pool is aliased into out
    out_ref[...] = buf_ref[...]


def gather_pages(pool: jax.Array, idx: jax.Array, *,
                 interpret: bool = False) -> jax.Array:
    """pool: (P, R, 128); idx: (n,) int32 -> (n, R, 128)."""
    P, R, L = pool.shape
    assert L == LANE, f"last dim must be {LANE}"
    n = idx.shape[0]
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,
        grid=(n,),
        in_specs=[pl.BlockSpec((1, R, LANE),
                               lambda i, idx_ref: (idx_ref[i], 0, 0))],
        out_specs=pl.BlockSpec((1, R, LANE),
                               lambda i, idx_ref: (i, 0, 0)),
    )
    return pl.pallas_call(
        _copy_kernel,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((n, R, LANE), pool.dtype),
        interpret=interpret,
    )(idx, pool)


def scatter_pages(pool: jax.Array, idx: jax.Array, buf: jax.Array, *,
                  interpret: bool = False) -> jax.Array:
    """pool[idx[i]] = buf[i].  pool: (P, R, 128); buf: (n, R, 128).

    The pool is aliased in-place (donated) — the kernel only touches the
    pages named in ``idx``; every other page passes through untouched.
    """
    P, R, L = pool.shape
    n = idx.shape[0]
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,
        grid=(n,),
        in_specs=[
            pl.BlockSpec((1, R, LANE), lambda i, idx_ref: (i, 0, 0)),      # buf
            pl.BlockSpec((1, R, LANE),
                         lambda i, idx_ref: (idx_ref[i], 0, 0)),           # pool
        ],
        out_specs=pl.BlockSpec((1, R, LANE),
                               lambda i, idx_ref: (idx_ref[i], 0, 0)),
    )
    return pl.pallas_call(
        _scatter_kernel,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((P, R, LANE), pool.dtype),
        input_output_aliases={2: 0},       # pool (after the scalar operand)
        interpret=interpret,
    )(idx, buf, pool)
