"""jit'd public wrappers for the page_copy kernel.

``as_pages`` reshapes a flat (P, page_elems) pool into the lane-aligned
(P, R, 128) tile layout the kernel requires (page_elems % 128 == 0 is the
pool's alignment contract on TPU).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from repro.kernels.page_copy import kernel, ref

LANE = kernel.LANE


def as_pages(pool_flat: jax.Array) -> jax.Array:
    P, E = pool_flat.shape
    if E % LANE:
        raise ValueError(f"page_elems {E} not a multiple of {LANE}")
    return pool_flat.reshape(P, E // LANE, LANE)


@functools.partial(jax.jit, static_argnames=("interpret",))
def gather_pages(pool: jax.Array, idx: jax.Array, *,
                 interpret: bool = False) -> jax.Array:
    """pool: (P, R, 128) or (P, E); idx: (n,) -> (n, ...) page batch."""
    flat = pool.ndim == 2
    if flat:
        pool = as_pages(pool)
    out = kernel.gather_pages(pool, idx.astype(jnp.int32),
                              interpret=interpret)
    return out.reshape(out.shape[0], -1) if flat else out


@functools.partial(jax.jit, static_argnames=("interpret",),
                   donate_argnames=("pool",))
def scatter_pages(pool: jax.Array, idx: jax.Array, buf: jax.Array, *,
                  interpret: bool = False) -> jax.Array:
    flat = pool.ndim == 2
    if flat:
        P, E = pool.shape
        pool = as_pages(pool)
        buf = buf.reshape(buf.shape[0], E // LANE, LANE)
    out = kernel.scatter_pages(pool, idx.astype(jnp.int32), buf,
                               interpret=interpret)
    return out.reshape(out.shape[0], -1) if flat else out
