"""jit'd public wrapper for paged decode attention."""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from repro.kernels.paged_attention import kernel


@functools.partial(jax.jit,
                   static_argnames=("scale", "window", "interpret"))
def paged_decode_attention(q, k_pages, v_pages, page_table, lengths, *,
                           scale=None, window: int = 0,
                           interpret: bool = False):
    """GQA decode over paged KV.  See kernel.py for shapes."""
    B, H, D = q.shape
    Hkv, P, T, Dk = k_pages.shape
    if D != Dk:
        raise ValueError(f"head_dim mismatch {D} != {Dk}")
    if H % Hkv:
        raise ValueError(f"q heads {H} not a multiple of kv heads {Hkv}")
    if page_table.ndim != 2 or page_table.shape[0] != B:
        raise ValueError(f"bad page_table shape {page_table.shape}")
    return kernel.paged_decode_attention(
        q, k_pages, v_pages, page_table, lengths,
        scale=scale, window=window, interpret=interpret)
