"""jit'd public wrapper for the SSD scan kernel.

Takes the same (B, S, H, P) sequence-major arguments as the reference
``ssd_chunked`` and handles chunk padding, the (dt*A, dt*x) pre-scaling,
chunk-major re-layout, and the D skip connection.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from repro.kernels.ssd_scan import kernel


@functools.partial(jax.jit, static_argnames=("chunk_size", "interpret"))
def ssd(x, dt, A, Bm, Cm, D, *, chunk_size: int = 256, h0=None,
        interpret: bool = False):
    """SSD forward.  x: (B,S,H,P); dt: (B,S,H); A,D: (H,); Bm,Cm: (B,S,N).
    Returns (y (B,S,H,P), h_final (B,H,N,P))."""
    B, S, H, P = x.shape
    N = Bm.shape[-1]
    Q = min(chunk_size, S)
    pad = (-S) % Q
    if pad:
        x = jnp.pad(x, ((0, 0), (0, pad), (0, 0), (0, 0)))
        dt = jnp.pad(dt, ((0, 0), (0, pad), (0, 0)))
        Bm = jnp.pad(Bm, ((0, 0), (0, pad), (0, 0)))
        Cm = jnp.pad(Cm, ((0, 0), (0, pad), (0, 0)))
    Sp = S + pad
    nc = Sp // Q

    dt32 = dt.astype(jnp.float32)
    logdec = dt32 * A[None, None, :]                       # (B,Sp,H)
    dtx = x.astype(jnp.float32) * dt32[..., None]          # (B,Sp,H,P)

    # chunk-major layouts
    logdec = logdec.reshape(B, nc, Q, H).transpose(0, 3, 1, 2)[:, :, :, None]
    dtx = dtx.reshape(B, nc, Q, H, P).transpose(0, 3, 1, 2, 4)
    Bmc = Bm.reshape(B, nc, Q, N).astype(jnp.float32)
    Cmc = Cm.reshape(B, nc, Q, N).astype(jnp.float32)
    if h0 is None:
        h0 = jnp.zeros((B, H, N, P), jnp.float32)

    y, h_final = kernel.ssd_scan(logdec, dtx, Bmc, Cmc, h0,
                                 interpret=interpret)
    y = y.transpose(0, 2, 3, 1, 4).reshape(B, Sp, H, P)[:, :S]
    y = y + x[:, :S].astype(jnp.float32) * D[None, None, :, None]
    return y.astype(x.dtype), h_final
