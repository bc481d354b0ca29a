"""Plain float32 reference of the Hymba hybrid block (arXiv:2411.13676).

Each layer runs attention heads and SSM heads side by side on one RMSNorm
of the input, RMS-normalises each branch's output, averages the two and
adds the result, then a SwiGLU MLP.  Attention is grouped-query with
rotary positions on interleaved channel pairs and a causal sliding window
of ``sliding_window`` keys on every layer; the SSM branch is the SSD
recurrence of :mod:`bench.reference.ssm`.  Scores are formed densely per
row (``ROWS`` = 1), which is plain and fits one chip at 2k tokens.
"""
from __future__ import annotations

import math

import jax
import jax.numpy as jnp

from bench.reference import ssm

ROWS = 1


def init_layer(key, cfg):
    d, H, Hkv, D = (cfg["d_model"], cfg["num_heads"], cfg["num_kv_heads"],
                    cfg["head_dim"])
    f, dt = cfg["d_ff"], jnp.dtype(cfg["dtype"])
    ks = jax.random.split(key, 8)
    ka = jax.random.split(ks[1], 4)
    km = jax.random.split(ks[6], 3)
    one = jnp.ones((d,), jnp.float32)
    return {
        "ln1": {"scale": one},
        "attn": {"wq": ssm.dense(ka[0], (d, H * D), dt),
                 "wk": ssm.dense(ka[1], (d, Hkv * D), dt),
                 "wv": ssm.dense(ka[2], (d, Hkv * D), dt),
                 "wo": ssm.dense(ka[3], (H * D, d), dt)},
        "ssm": ssm.init_mixer(ks[2], cfg),
        "attn_out_norm": one,
        "ssm_out_norm": one,
        "ln2": {"scale": one},
        "mlp": {"w_gate": ssm.dense(km[0], (d, f), dt),
                "w_up": ssm.dense(km[1], (d, f), dt),
                "w_down": ssm.dense(km[2], (f, d), dt)},
    }


def init(key, cfg):
    """The served weights of one tenant, from its key."""
    return ssm.init_top(key, cfg, init_layer)


def rope(x, theta):
    """Rotary positions 0..S-1 on interleaved (even, odd) channel pairs.
    x: (B, S, H, D)."""
    S, D = x.shape[1], x.shape[-1]
    inv = 1.0 / theta ** (jnp.arange(0, D, 2, dtype=jnp.float32) / D)
    ang = jnp.arange(S, dtype=jnp.float32)[:, None] * inv      # (S, D/2)
    cos, sin = jnp.cos(ang)[None, :, None], jnp.sin(ang)[None, :, None]
    x1, x2 = x[..., 0::2], x[..., 1::2]
    return jnp.stack([x1 * cos - x2 * sin, x2 * cos + x1 * sin],
                     -1).reshape(x.shape)


def attention(p, h, cfg, dot):
    Bb, S, _ = h.shape
    H, Hkv, D = cfg["num_heads"], cfg["num_kv_heads"], cfg["head_dim"]
    q = rope(dot(h, p["wq"]).reshape(Bb, S, H, D), cfg["rope_theta"])
    k = rope(dot(h, p["wk"]).reshape(Bb, S, Hkv, D), cfg["rope_theta"])
    v = dot(h, p["wv"]).reshape(Bb, S, Hkv, D)
    k, v = jnp.repeat(k, H // Hkv, axis=2), jnp.repeat(v, H // Hkv, axis=2)
    s = jnp.einsum("bqhd,bkhd->bhqk", q, k) / math.sqrt(D)
    pos = jnp.arange(S)
    ok = pos[None, :] <= pos[:, None]
    if cfg.get("sliding_window"):
        ok &= pos[None, :] > pos[:, None] - cfg["sliding_window"]
    s = jnp.where(ok, s, -jnp.inf)
    o = jnp.einsum("bhqk,bkhd->bqhd", jax.nn.softmax(s, axis=-1), v)
    return dot(o.reshape(Bb, S, H * D), p["wo"])


def layer(p, x, cfg, dot):
    h = ssm.rmsnorm(x, p["ln1"]["scale"])
    a = ssm.rmsnorm(attention(p["attn"], h, cfg, dot), p["attn_out_norm"])
    s = ssm.rmsnorm(ssm.mixer(p["ssm"], h, cfg, dot), p["ssm_out_norm"])
    x = x + 0.5 * (a + s)
    h = ssm.rmsnorm(x, p["ln2"]["scale"])
    m = p["mlp"]
    return x + dot(ssm.silu(dot(h, m["w_gate"])) * dot(h, m["w_up"]),
                   m["w_down"])


def forward(params, cfg, tokens, dot=ssm.exact_dot):
    with jax.default_matmul_precision("highest"):
        return ssm.forward_top(params, cfg, tokens, dot, layer)
