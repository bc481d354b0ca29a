"""Plain float32 reference of the Mamba-2 (SSD) language model.

Follows arXiv:2405.21060 directly: per layer an RMSNorm, the input
projection into z | x | B | C | dt, a depthwise causal convolution with
SiLU over x | B | C, and the selective state-space recurrence written as a
scan over time,

    h_t = exp(dt_t * A) h_{t-1} + dt_t * B_t x_t^T,   y_t = C_t h_t + D x_t,

then a gated RMSNorm and the output projection.  The program computes the
same map in the chunked dual form; this file shares no code with it.

``init`` draws the weights from the seed's key exactly as the served
system's initialiser lays them out (normal / sqrt(fan_in), embedding 0.02,
rounded to the served dtype), so the reference and the program hold the
same numbers without either handing the other anything.

``forward`` takes a ``dot`` (``exact_dot`` or ``fp8_dot``) for every
projection, so the same code serves as the reference and as its
lower-precision control.
"""
from __future__ import annotations

import math

import jax
import jax.numpy as jnp

#: rows of the reference's batch per call (memory grows with S, not S^2)
ROWS = 8

EPS = 1e-6


# ---------------------------------------------------------------- weights
def normal(key, shape, scale, dtype):
    return (jax.random.normal(key, shape, jnp.float32) * scale).astype(dtype)


def dense(key, shape, dtype):
    return normal(key, shape, 1.0 / math.sqrt(max(shape[0], 1)), dtype)


def padded_vocab(cfg) -> int:
    return -(-cfg["vocab_size"] // 256) * 256


def d_inner(cfg) -> int:
    return cfg["ssm"]["expand"] * cfg["d_model"]


def ssm_heads(cfg) -> int:
    return d_inner(cfg) // cfg["ssm"]["head_dim"]


def init_mixer(key, cfg):
    s, d, di = cfg["ssm"], cfg["d_model"], d_inner(cfg)
    H, N = ssm_heads(cfg), s["state_dim"]
    dt = jnp.dtype(cfg["dtype"])
    ks = jax.random.split(key, 3)
    conv_ch = di + 2 * N
    return {
        "in_proj": dense(ks[0], (d, 2 * di + 2 * N + H), dt),
        "conv_w": normal(ks[1], (s["conv_width"], conv_ch), 0.1, dt),
        "conv_b": jnp.zeros((conv_ch,), dt),
        "A_log": jnp.zeros((H,), jnp.float32),
        "D": jnp.ones((H,), jnp.float32),
        "dt_bias": jnp.zeros((H,), jnp.float32),
        "norm_scale": jnp.ones((di,), jnp.float32),
        "out_proj": dense(ks[2], (di, d), dt),
    }


def init_layer(key, cfg):
    ks = jax.random.split(key, 8)
    return {"ln1": {"scale": jnp.ones((cfg["d_model"],), jnp.float32)},
            "ssm": init_mixer(ks[2], cfg)}


def init_top(key, cfg, init_layer_fn):
    """Embedding, stacked layers, final norm and (untied) head."""
    ks = jax.random.split(key, 8)
    dt = jnp.dtype(cfg["dtype"])
    Vp, d = padded_vocab(cfg), cfg["d_model"]
    layers = [init_layer_fn(k, cfg)
              for k in jax.random.split(ks[1], cfg["num_layers"])]
    p = {"embed": normal(ks[0], (Vp, d), 0.02, dt),
         "layers": jax.tree.map(lambda *xs: jnp.stack(xs), *layers),
         "final_norm": {"scale": jnp.ones((d,), jnp.float32)}}
    if not cfg["tie_embeddings"]:
        p["lm_head"] = dense(ks[3], (d, Vp), dt)
    return p


def init(key, cfg):
    """The served weights of one tenant, from its key."""
    return init_top(key, cfg, init_layer)


# ---------------------------------------------------------------- maths
def exact_dot(x, w):
    return jnp.matmul(x, w, precision=jax.lax.Precision.HIGHEST)


def _fp8(a):
    """Round to float8_e4m3fn under a per-tensor scale, back to f32."""
    scale = jnp.maximum(jnp.max(jnp.abs(a)), 1e-30) / 448.0
    return (a / scale).astype(jnp.float8_e4m3fn).astype(jnp.float32) * scale


def fp8_dot(x, w):
    """The control: both operands rounded to fp8, f32 accumulation."""
    return exact_dot(_fp8(x), _fp8(w))


def rmsnorm(x, scale):
    var = jnp.mean(jnp.square(x), axis=-1, keepdims=True)
    return x * jax.lax.rsqrt(var + EPS) * scale


def silu(x):
    return x * jax.nn.sigmoid(x)


def mixer(p, h, cfg, dot):
    """The SSD mixer on normed input h (B, S, d)."""
    s = cfg["ssm"]
    di, H, N, P = d_inner(cfg), ssm_heads(cfg), s["state_dim"], s["head_dim"]
    Bb, S, _ = h.shape
    proj = dot(h, p["in_proj"])
    z, xbc, dt = proj[..., :di], proj[..., di:2 * di + 2 * N], \
        proj[..., 2 * di + 2 * N:]
    W = p["conv_w"].shape[0]
    xp = jnp.pad(xbc, ((0, 0), (W - 1, 0), (0, 0)))
    conv = sum(xp[:, i:i + S] * p["conv_w"][i] for i in range(W))
    xbc = silu(conv + p["conv_b"])
    x = xbc[..., :di].reshape(Bb, S, H, P)
    Bm, Cm = xbc[..., di:di + N], xbc[..., di + N:]
    dt = jax.nn.softplus(dt + p["dt_bias"])
    A = -jnp.exp(p["A_log"])

    def step(state, inp):
        x_t, b_t, c_t, dt_t = inp
        state = state * jnp.exp(dt_t * A)[:, :, None, None] \
            + dt_t[:, :, None, None] * b_t[:, None, :, None] \
            * x_t[:, :, None, :]
        y = jnp.einsum("bn,bhnp->bhp", c_t, state,
                       precision=jax.lax.Precision.HIGHEST) \
            + p["D"][None, :, None] * x_t
        return state, y

    h0 = jnp.zeros((Bb, H, N, P), jnp.float32)
    seq = (x.transpose(1, 0, 2, 3), Bm.transpose(1, 0, 2),
           Cm.transpose(1, 0, 2), dt.transpose(1, 0, 2))
    _, ys = jax.lax.scan(step, h0, seq)
    y = ys.transpose(1, 0, 2, 3).reshape(Bb, S, di)
    y = rmsnorm(y * silu(z), p["norm_scale"])
    return dot(y, p["out_proj"])


def layer(p, x, cfg, dot):
    return x + mixer(p["ssm"], rmsnorm(x, p["ln1"]["scale"]), cfg, dot)


def forward_top(params, cfg, tokens, dot, layer_fn):
    """Logits (B, S, vocab_size) in f32 for token ids (B, S)."""
    p = jax.tree.map(lambda a: a.astype(jnp.float32), params)
    x = p["embed"][tokens]

    def body(x, lp):
        return layer_fn(lp, x, cfg, dot), None

    x, _ = jax.lax.scan(body, x, p["layers"])
    x = rmsnorm(x, p["final_norm"]["scale"])
    head = p["embed"].T if cfg["tie_embeddings"] else p["lm_head"]
    return dot(x, head)[..., :cfg["vocab_size"]]


def forward(params, cfg, tokens, dot=exact_dot):
    with jax.default_matmul_precision("highest"):
        return forward_top(params, cfg, tokens, dot, layer)
