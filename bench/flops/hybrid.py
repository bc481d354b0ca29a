"""Model FLOPs of the Hymba hybrid family, counted from the shapes.

Per layer and token: the SSM branch as :mod:`bench.flops.ssm` counts it,
the attention projections and the SwiGLU MLP as 2 x their weights, and
the scores and the weighted sum of values over the keys in the window
(2 x 2 x heads x head_dim per key).
"""
from __future__ import annotations

from bench.flops import ssm


def layer_matmul_params(cfg) -> int:
    d, H, Hkv, D, f = (cfg["d_model"], cfg["num_heads"], cfg["num_kv_heads"],
                       cfg["head_dim"], cfg["d_ff"])
    attn = d * (H + 2 * Hkv) * D + H * D * d
    return ssm.layer_matmul_params(cfg) + attn + 3 * d * f


def keys_seen(cfg, position: int) -> int:
    n = position + 1
    w = cfg.get("sliding_window")
    return min(n, w) if w else n


def layer_flops(cfg, position: int) -> float:
    extra = 2.0 * (layer_matmul_params(cfg) - ssm.layer_matmul_params(cfg))
    attend = 4.0 * cfg["num_heads"] * cfg["head_dim"] * keys_seen(cfg, position)
    return ssm.layer_flops(cfg, position) + extra + attend


head_flops = ssm.head_flops


def token_flops(cfg, position: int) -> float:
    return cfg["num_layers"] * layer_flops(cfg, position)
