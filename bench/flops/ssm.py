"""Model FLOPs of the Mamba-2 (SSD) family, counted from the shapes.

A multiply-add counts 2.  ``layer_matmul_params`` are the weights one
token multiplies per layer; the SSD recurrence adds, per token and head,
the state update (dt * B x^T, the decay, the sum: 4 N P) and the read-out
C h (2 N P), and the depthwise convolution 2 W per channel.  This is the
recurrent form's count: the chunked prefill does more arithmetic for the
same result, and that extra is not model work.
"""
from __future__ import annotations


def d_inner(cfg) -> int:
    return cfg["ssm"]["expand"] * cfg["d_model"]


def layer_matmul_params(cfg) -> int:
    s, d, di = cfg["ssm"], cfg["d_model"], d_inner(cfg)
    H, N = di // s["head_dim"], s["state_dim"]
    return d * (2 * di + 2 * N + H) + di * d


def layer_flops(cfg, position: int) -> float:
    """One token at ``position`` (0-based) through one layer."""
    s, di = cfg["ssm"], d_inner(cfg)
    N = s["state_dim"]
    return (2.0 * layer_matmul_params(cfg) + 2.0 * s["conv_width"] * (di + 2 * N)
            + 6.0 * N * di)


def head_flops(cfg) -> float:
    """The LM head for one token (the logits over the served vocabulary)."""
    return 2.0 * cfg["d_model"] * cfg["vocab_size"]


def token_flops(cfg, position: int) -> float:
    """All layers at ``position``, without the head."""
    return cfg["num_layers"] * layer_flops(cfg, position)
