"""What training the lm124m decoder requires per token, counted from shapes:
the numerator of ``mfu.train``, with no recompute."""
from __future__ import annotations


def matmul_params(cfg: dict) -> int:
    """Weights that take part in a matrix product per token: attention
    projections, the SwiGLU MLP and the output head (the embedding is a
    gather)."""
    d, h, kh, hd = (cfg["d_model"], cfg["num_heads"], cfg["num_kv_heads"],
                    cfg["head_dim"])
    attn = d * h * hd * 2 + d * kh * hd * 2
    mlp = d * cfg["d_ff"] * 3
    return cfg["num_layers"] * (attn + mlp) + d * cfg["vocab_size"]


def flops_per_token(cfg: dict, seq_len: int) -> float:
    """Forward and backward FLOPs one token requires: 6 per matmul weight,
    plus causal attention's scores and values, whose mean context is
    (seq_len + 1) / 2 keys."""
    ctx = (seq_len + 1) / 2
    attn = 4 * cfg["num_heads"] * cfg["head_dim"] * ctx   # QK^T and PV
    return 6 * matmul_params(cfg) + 3 * cfg["num_layers"] * attn
