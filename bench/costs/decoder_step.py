"""Operations of one training step of a dense decoder (Llama-style block).

Per token: 6 N for the matrix products of forward and backward over the
N weights used in matmuls (the input embedding is a gather and does not
count; a tied output head does), plus causal attention: forward
QK^T and PV cost 2 * 2 * S * H * hd per token for full rows, halved for
causality, times 3 for forward and backward.  Recomputation (remat) does
not count.  This is the model's work, so a step that wastes work on padding
or recompute reads lower, not higher.
"""
from __future__ import annotations

from typing import Any, Dict


def matmul_params(cfg: Dict[str, Any]) -> int:
    d, f = cfg["hidden_size"], cfg["intermediate_size"]
    h, kv = cfg["num_attention_heads"], cfg["num_key_value_heads"]
    hd = cfg["head_dim"]
    per_layer = d * h * hd + 2 * d * kv * hd + h * hd * d + 3 * d * f
    return cfg["num_hidden_layers"] * per_layer + cfg["vocab_size"] * d


def flops_per_token(cfg: Dict[str, Any], seq_len: int) -> float:
    h, hd = cfg["num_attention_heads"], cfg["head_dim"]
    attn = 3 * 2 * 2 * seq_len * h * hd / 2 * cfg["num_hidden_layers"]
    return 6.0 * matmul_params(cfg) + attn


def flops_per_step(cfg: Dict[str, Any], batch: int, seq_len: int) -> float:
    return flops_per_token(cfg, seq_len) * batch * seq_len
