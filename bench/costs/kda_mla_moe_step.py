"""Operations of one training step of the Kimi-Linear chip share (KDA and
latent attention, then dense or expert FFNs), and of the calls of its KDA
kernels.

Model work per token: 6 N over the weights a token's products use here
(the KDA projections q, k, v, o, beta and the rank-r decay and gate
products; every latent-attention projection; the leading dense FFN; the
router, the shared expert and the routed experts held here at their
expected share of a token's assignments, ``k * held / E``; the output
head; the input embedding is a gather and does not count; the short
convolutions, norms and gates are elementwise and do not count either),
plus causal attention on the latent-attention layers (q.k at nope + rope
and p.v at v_head per (query, key) pair, S / 2 pairs per query, times 3
for forward and backward), plus the KDA layers' chunked products, times 3
for forward and backward.  Recomputation does not count.

The chunked products are fixed by the math of the chunked form at chunk
C = 64 with d_k = d_v = d, per chunk and head, in multiply-adds, whatever
implements it: the intra-chunk matrices A (C (C - 1) / 2 d) and P
(C (C + 1) / 2 d), the triangular inverse (C^3 / 6), W and U (C^2 / 2 d
each), V' = U - W S (C d^2), O = Q S + P V' (C d^2 + C^2 / 2 d) and the
state update (C d^2).

Kernel calls, for ``kda_roofline``: ``kda_fwd`` holds the whole
chunked form of a layer's rows and heads, so a call counts those
products once; ``kda_bwd`` holds their backward, counted at twice the
forward (the rebuilt forward terms inside it do not count).
"""
from __future__ import annotations

from typing import Any, Dict, List

#: tokens per chunk of the chunked form
CHUNK = 64


def layer_kinds(cfg: Dict[str, Any]) -> List[str]:
    lin = cfg["linear_attn_config"]
    return ["kda" if i in lin["kda_layers"] else "attn"
            for i in range(1, cfg["num_hidden_layers"] + 1)]


def _kda(cfg: Dict[str, Any]):
    lin = cfg["linear_attn_config"]
    return lin["num_heads"], lin["head_dim"]


def matmul_params_per_token(cfg: Dict[str, Any]) -> float:
    D, H, R = cfg["hidden_size"], cfg["num_attention_heads"], cfg["kv_lora_rank"]
    nope, rope, vd = cfg["qk_nope_head_dim"], cfg["qk_rope_head_dim"], cfg["v_head_dim"]
    mla = D * H * (nope + rope) + D * (R + rope) + R * H * (nope + vd) + H * vd * D
    Hk, d = _kda(cfg)
    HK, r = Hk * d, d
    kda = 4 * D * HK + D * Hk + 2 * (D * r + r * HK)
    Fe, E = cfg["moe_intermediate_size"], cfg["deployment"]["num_experts"]
    routed = cfg["num_experts_per_token"] * cfg["num_experts"] / E * 3 * D * Fe
    moe = routed + 3 * D * cfg["num_shared_experts"] * Fe + D * E
    kinds = layer_kinds(cfg)
    lead = cfg["first_k_dense_replace"]
    mixers = sum(kda if k == "kda" else mla for k in kinds)
    return (mixers + lead * 3 * D * cfg["intermediate_size"]
            + (len(kinds) - lead) * moe + D * cfg["vocab_size"])


def kda_chunk_macs(d: int) -> float:
    """Multiply-adds of one chunk of one head, forward (docstring)."""
    C = CHUNK
    intra = C * (C - 1) / 2 * d + C * (C + 1) / 2 * d + C ** 3 / 6 + C * C * d
    state = 3 * C * d * d + C * C / 2 * d
    return intra + state


def flops_per_token(cfg: Dict[str, Any], seq_len: int) -> float:
    H = cfg["num_attention_heads"]
    qk = cfg["qk_nope_head_dim"] + cfg["qk_rope_head_dim"]
    kinds = layer_kinds(cfg)
    attn = 3 * 2 * seq_len / 2 * H * (qk + cfg["v_head_dim"]) * kinds.count("attn")
    Hk, d = _kda(cfg)
    kda = 3 * 2 * Hk * kda_chunk_macs(d) / CHUNK * kinds.count("kda")
    return 6.0 * matmul_params_per_token(cfg) + attn + kda


def flops_per_step(cfg: Dict[str, Any], batch: int, seq_len: int) -> float:
    return flops_per_token(cfg, seq_len) * batch * seq_len


#: multiply-adds per chunk and head of each kernel's call, as functions
#: of d -- the docstring's counts
KERNEL_MACS = {
    "kda_fwd": lambda d: kda_chunk_macs(d),
    "kda_bwd": lambda d: 2 * kda_chunk_macs(d),
}


def kernel_flops(cfg: Dict[str, Any], kernel: str, rows: int,
                 seq_len: int) -> float:
    """One call of kernel ``kernel`` over ``rows`` rows of ``seq_len``
    tokens and every KDA head."""
    H, d = _kda(cfg)
    chunks = -(-seq_len // CHUNK)
    return 2.0 * rows * H * chunks * KERNEL_MACS[kernel](d)
