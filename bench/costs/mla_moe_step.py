"""Operations of one training step of the Moonlight (MLA + MoE) chip share,
and of the calls of its two kernels.

Model work per token: 6 N over the weights a token's products use here
(every attention projection, the leading dense FFN, the router, the shared
experts, and the routed experts held here at their expected share of a
token's assignments, ``k * held / E``; the output head; the input
embedding is a gather and does not count), plus causal attention: q.k at
nope + rope and p.v at v_head per (query, key) pair, S / 2 pairs per
query for causality, times 3 for forward and backward.  Recomputation does
not count.

Kernel calls, for their roofline shares: a grouped product over ``n``
assignments moves ``n`` rows through one (D, F) or (F, D) matrix,
2 n D F operations, whichever of the three products and whether forward
(``gmm``) or backward (``gmm`` against the transposed weights, ``tgmm``);
a splash call over B rows of S tokens and H heads does the causal half of
its products: forward q.k and p.v, dq q.k, dO.v and dS.k, dkv q.k, dO.v,
p.dO and dS.q.
"""
from __future__ import annotations

from typing import Any, Dict


def _mla(cfg: Dict[str, Any]) -> Dict[str, int]:
    return {"qk": cfg["qk_nope_head_dim"] + cfg["qk_rope_head_dim"],
            "v": cfg["v_head_dim"], "H": cfg["num_attention_heads"]}


def matmul_params_per_token(cfg: Dict[str, Any]) -> float:
    D, H, R = cfg["hidden_size"], cfg["num_attention_heads"], cfg["kv_lora_rank"]
    a = _mla(cfg)
    nope, rope = cfg["qk_nope_head_dim"], cfg["qk_rope_head_dim"]
    attn = (D * H * a["qk"] + D * (R + rope) + R * H * (nope + a["v"])
            + H * a["v"] * D)
    dense = 3 * D * cfg["intermediate_size"]
    Fe = cfg["moe_intermediate_size"]
    E = cfg["deployment"]["n_routed_experts"]
    routed = cfg["num_experts_per_tok"] * cfg["n_routed_experts"] / E * 3 * D * Fe
    moe = routed + 3 * D * cfg["n_shared_experts"] * Fe + D * E
    L, lead = cfg["num_hidden_layers"], cfg["first_k_dense_replace"]
    return (L * attn + lead * dense + (L - lead) * moe
            + D * cfg["vocab_size"])


def flops_per_token(cfg: Dict[str, Any], seq_len: int) -> float:
    a = _mla(cfg)
    attn = 3 * 2 * seq_len / 2 * a["H"] * (a["qk"] + a["v"])
    return 6.0 * matmul_params_per_token(cfg) + attn * cfg["num_hidden_layers"]


def flops_per_step(cfg: Dict[str, Any], batch: int, seq_len: int) -> float:
    return flops_per_token(cfg, seq_len) * batch * seq_len


def gmm_flops(cfg: Dict[str, Any], assignments: float) -> float:
    """One grouped-product call over ``assignments`` rows."""
    return 2.0 * assignments * cfg["hidden_size"] * cfg["moe_intermediate_size"]


#: (q.k products, p.v-sized products) of each splash kernel
SPLASH_PRODUCTS = {"splash_mha_fwd": (1, 1), "splash_mha_dq": (2, 1),
                   "splash_mha_dkv": (2, 2)}


def splash_flops(cfg: Dict[str, Any], kernel: str, batch: int,
                 seq_len: int) -> float:
    """One call of splash kernel ``kernel`` (a key of ``SPLASH_PRODUCTS``)
    over ``batch`` rows: the causal half of its products."""
    a = _mla(cfg)
    nqk, nv = SPLASH_PRODUCTS[kernel]
    pairs = batch * a["H"] * seq_len * seq_len / 2
    return 2.0 * pairs * (nqk * a["qk"] + nv * a["v"])
