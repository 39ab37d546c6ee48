"""The decoder the benchmark trains: one full-sequence forward.

Structure (cfg.leading, then cfg.pattern × cfg.pattern_repeats, then
cfg.remainder):

  tokens ──embed──▶ [leading] ─▶ [ scan over repeats: pattern blocks ] ─▶ [remainder] ─▶ norm ─▶ unembed

Every block is causal self-attention over the row's segments
(grouped-query, or multi-head latent attention where ``cfg.mla`` is set),
pre-norm residual: x + attn(norm(x)), then x + mlp(norm(x)) where the MLP
is the dense ``config.LEADING_MLP`` for the leading layers and
``cfg.mlp_kind`` (SwiGLU or MoE) for the rest.  ``forward`` returns the
hidden states; the train step (``training/steps.py``) takes the chunked
loss over them.
"""
from __future__ import annotations

from collections import Counter
from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp

from ..kernels import ops as kernel_ops
from ..kernels.flash_attention import RESIDUALS as FLASH_RESIDUALS
from ..kernels.flash_attention import supports as flash_supports
from . import attention as attn
from .config import LEADING_MLP, ModelConfig
from .layers import embed, embedding_defs, mlp, mlp_defs, rmsnorm, rmsnorm_defs
from .moe import moe_defs, moe_ffn
from .params import ParamDef, stack_tree


# ======================================================================= defs
def mla_defs(cfg: ModelConfig) -> Dict[str, Any]:
    """Latent attention: W_q (or W_qa, its norm and W_qb), W_kva to the
    latent and the shared rotary key, the latent's norm, W_kvb to the
    per-head key and value, W_o."""
    a, D, H = cfg.mla, cfg.d_model, cfg.n_heads
    dt = jnp.dtype(cfg.param_dtype)
    d: Dict[str, Any] = {}
    if a.q_lora_rank:
        d["wq_a"] = ParamDef((D, a.q_lora_rank), ("embed", None), dt)
        d["q_norm"] = rmsnorm_defs(a.q_lora_rank)
        d["wq_b"] = ParamDef((a.q_lora_rank, H, a.qk_head_dim),
                             (None, "heads", None), dt)
    else:
        d["wq"] = ParamDef((D, H, a.qk_head_dim), ("embed", "heads", None), dt)
    d["wkv_a"] = ParamDef((D, a.kv_lora_rank + a.qk_rope_head_dim),
                          ("embed", None), dt)
    d["kv_norm"] = rmsnorm_defs(a.kv_lora_rank)
    d["wkv_b"] = ParamDef((a.kv_lora_rank, H, a.qk_nope_head_dim + a.v_head_dim),
                          (None, "heads", None), dt)
    d["wo"] = ParamDef((H, a.v_head_dim, D), ("heads", None, "embed"), dt, "scaled")
    return d


def attn_defs(cfg: ModelConfig) -> Dict[str, Any]:
    if cfg.mla is not None:
        return mla_defs(cfg)
    D, H, KV, hd = cfg.d_model, cfg.n_heads, cfg.n_kv_heads, cfg.head_dim
    dt = jnp.dtype(cfg.param_dtype)
    return {
        "wq": ParamDef((D, H, hd), ("embed", "heads", None), dt),
        "wk": ParamDef((D, KV, hd), ("embed", "kv", None), dt),
        "wv": ParamDef((D, KV, hd), ("embed", "kv", None), dt),
        "wo": ParamDef((H, hd, D), ("heads", None, "embed"), dt, "scaled"),
    }


def block_defs(cfg: ModelConfig, mlp_kind: Optional[str] = None) -> Dict[str, Any]:
    mlp_kind = mlp_kind or cfg.mlp_kind
    return {"pre_norm": rmsnorm_defs(cfg.d_model),
            "attn": attn_defs(cfg),
            "mlp_norm": rmsnorm_defs(cfg.d_model),
            "mlp": moe_defs(cfg) if mlp_kind == "moe" else mlp_defs(cfg)}


def model_defs(cfg: ModelConfig) -> Dict[str, Any]:
    """The full ParamDef tree.  Pattern blocks get a leading scan dim; a
    config with leading layers has a ``leading`` list of them."""
    defs: Dict[str, Any] = {"embed": embedding_defs(cfg)}
    if cfg.leading:
        defs["leading"] = [block_defs(cfg, LEADING_MLP) for _ in cfg.leading]
    defs["pattern"] = ([stack_tree(block_defs(cfg), cfg.pattern_repeats)
                        for _ in cfg.pattern] if cfg.pattern_repeats > 0 else [])
    defs["remainder"] = [block_defs(cfg) for _ in cfg.remainder]
    defs["final_norm"] = rmsnorm_defs(cfg.d_model)
    return defs


# ==================================================================== blocks
#: self-attention calls traced, by the path each was lowered to
#: ("kernel", "chunked", "naive"); counted at trace time, so a
#: scanned layer counts once per trace of the step
ATTENTION_PATHS: Counter = Counter()
#: kernel-path calls traced, by whether the layer checkpoint keeps the
#: kernel's output and log-sum-exp ("saved") or the backward runs the
#: forward kernel again ("recomputed"); counted at trace time
ATTENTION_RESIDUALS: Counter = Counter()


def _count_attention(cfg: ModelConfig, path: str) -> None:
    ATTENTION_PATHS[path] += 1
    if path == "kernel":
        kept = cfg.remat_policy != "nothing"   # see ``_remat_policy``
        ATTENTION_RESIDUALS["saved" if kept else "recomputed"] += 1


def _attention_path(cfg: ModelConfig, S: int, head_dim: int = 0,
                    head_dim_v: int = 0) -> str:
    """The path of one causal self-attention call over ``S`` tokens.

    The Pallas flash kernel takes it on a TPU that the process drives
    alone (XLA cannot partition a Pallas call over a mesh), at a sequence
    and head size the kernel supports (``head_dim`` of q and k,
    ``cfg.head_dim`` where 0; ``head_dim_v`` of v).  Everywhere else the
    jnp paths run: chunked over the keys above ``cfg.attn_chunk`` tokens,
    the whole logits tile at or below it."""
    if (jax.default_backend() == "tpu" and jax.device_count() == 1
            and flash_supports(S, head_dim or cfg.head_dim, head_dim_v)):
        return "kernel"
    return "chunked" if S > cfg.attn_chunk else "naive"


def _mla_qkv(cfg: ModelConfig, p: Dict[str, Any], x: jax.Array,
             pos: jax.Array) -> Tuple[jax.Array, jax.Array, jax.Array]:
    """Latent attention's projections (the equations of
    ``configs/moonlight_16b_a3b.py``): q (B, S, H, nope + rope) in float32,
    unrounded; k (B, S, H, nope + rope) and v (B, S, H, v_head) in the
    activation dtype, the rotary part of k shared by every head."""
    a = cfg.mla
    dt = x.dtype
    if a.q_lora_rank:
        cq = rmsnorm(p["q_norm"], x @ p["wq_a"], cfg.norm_eps)
        q = jnp.einsum("bsr,rhk->bshk", cq, p["wq_b"],
                       preferred_element_type=jnp.float32)
    else:
        q = jnp.einsum("bsd,dhk->bshk", x, p["wq"],
                       preferred_element_type=jnp.float32)
    q_nope, q_pe = jnp.split(q, [a.qk_nope_head_dim], axis=-1)
    q = jnp.concatenate([q_nope, attn.rope(q_pe, pos, cfg.rope_theta)], axis=-1)
    ckv = x @ p["wkv_a"]
    c, k_pe = jnp.split(ckv, [a.kv_lora_rank], axis=-1)
    c = rmsnorm(p["kv_norm"], c, cfg.norm_eps)
    kv = jnp.einsum("bsr,rhk->bshk", c, p["wkv_b"])
    k_nope, v = jnp.split(kv, [a.qk_nope_head_dim], axis=-1)
    k_pe = attn.rope(k_pe[:, :, None, :], pos, cfg.rope_theta)
    k = jnp.concatenate(
        [k_nope, jnp.broadcast_to(k_pe, k_nope.shape[:3] + k_pe.shape[3:])],
        axis=-1).astype(dt)
    return q, k, v.astype(dt)


def _mla_attention(cfg: ModelConfig, p: Dict[str, Any], x: jax.Array,
                   seg: jax.Array, pos: jax.Array) -> jax.Array:
    """Causal latent self-attention over the row's segments, scale
    (nope + rope) ** -0.5.  q is rounded to the activation dtype once: by
    the kernel's wrapper after it applies the scale in float32, on the jnp
    paths before the scale, which those apply to their float32 logits."""
    a = cfg.mla
    S = x.shape[1]
    q, k, v = _mla_qkv(cfg, p, x, pos)
    path = _attention_path(cfg, S, a.qk_head_dim, a.v_head_dim)
    _count_attention(cfg, path)
    if path == "kernel":
        o = kernel_ops.flash_attention(q, k, v, seg)
    elif path == "chunked":
        o = attn.attention_chunked(q.astype(x.dtype), k, v, pos, pos, seg, seg,
                                   chunk=cfg.attn_chunk)
    else:
        o = attn.attention_naive(q.astype(x.dtype), k, v, pos, pos, seg, seg)
    return jnp.einsum("bshk,hkd->bsd", o, p["wo"])


def _self_attention(cfg: ModelConfig, p: Dict[str, jax.Array], x: jax.Array,
                    seg: jax.Array, pos: jax.Array) -> jax.Array:
    if cfg.mla is not None:
        return _mla_attention(cfg, p, x, seg, pos)
    S = x.shape[1]
    q = jnp.einsum("bsd,dhk->bshk", x, p["wq"])
    k = jnp.einsum("bsd,dhk->bshk", x, p["wk"])
    v = jnp.einsum("bsd,dhk->bshk", x, p["wv"])
    q = attn.rope(q, pos, cfg.rope_theta)
    k = attn.rope(k, pos, cfg.rope_theta)
    path = _attention_path(cfg, S)
    _count_attention(cfg, path)
    if path == "kernel":
        o = kernel_ops.flash_attention(q, k, v, seg)
    elif path == "chunked":
        o = attn.attention_chunked(q, k, v, pos, pos, seg, seg,
                                   chunk=cfg.attn_chunk)
    else:
        o = attn.attention_naive(q, k, v, pos, pos, seg, seg)
    return jnp.einsum("bshk,hkd->bsd", o, p["wo"])


def _apply_mlp(cfg: ModelConfig, p: Dict[str, Any], x: jax.Array,
               constrain=None, mlp_kind: Optional[str] = None,
               valid: Optional[jax.Array] = None
               ) -> Tuple[jax.Array, jax.Array, Dict[str, jax.Array]]:
    """Returns (mlp_out, moe_lb_loss, router stats: empty for a dense MLP;
    see ``moe.moe_ffn``)."""
    mlp_kind = mlp_kind or cfg.mlp_kind
    if mlp_kind == "moe":
        out, aux = moe_ffn(p, x, cfg, constrain=constrain, valid=valid)
        stats = {k: v for k, v in aux.items() if k not in ("lb_loss", "drop_frac")}
        return out, aux["lb_loss"], stats
    return mlp(p, x), jnp.zeros((), jnp.float32), {}


def apply_block(cfg: ModelConfig, p: Dict[str, Any], x: jax.Array,
                *, seg: jax.Array, pos: jax.Array,
                constrain=None, mlp_kind: Optional[str] = None
                ) -> Tuple[jax.Array, jax.Array, Dict[str, jax.Array]]:
    """One layer.  Returns (x, moe_aux_loss, router stats)."""
    h = rmsnorm(p["pre_norm"], x, cfg.norm_eps)
    x = x + _self_attention(cfg, p["attn"], h, seg, pos)
    h = rmsnorm(p["mlp_norm"], x, cfg.norm_eps)
    out, aux, stats = _apply_mlp(cfg, p["mlp"], h, constrain=constrain,
                                 mlp_kind=mlp_kind, valid=seg > 0)
    return x + out, aux, stats


def _remat_policy(cfg: ModelConfig):
    """The layer checkpoint's policy.  Every policy but ``nothing`` keeps
    the attention kernel's residuals (``FLASH_RESIDUALS``); a call on a jnp
    path carries no such name and is recomputed as the policy says."""
    cp = jax.checkpoint_policies
    if cfg.remat_policy == "nothing":
        return cp.nothing_saveable
    residuals = cp.save_only_these_names(FLASH_RESIDUALS)
    if cfg.remat_policy == "save_layer_inputs":
        return residuals
    return cp.save_from_both_policies({
        "dots": cp.dots_saveable,
        "dots_no_batch": cp.dots_with_no_batch_dims_saveable,
    }[cfg.remat_policy], residuals)


# =================================================================== forward
def forward(cfg: ModelConfig, params: Dict[str, Any], batch: Dict[str, jax.Array],
            constrain=None) -> Tuple[jax.Array, Dict[str, Any]]:
    """Full-sequence forward.  batch: tokens/segments/positions (B,S).
    Returns (hidden (B,S,D), aux): aux holds
    ``moe_aux`` (the summed balance loss) and ``router``, each MoE layer's
    router stats in the params' layout (``leading``/``pattern``/
    ``remainder``; scanned layers stacked), empty dicts for dense layers.

    ``constrain("hidden", x)`` re-pins the residual stream after every block:
    without it, GSPMD sometimes migrates the FSDP params' "data" sharding onto
    the *embed* dim of activation gradients (full-batch all-reduces in the
    backward — verified on gemma-7b)."""
    seg = batch["segments"]
    pos = batch["positions"]
    pin = (lambda h: constrain("hidden", h)) if constrain else (lambda h: h)
    x = pin(embed(params["embed"], batch["tokens"], cfg))
    router: Dict[str, Any] = {}

    def unrolled(section, mlp_kind, x, aux):
        # per-layer remat for unrolled blocks (the scan body's policy)
        blk = jax.checkpoint(
            lambda p, h: apply_block(cfg, p, h, seg=seg, pos=pos,
                                     constrain=constrain, mlp_kind=mlp_kind),
            policy=_remat_policy(cfg), prevent_cse=False)
        stats = []
        for layer_params in params[section]:
            x, a, st = blk(layer_params, x)
            x = pin(x)
            aux = aux + a
            stats.append(st)
        router[section] = stats
        return x, aux

    def body(carry, layer_params):
        h, aux = carry
        stats = []
        for i in range(len(cfg.pattern)):
            h, a, st = apply_block(cfg, layer_params[i], h,
                                   seg=seg, pos=pos, constrain=constrain)
            h = pin(h)
            aux = aux + a
            stats.append(st)
        return (h, aux), stats

    aux = jnp.zeros((), jnp.float32)
    if cfg.leading:
        x, aux = unrolled("leading", LEADING_MLP, x, aux)
    router["pattern"] = []
    if cfg.pattern_repeats > 0:
        body_r = jax.checkpoint(body, policy=_remat_policy(cfg),
                                prevent_cse=False)
        (x, aux), router["pattern"] = jax.lax.scan(body_r, (x, aux),
                                                   params["pattern"])
    x, aux = unrolled("remainder", cfg.mlp_kind, x, aux)
    x = rmsnorm(params["final_norm"], x, cfg.norm_eps)
    return x, {"moe_aux": aux, "router": router}
