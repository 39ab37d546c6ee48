"""The flexible decoder: one parameterized definition covering every arch.

Structure (cfg.leading, then cfg.pattern × cfg.pattern_repeats, then
cfg.remainder):

  tokens ──embed──▶ [leading] ─▶ [ scan over repeats: pattern blocks ] ─▶ [remainder] ─▶ norm ─▶ unembed

Block kinds: attn / swa / local (GQA self-attention, optionally windowed;
multi-head latent attention where ``cfg.mla`` is set), cross
(cross-attention to stubbed encoder embeddings), ssd (Mamba-2), rec
(RG-LRU).  Each block is pre-norm residual: x + mixer(norm(x)), then
x + mlp(norm(x)) where the MLP may be dense or MoE: the dense
``config.LEADING_MLP`` for the leading layers, ``cfg.mlp_kind`` for the rest.

Three entry points (pure functions of (cfg, params, batch)):
  forward(...)            — full-sequence training forward -> hidden states
  prefill(...)            — forward + populate decode caches, last-pos logits
  decode_step(...)        — one-token serve step against caches

Caches are ParamDef trees too (see ``cache_defs``) so the AOT dry-run can
shard them exactly like parameters.
"""
from __future__ import annotations

from collections import Counter
from typing import Any, Dict, List, Optional, Tuple

import jax
import jax.numpy as jnp

from ..kernels import ops as kernel_ops
from ..kernels.flash_attention import RESIDUALS as FLASH_RESIDUALS
from ..kernels.flash_attention import supports as flash_supports
from . import attention as attn
from .config import LEADING_MLP, ModelConfig
from .layers import embed, embedding_defs, mlp, mlp_defs, rmsnorm, rmsnorm_defs, unembed
from .moe import moe_defs, moe_ffn
from .params import ParamDef, ParamTree, stack_tree
from .rglru import rglru_defs, rglru_mixer
from .ssd import ssd_defs, ssd_dims, ssd_mixer

ATTN_KINDS = ("attn", "swa", "local", "cross")


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


def block_defs(cfg: ModelConfig, kind: str,
               mlp_kind: Optional[str] = None) -> Dict[str, Any]:
    mlp_kind = mlp_kind or cfg.mlp_kind
    d: Dict[str, Any] = {"pre_norm": rmsnorm_defs(cfg.d_model)}
    if kind in ATTN_KINDS:
        d["attn"] = attn_defs(cfg)
        d["mlp_norm"] = rmsnorm_defs(cfg.d_model)
        d["mlp"] = (moe_defs(cfg) if mlp_kind == "moe"
                    else mlp_defs(cfg.replace(mlp_kind=mlp_kind)))
    elif kind == "ssd":
        d["mixer"] = ssd_defs(cfg)
        if cfg.mlp_kind != "none":
            d["mlp_norm"] = rmsnorm_defs(cfg.d_model)
            d["mlp"] = mlp_defs(cfg)
    elif kind == "rec":
        d["mixer"] = rglru_defs(cfg)
        d["mlp_norm"] = rmsnorm_defs(cfg.d_model)
        d["mlp"] = mlp_defs(cfg)
    else:
        raise ValueError(f"unknown block kind {kind!r}")
    return d


def model_defs(cfg: ModelConfig) -> Dict[str, Any]:
    """The full ParamDef tree.  Pattern blocks get a leading scan dim; a
    config with leading layers has a ``leading`` list of them."""
    defs: Dict[str, Any] = {"embed": embedding_defs(cfg)}
    if cfg.leading:
        defs["leading"] = [block_defs(cfg, k, LEADING_MLP)
                           for k in cfg.leading]
    defs["pattern"] = ([stack_tree(block_defs(cfg, k), cfg.pattern_repeats)
                        for k in cfg.pattern] if cfg.pattern_repeats > 0 else [])
    defs["remainder"] = [block_defs(cfg, k) for k in cfg.remainder]
    defs["final_norm"] = rmsnorm_defs(cfg.d_model)
    return defs


# ----------------------------------------------------------------- cache defs
def _attn_cache_len(cfg: ModelConfig, kind: str, max_len: int) -> int:
    window = cfg.window if kind in ("swa", "local") else None
    return min(window, max_len) if window else max_len


def cache_defs(cfg: ModelConfig, batch: int, max_len: int) -> Dict[str, Any]:
    """Decode-state ParamDef tree mirroring the block structure."""
    dt = jnp.dtype(cfg.dtype)
    KV, hd, hd_v = cfg.n_kv_heads, cfg.head_dim, cfg.head_dim
    if cfg.mla is not None:      # keys and values cached per head, expanded
        KV, hd, hd_v = cfg.n_heads, cfg.mla.qk_head_dim, cfg.mla.v_head_dim

    def one(kind: str) -> Dict[str, Any]:
        if kind == "cross":
            Ne = cfg.cross_attn_kv_len
            return {"k": ParamDef((batch, Ne, KV, hd), ("cache_batch", "cache_len", "kv", None), dt, "zeros"),
                    "v": ParamDef((batch, Ne, KV, hd), ("cache_batch", "cache_len", "kv", None), dt, "zeros")}
        if kind in ATTN_KINDS:
            C = _attn_cache_len(cfg, kind, max_len)
            return {"k": ParamDef((batch, C, KV, hd), ("cache_batch", "cache_len", "kv", None), dt, "zeros"),
                    "v": ParamDef((batch, C, KV, hd_v), ("cache_batch", "cache_len", "kv", None), dt, "zeros")}
        if kind == "ssd":
            d_in, nh, P, G, N = ssd_dims(cfg)
            s = cfg.ssm
            conv_ch = d_in + 2 * G * N
            return {"conv": ParamDef((batch, s.conv_width - 1, conv_ch),
                                     ("cache_batch", None, "heads"), dt, "zeros"),
                    "ssm": ParamDef((batch, nh, P, N),
                                    ("cache_batch", "heads", None, None), jnp.float32, "zeros")}
        if kind == "rec":
            r = cfg.rglru
            W = (r.lru_width or cfg.d_model) if r else cfg.d_model
            K = r.conv_width if r else 4
            return {"conv": ParamDef((batch, K - 1, W), ("cache_batch", None, "ffn"), dt, "zeros"),
                    "h": ParamDef((batch, W), ("cache_batch", "ffn"), jnp.float32, "zeros")}
        raise ValueError(kind)

    out: Dict[str, Any] = {}
    if cfg.leading:
        out["leading"] = [one(k) for k in cfg.leading]
    out["pattern"] = ([stack_tree(one(k), cfg.pattern_repeats)
                       for k in cfg.pattern] if cfg.pattern_repeats > 0 else [])
    out["remainder"] = [one(k) for k in cfg.remainder]
    return out


# ==================================================================== blocks
def _pin_w(constrain, name: str, w: jax.Array) -> jax.Array:
    return constrain(name, w) if constrain is not None else w


#: self-attention calls traced, by the path each was lowered to
#: ("kernel", "chunked", "naive", "local"); counted at trace time, so a
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


def _attention_path(cfg: ModelConfig, window: Optional[int], S: int,
                    head_dim: int = 0, head_dim_v: int = 0) -> str:
    """The path of one causal self-attention call over ``S`` tokens.

    The Pallas flash kernel takes it on a TPU that the process drives
    alone (XLA cannot partition a Pallas call over a mesh), without a
    window, at a sequence and head size the kernel supports (``head_dim``
    of q and k, ``cfg.head_dim`` where 0; ``head_dim_v`` of v), and off the
    dry-run cost path
    (``unroll_scans``: XLA's cost analysis sees no FLOPs in a Pallas
    call).  Everywhere else the jnp paths run as they always have."""
    if window is not None and S % window == 0 and S // window >= 2:
        return "local"
    if cfg.attn_impl != "chunked":
        return "naive"
    if (jax.default_backend() == "tpu" and jax.device_count() == 1
            and window is None and not cfg.unroll_scans
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
    path = _attention_path(cfg, None, S, a.qk_head_dim, a.v_head_dim)
    _count_attention(cfg, path)
    if path == "kernel":
        o = kernel_ops.flash_attention(q, k, v, seg)
    elif path == "chunked":
        o = attn.attention_chunked(q.astype(x.dtype), k, v, pos, pos, seg, seg,
                                   chunk=cfg.attn_chunk, unroll=cfg.unroll_scans,
                                   logits_dtype=jnp.dtype(cfg.attn_logits_dtype))
    else:
        o = attn.attention_naive(q.astype(x.dtype), k, v, pos, pos, seg, seg)
    return jnp.einsum("bshk,hkd->bsd", o, p["wo"])


def _self_attention(cfg: ModelConfig, kind: str, p: Dict[str, jax.Array],
                    x: jax.Array, seg: jax.Array, pos: jax.Array,
                    constrain=None) -> jax.Array:
    if cfg.mla is not None:
        return _mla_attention(cfg, p, x, seg, pos)
    B, S, D = x.shape
    q = jnp.einsum("bsd,dhk->bshk", x, _pin_w(constrain, "w_q", p["wq"]))
    k = jnp.einsum("bsd,dhk->bshk", x, _pin_w(constrain, "w_kv", p["wk"]))
    v = jnp.einsum("bsd,dhk->bshk", x, _pin_w(constrain, "w_kv", p["wv"]))
    q = attn.rope(q, pos, cfg.rope_theta)
    k = attn.rope(k, pos, cfg.rope_theta)
    window = cfg.window if kind in ("swa", "local") else None
    path = _attention_path(cfg, window, S)
    _count_attention(cfg, path)
    if path == "local":
        o = attn.attention_local(q, k, v, pos, pos, seg, seg, window=window)
    elif path == "kernel":
        o = kernel_ops.flash_attention(q, k, v, seg)
    elif path == "chunked":
        o = attn.attention_chunked(q, k, v, pos, pos, seg, seg,
                                   chunk=cfg.attn_chunk, window=window,
                                   unroll=cfg.unroll_scans,
                                   logits_dtype=jnp.dtype(cfg.attn_logits_dtype))
    else:
        o = attn.attention_naive(q, k, v, pos, pos, seg, seg, window=window)
    return jnp.einsum("bshk,hkd->bsd", o, _pin_w(constrain, "w_o", p["wo"]))


def _cross_attention(cfg: ModelConfig, p: Dict[str, jax.Array], x: jax.Array,
                     seg: jax.Array, enc: jax.Array, constrain=None) -> jax.Array:
    q = jnp.einsum("bsd,dhk->bshk", x, _pin_w(constrain, "w_q", p["wq"]))
    k = jnp.einsum("bsd,dhk->bshk", enc.astype(x.dtype),
                   _pin_w(constrain, "w_kv", p["wk"]))
    v = jnp.einsum("bsd,dhk->bshk", enc.astype(x.dtype),
                   _pin_w(constrain, "w_kv", p["wv"]))
    o = attn.attention_cross(q, k, v, seg)
    return jnp.einsum("bshk,hkd->bsd", o, _pin_w(constrain, "w_o", p["wo"]))


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
    if constrain is not None and mlp_kind in ("swiglu", "geglu", "gelu"):
        p = dict(p)
        for key in ("wi_gate", "wi_up", "wi"):
            if key in p:
                p[key] = constrain("w_in", p[key])
        p["wo"] = constrain("w_out", p["wo"])
    return mlp(p, x, mlp_kind), jnp.zeros((), jnp.float32), {}


def apply_block(cfg: ModelConfig, kind: str, p: Dict[str, Any], x: jax.Array,
                *, seg: jax.Array, pos: jax.Array,
                enc: Optional[jax.Array] = None,
                constrain=None, mlp_kind: Optional[str] = None
                ) -> Tuple[jax.Array, jax.Array, Dict[str, jax.Array]]:
    """Training/prefill-forward block.  Returns (x, moe_aux_loss, router
    stats)."""
    h = rmsnorm(p["pre_norm"], x, cfg.norm_eps)
    if kind in ("attn", "swa", "local"):
        x = x + _self_attention(cfg, kind, p["attn"], h, seg, pos)
    elif kind == "cross":
        assert enc is not None, "cross block needs encoder embeddings"
        x = x + _cross_attention(cfg, p["attn"], h, seg, enc)
    elif kind == "ssd":
        out, _ = ssd_mixer(p["mixer"], h, cfg, seg=seg)
        x = x + out
    elif kind == "rec":
        out, _ = rglru_mixer(p["mixer"], h, cfg, seg=seg)
        x = x + out
    aux = jnp.zeros((), jnp.float32)
    stats: Dict[str, jax.Array] = {}
    if "mlp" in p:
        h = rmsnorm(p["mlp_norm"], x, cfg.norm_eps)
        out, aux, stats = _apply_mlp(cfg, p["mlp"], h, constrain=constrain,
                                     mlp_kind=mlp_kind, valid=seg > 0)
        x = x + out
    return x, aux, stats


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
    """Full-sequence forward.  batch: tokens/segments/positions (B,S)
    [+ encoder_embeds (B,Ne,D)].  Returns (hidden (B,S,D), aux): aux holds
    ``moe_aux`` (the summed balance loss) and ``router``, each MoE layer's
    router stats in the params' layout (``leading``/``pattern``/
    ``remainder``; scanned layers stacked), empty dicts for dense layers.

    ``constrain("hidden", x)`` re-pins the residual stream after every block:
    without it, GSPMD sometimes migrates the FSDP params' "data" sharding onto
    the *embed* dim of activation gradients (full-batch all-reduces in the
    backward — verified on gemma-7b)."""
    seg = batch["segments"]
    pos = batch["positions"]
    enc = batch.get("encoder_embeds")
    pin = (lambda h: constrain("hidden", h)) if constrain else (lambda h: h)
    x = pin(embed(params["embed"], batch["tokens"], cfg))
    router: Dict[str, Any] = {}

    def unrolled(section, kinds, mlp_kind, x, aux):
        stats = []
        for i, kind in enumerate(kinds):
            # per-layer remat for unrolled blocks (same policy as the scan
            # body, so production and dry-run-cost graphs do the same
            # recompute work)
            blk = jax.checkpoint(
                lambda p, h, k=kind: apply_block(cfg, k, p, h, seg=seg, pos=pos,
                                                 enc=enc, constrain=constrain,
                                                 mlp_kind=mlp_kind),
                policy=_remat_policy(cfg), prevent_cse=False)
            x, a, st = blk(params[section][i], x)
            x = pin(x)
            aux = aux + a
            stats.append(st)
        router[section] = stats
        return x, aux

    def body(carry, layer_params):
        h, aux = carry
        stats = []
        for i, kind in enumerate(cfg.pattern):
            h, a, st = apply_block(cfg, kind, layer_params[i], h,
                                   seg=seg, pos=pos, enc=enc, constrain=constrain)
            h = pin(h)
            aux = aux + a
            stats.append(st)
        return (h, aux), stats

    aux = jnp.zeros((), jnp.float32)
    if cfg.leading:
        x, aux = unrolled("leading", cfg.leading, LEADING_MLP, x, aux)
    router["pattern"] = []
    if cfg.pattern_repeats > 0:
        body_r = jax.checkpoint(body, policy=_remat_policy(cfg),
                                prevent_cse=False)
        (x, aux), router["pattern"] = jax.lax.scan(body_r, (x, aux),
                                                   params["pattern"])
    x, aux = unrolled("remainder", cfg.remainder, cfg.mlp_kind, x, aux)
    x = rmsnorm(params["final_norm"], x, cfg.norm_eps)
    return x, {"moe_aux": aux, "router": router}


def logits_fn(cfg: ModelConfig, params: Dict[str, Any],
              hidden: jax.Array) -> jax.Array:
    return unembed(params["embed"], hidden, cfg)


# ==================================================================== decode
def _decode_attn(cfg: ModelConfig, kind: str, p: Dict[str, Any],
                 x: jax.Array, cache: Dict[str, jax.Array], pos: jax.Array,
                 constrain=None) -> Tuple[jax.Array, Dict[str, jax.Array]]:
    """One-token self-attention against a (ring) KV cache.  x (B,1,D).

    ``pos`` is a scalar (uniform batch — the dry-run/production fast path,
    dynamic-update-slice cache write) or a (B,) vector (continuous batching:
    per-slot positions, scatter cache write)."""
    B = x.shape[0]
    C = cache["k"].shape[1]
    window = cfg.window if kind in ("swa", "local") else None
    per_row = pos.ndim == 1
    posb = (pos[:, None] if per_row
            else jnp.broadcast_to(pos[None, None], (B, 1))).astype(jnp.int32)
    if cfg.mla is not None:
        q, k, v = _mla_qkv(cfg, p, x, posb)
        q = q.astype(x.dtype)
    else:
        q = jnp.einsum("bsd,dhk->bshk", x, _pin_w(constrain, "w_q", p["wq"]))
        k = jnp.einsum("bsd,dhk->bshk", x, _pin_w(constrain, "w_kv", p["wk"]))
        v = jnp.einsum("bsd,dhk->bshk", x, _pin_w(constrain, "w_kv", p["wv"]))
        q = attn.rope(q, posb, cfg.rope_theta)
        k = attn.rope(k, posb, cfg.rope_theta)
    slot = (pos % C) if window is not None else jnp.minimum(pos, C - 1)
    if per_row:
        rows = jnp.arange(B)
        k_cache = cache["k"].at[rows, slot].set(k[:, 0].astype(cache["k"].dtype))
        v_cache = cache["v"].at[rows, slot].set(v[:, 0].astype(cache["v"].dtype))
    else:
        k_cache = jax.lax.dynamic_update_slice(
            cache["k"], k.astype(cache["k"].dtype), (0, slot, 0, 0))
        v_cache = jax.lax.dynamic_update_slice(
            cache["v"], v.astype(cache["v"].dtype), (0, slot, 0, 0))
    n_valid = jnp.minimum(pos + 1, C)
    cache_len = jnp.broadcast_to(n_valid, (B,))
    o = attn.attention_decode(q, k_cache, v_cache, cache_len, softcap=0.0)
    return (jnp.einsum("bshk,hkd->bsd", o, _pin_w(constrain, "w_o", p["wo"])),
            {"k": k_cache, "v": v_cache})


def _decode_cross(cfg: ModelConfig, p: Dict[str, Any], x: jax.Array,
                  cache: Dict[str, jax.Array]) -> jax.Array:
    """Cross-attention during decode: cache holds projected encoder kv."""
    q = jnp.einsum("bsd,dhk->bshk", x, p["wq"])
    seg = jnp.ones(x.shape[:2], jnp.int32)
    o = attn.attention_cross(q, cache["k"].astype(x.dtype),
                             cache["v"].astype(x.dtype), seg)
    return jnp.einsum("bshk,hkd->bsd", o, p["wo"])


def decode_block(cfg: ModelConfig, kind: str, p: Dict[str, Any], x: jax.Array,
                 cache: Dict[str, Any], pos: jax.Array, constrain=None,
                 mlp_kind: Optional[str] = None
                 ) -> Tuple[jax.Array, Dict[str, Any]]:
    h = rmsnorm(p["pre_norm"], x, cfg.norm_eps)
    if kind in ("attn", "swa", "local"):
        out, cache = _decode_attn(cfg, kind, p["attn"], h, cache, pos,
                                  constrain=constrain)
        x = x + out
    elif kind == "cross":
        x = x + _decode_cross(cfg, p["attn"], h, cache)
    elif kind == "ssd":
        out, cache = ssd_mixer(p["mixer"], h, cfg, decode_state=cache)
        x = x + out
    elif kind == "rec":
        out, cache = rglru_mixer(p["mixer"], h, cfg, decode_state=cache)
        x = x + out
    if "mlp" in p:
        h = rmsnorm(p["mlp_norm"], x, cfg.norm_eps)
        out, _, _ = _apply_mlp(cfg, p["mlp"], h, constrain=constrain,
                               mlp_kind=mlp_kind)
        x = x + out
    return x, cache


def decode_step(cfg: ModelConfig, params: Dict[str, Any],
                cache: Dict[str, Any], tokens: jax.Array, pos: jax.Array,
                constrain=None) -> Tuple[jax.Array, Dict[str, Any]]:
    """One serve step: tokens (B,1) at position ``pos`` (scalar int32, or a
    (B,) vector of per-slot positions for continuous batching).
    Returns (logits (B,1,V), new cache)."""
    pos = jnp.asarray(pos, jnp.int32)
    pin = (lambda h: constrain("hidden", h)) if constrain else (lambda h: h)
    x = pin(embed(params["embed"], tokens, cfg))

    def body(h, xs):
        layer_params, layer_cache = xs
        new_caches = []
        for i, kind in enumerate(cfg.pattern):
            h, nc = decode_block(cfg, kind, layer_params[i], h,
                                 layer_cache[i], pos, constrain=constrain)
            h = pin(h)
            new_caches.append(nc)
        return h, new_caches

    new_cache: Dict[str, Any] = {"pattern": [], "remainder": []}
    if cfg.leading:
        new_cache["leading"] = []
        for i, kind in enumerate(cfg.leading):
            x, nc = decode_block(cfg, kind, params["leading"][i], x,
                                 cache["leading"][i], pos, constrain=constrain,
                                 mlp_kind=LEADING_MLP)
            x = pin(x)
            new_cache["leading"].append(nc)
    if cfg.pattern_repeats > 0:
        x, new_cache["pattern"] = jax.lax.scan(
            body, x, (params["pattern"], cache["pattern"]))
    for i, kind in enumerate(cfg.remainder):
        x, nc = decode_block(cfg, kind, params["remainder"][i], x,
                             cache["remainder"][i], pos, constrain=constrain)
        x = pin(x)
        new_cache["remainder"].append(nc)
    x = rmsnorm(params["final_norm"], x, cfg.norm_eps)
    logits = unembed(params["embed"], x, cfg)
    if constrain is not None:
        logits = constrain("logits", logits)
    return logits, new_cache


# =================================================================== prefill
def prefill(cfg: ModelConfig, params: Dict[str, Any],
            batch: Dict[str, jax.Array], max_len: int, constrain=None
            ) -> Tuple[jax.Array, Dict[str, Any]]:
    """Forward + cache population.  Returns (last-position logits, cache).

    Cache layout matches ``cache_defs(cfg, B, max_len)``: full-attention
    caches hold positions [0, S); windowed caches hold the last ``window``
    keys in ring order (slot = pos % window).
    """
    seg, pos = batch["segments"], batch["positions"]
    enc = batch.get("encoder_embeds")
    B, S = batch["tokens"].shape
    pin = (lambda h: constrain("hidden", h)) if constrain else (lambda h: h)
    x = pin(embed(params["embed"], batch["tokens"], cfg))

    def fill_attn(kind: str, p: Dict[str, Any], h: jax.Array) -> Dict[str, jax.Array]:
        if kind == "cross":
            k = jnp.einsum("bsd,dhk->bshk", enc.astype(h.dtype),
                           _pin_w(constrain, "w_kv", p["attn"]["wk"]))
            v = jnp.einsum("bsd,dhk->bshk", enc.astype(h.dtype),
                           _pin_w(constrain, "w_kv", p["attn"]["wv"]))
            return {"k": k, "v": v}
        C = _attn_cache_len(cfg, kind, max_len)
        if cfg.mla is not None:
            _, k, v = _mla_qkv(cfg, p["attn"], h, pos)
        else:
            k = jnp.einsum("bsd,dhk->bshk", h,
                           _pin_w(constrain, "w_kv", p["attn"]["wk"]))
            k = attn.rope(k, pos, cfg.rope_theta)
            v = jnp.einsum("bsd,dhk->bshk", h,
                           _pin_w(constrain, "w_kv", p["attn"]["wv"]))
        if C >= S:
            pad = lambda a: jnp.zeros((B, C - S) + a.shape[2:], a.dtype)
            return {"k": jnp.concatenate([k, pad(k)], 1),
                    "v": jnp.concatenate([v, pad(v)], 1)}
        # ring: keep last C keys, placed at slot = pos % C
        kl, vl = k[:, S - C:], v[:, S - C:]
        shift = S % C
        idx = (jnp.arange(C) - shift) % C
        return {"k": kl[:, idx], "v": vl[:, idx]}

    def run_block(kind: str, p: Dict[str, Any], h: jax.Array,
                  mlp_kind: Optional[str] = None
                  ) -> Tuple[jax.Array, Dict[str, Any]]:
        hn = rmsnorm(p["pre_norm"], h, cfg.norm_eps)
        if kind in ATTN_KINDS:
            c = fill_attn(kind, p, hn)
            if kind == "cross":
                h = h + _cross_attention(cfg, p["attn"], hn, seg, enc,
                                         constrain=constrain)
            else:
                h = h + _self_attention(cfg, kind, p["attn"], hn, seg, pos,
                                        constrain=constrain)
        elif kind == "ssd":
            out, c = ssd_mixer(p["mixer"], hn, cfg, seg=seg)
            h = h + out
        elif kind == "rec":
            out, c = rglru_mixer(p["mixer"], hn, cfg, seg=seg)
            h = h + out
        if "mlp" in p:
            hn = rmsnorm(p["mlp_norm"], h, cfg.norm_eps)
            out, _, _ = _apply_mlp(cfg, p["mlp"], hn, constrain=constrain,
                                   mlp_kind=mlp_kind, valid=seg > 0)
            h = h + out
        return h, c

    def body(h, layer_params):
        caches = []
        for i, kind in enumerate(cfg.pattern):
            h, c = run_block(kind, layer_params[i], h)
            h = pin(h)
            caches.append(c)
        return h, caches

    cache: Dict[str, Any] = {"pattern": [], "remainder": []}
    if cfg.leading:
        cache["leading"] = []
        for i, kind in enumerate(cfg.leading):
            x, c = run_block(kind, params["leading"][i], x, LEADING_MLP)
            x = pin(x)
            cache["leading"].append(c)
    if cfg.pattern_repeats > 0:
        body_r = jax.checkpoint(body, policy=_remat_policy(cfg), prevent_cse=False)
        x, cache["pattern"] = jax.lax.scan(body_r, x, params["pattern"])
    for i, kind in enumerate(cfg.remainder):
        x, c = run_block(kind, params["remainder"][i], x)
        x = pin(x)
        cache["remainder"].append(c)
    x = rmsnorm(params["final_norm"], x, cfg.norm_eps)
    logits = unembed(params["embed"], x[:, -1:], cfg)
    return logits, cache
