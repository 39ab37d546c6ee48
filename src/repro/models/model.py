"""The flexible decoder: one parameterized definition covering all 10 archs.

Structure (cfg.pattern × cfg.pattern_repeats, then cfg.remainder):

  tokens ──embed──▶ [ scan over repeats: pattern blocks ] ─▶ [remainder] ─▶ norm ─▶ unembed

Block kinds: attn / swa / local (GQA self-attention, optionally windowed),
cross (cross-attention to stubbed encoder embeddings), ssd (Mamba-2),
rec (RG-LRU).  Each block is pre-norm residual: x + mixer(norm(x)), then
x + mlp(norm(x)) where the MLP may be dense or MoE ("moe" mlp_kind).

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
from ..kernels.flash_attention import supports as flash_supports
from . import attention as attn
from .config import ModelConfig
from .layers import embed, embedding_defs, mlp, mlp_defs, rmsnorm, rmsnorm_defs, unembed
from .moe import moe_defs, moe_ffn
from .params import ParamDef, ParamTree, stack_tree
from .rglru import rglru_defs, rglru_mixer
from .ssd import ssd_defs, ssd_dims, ssd_mixer

ATTN_KINDS = ("attn", "swa", "local", "cross")


# ======================================================================= defs
def attn_defs(cfg: ModelConfig) -> Dict[str, ParamDef]:
    D, H, KV, hd = cfg.d_model, cfg.n_heads, cfg.n_kv_heads, cfg.head_dim
    dt = jnp.dtype(cfg.param_dtype)
    return {
        "wq": ParamDef((D, H, hd), ("embed", "heads", None), dt),
        "wk": ParamDef((D, KV, hd), ("embed", "kv", None), dt),
        "wv": ParamDef((D, KV, hd), ("embed", "kv", None), dt),
        "wo": ParamDef((H, hd, D), ("heads", None, "embed"), dt, "scaled"),
    }


def block_defs(cfg: ModelConfig, kind: str) -> Dict[str, Any]:
    d: Dict[str, Any] = {"pre_norm": rmsnorm_defs(cfg.d_model)}
    if kind in ATTN_KINDS:
        d["attn"] = attn_defs(cfg)
        d["mlp_norm"] = rmsnorm_defs(cfg.d_model)
        d["mlp"] = moe_defs(cfg) if cfg.mlp_kind == "moe" else mlp_defs(cfg)
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
    """The full ParamDef tree.  Pattern blocks get a leading scan dim."""
    defs: Dict[str, Any] = {"embed": embedding_defs(cfg)}
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
    KV, hd = cfg.n_kv_heads, cfg.head_dim

    def one(kind: str) -> Dict[str, Any]:
        if kind == "cross":
            Ne = cfg.cross_attn_kv_len
            return {"k": ParamDef((batch, Ne, KV, hd), ("cache_batch", "cache_len", "kv", None), dt, "zeros"),
                    "v": ParamDef((batch, Ne, KV, hd), ("cache_batch", "cache_len", "kv", None), dt, "zeros")}
        if kind in ATTN_KINDS:
            C = _attn_cache_len(cfg, kind, max_len)
            return {"k": ParamDef((batch, C, KV, hd), ("cache_batch", "cache_len", "kv", None), dt, "zeros"),
                    "v": ParamDef((batch, C, KV, hd), ("cache_batch", "cache_len", "kv", None), dt, "zeros")}
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


def _attention_path(cfg: ModelConfig, window: Optional[int], S: int) -> str:
    """The path of one causal self-attention call over ``S`` tokens.

    The Pallas flash kernel takes it on a TPU that the process drives
    alone (XLA cannot partition a Pallas call over a mesh), without a
    window, at a sequence and head size the kernel supports, and off the
    dry-run cost path (``unroll_scans``: XLA's cost analysis sees no FLOPs
    in a Pallas call).  Everywhere else the jnp paths run as they always
    have."""
    if window is not None and S % window == 0 and S // window >= 2:
        return "local"
    if cfg.attn_impl != "chunked":
        return "naive"
    if (jax.default_backend() == "tpu" and jax.device_count() == 1
            and window is None and not cfg.unroll_scans
            and flash_supports(S, cfg.head_dim)):
        return "kernel"
    return "chunked" if S > cfg.attn_chunk else "naive"


def _self_attention(cfg: ModelConfig, kind: str, p: Dict[str, jax.Array],
                    x: jax.Array, seg: jax.Array, pos: jax.Array,
                    constrain=None) -> jax.Array:
    B, S, D = x.shape
    q = jnp.einsum("bsd,dhk->bshk", x, _pin_w(constrain, "w_q", p["wq"]))
    k = jnp.einsum("bsd,dhk->bshk", x, _pin_w(constrain, "w_kv", p["wk"]))
    v = jnp.einsum("bsd,dhk->bshk", x, _pin_w(constrain, "w_kv", p["wv"]))
    q = attn.rope(q, pos, cfg.rope_theta)
    k = attn.rope(k, pos, cfg.rope_theta)
    window = cfg.window if kind in ("swa", "local") else None
    path = _attention_path(cfg, window, S)
    ATTENTION_PATHS[path] += 1
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
               constrain=None) -> Tuple[jax.Array, jax.Array]:
    """Returns (mlp_out, moe_lb_loss)."""
    if cfg.mlp_kind == "moe":
        out, aux = moe_ffn(p, x, cfg, constrain=constrain)
        return out, aux["lb_loss"]
    if constrain is not None and cfg.mlp_kind in ("swiglu", "geglu", "gelu"):
        p = dict(p)
        for key in ("wi_gate", "wi_up", "wi"):
            if key in p:
                p[key] = constrain("w_in", p[key])
        p["wo"] = constrain("w_out", p["wo"])
    return mlp(p, x, cfg.mlp_kind), jnp.zeros((), jnp.float32)


def apply_block(cfg: ModelConfig, kind: str, p: Dict[str, Any], x: jax.Array,
                *, seg: jax.Array, pos: jax.Array,
                enc: Optional[jax.Array] = None,
                constrain=None) -> Tuple[jax.Array, jax.Array]:
    """Training/prefill-forward block.  Returns (x, moe_aux_loss)."""
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
    if "mlp" in p:
        h = rmsnorm(p["mlp_norm"], x, cfg.norm_eps)
        out, aux = _apply_mlp(cfg, p["mlp"], h, constrain=constrain)
        x = x + out
    return x, aux


def _remat_policy(cfg: ModelConfig):
    cp = jax.checkpoint_policies
    return {
        "nothing": cp.nothing_saveable,
        "dots": cp.dots_saveable,
        "save_layer_inputs": cp.nothing_saveable,
        "dots_no_batch": cp.dots_with_no_batch_dims_saveable,
    }[cfg.remat_policy]


# =================================================================== forward
def forward(cfg: ModelConfig, params: Dict[str, Any], batch: Dict[str, jax.Array],
            constrain=None) -> Tuple[jax.Array, jax.Array]:
    """Full-sequence forward.  batch: tokens/segments/positions (B,S)
    [+ encoder_embeds (B,Ne,D)].  Returns (hidden (B,S,D), moe_aux_loss).

    ``constrain("hidden", x)`` re-pins the residual stream after every block:
    without it, GSPMD sometimes migrates the FSDP params' "data" sharding onto
    the *embed* dim of activation gradients (full-batch all-reduces in the
    backward — verified on gemma-7b)."""
    seg = batch["segments"]
    pos = batch["positions"]
    enc = batch.get("encoder_embeds")
    pin = (lambda h: constrain("hidden", h)) if constrain else (lambda h: h)
    x = pin(embed(params["embed"], batch["tokens"], cfg))

    def body(carry, layer_params):
        h, aux = carry
        for i, kind in enumerate(cfg.pattern):
            h, a = apply_block(cfg, kind, layer_params[i], h,
                               seg=seg, pos=pos, enc=enc, constrain=constrain)
            h = pin(h)
            aux = aux + a
        return (h, aux), None

    aux = jnp.zeros((), jnp.float32)
    if cfg.pattern_repeats > 0:
        body_r = jax.checkpoint(body, policy=_remat_policy(cfg),
                                prevent_cse=False)
        (x, aux), _ = jax.lax.scan(body_r, (x, aux), params["pattern"])
    for i, kind in enumerate(cfg.remainder):
        # per-layer remat for unrolled blocks (same policy as the scan body,
        # so production and dry-run-cost graphs do the same recompute work)
        blk = jax.checkpoint(
            lambda p, h, k=kind: apply_block(cfg, k, p, h, seg=seg, pos=pos,
                                             enc=enc, constrain=constrain),
            policy=_remat_policy(cfg), prevent_cse=False)
        x, a = blk(params["remainder"][i], x)
        x = pin(x)
        aux = aux + a
    x = rmsnorm(params["final_norm"], x, cfg.norm_eps)
    return x, aux


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
    q = jnp.einsum("bsd,dhk->bshk", x, _pin_w(constrain, "w_q", p["wq"]))
    k = jnp.einsum("bsd,dhk->bshk", x, _pin_w(constrain, "w_kv", p["wk"]))
    v = jnp.einsum("bsd,dhk->bshk", x, _pin_w(constrain, "w_kv", p["wv"]))
    per_row = pos.ndim == 1
    posb = (pos[:, None] if per_row
            else jnp.broadcast_to(pos[None, None], (B, 1))).astype(jnp.int32)
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
                 cache: Dict[str, Any], pos: jax.Array, constrain=None
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
        out, _ = _apply_mlp(cfg, p["mlp"], h, constrain=constrain)
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
        k = jnp.einsum("bsd,dhk->bshk", h, _pin_w(constrain, "w_kv", p["attn"]["wk"]))
        k = attn.rope(k, pos, cfg.rope_theta)
        v = jnp.einsum("bsd,dhk->bshk", h, _pin_w(constrain, "w_kv", p["attn"]["wv"]))
        if C >= S:
            pad = jnp.zeros((B, C - S) + k.shape[2:], k.dtype)
            return {"k": jnp.concatenate([k, pad], 1),
                    "v": jnp.concatenate([v, pad], 1)}
        # ring: keep last C keys, placed at slot = pos % C
        kl, vl = k[:, S - C:], v[:, S - C:]
        shift = S % C
        idx = (jnp.arange(C) - shift) % C
        return {"k": kl[:, idx], "v": vl[:, idx]}

    def run_block(kind: str, p: Dict[str, Any], h: jax.Array
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
            out, _ = _apply_mlp(cfg, p["mlp"], hn, constrain=constrain)
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
