"""Attention mixers: GQA full/chunked/windowed, cross-attention, decode.

All functions are pure jnp: XLA's cost analysis sees their FLOPs, and they
run wherever the Pallas flash-attention kernel (kernels/flash_attention)
does not.  On a TPU the causal self-attention without a window takes that
kernel instead of ``attention_chunked`` (``models.model._attention_path``).

Conventions:
  q: (B, Sq, H, Dh)   k/v: (B, Sk, KV, Dh)   H = KV * q_per_kv
  (self-attention and cross-attention also take v of its own head size Dv,
  as latent attention has: the output is then (B, Sq, H, Dv))
  q_pos/k_pos: global positions within the packed block (causality),
  q_seg/k_seg: segment ids (packing isolation; 0 = padding).
"""
from __future__ import annotations

from functools import partial
from typing import Optional, Tuple

import jax
import jax.numpy as jnp

NEG_INF = -2.0e38


def rope(x: jax.Array, positions: jax.Array, theta: float) -> jax.Array:
    """Rotary embeddings; x: (B, S, H, D), positions: (B, S)."""
    d = x.shape[-1]
    half = d // 2
    freq = theta ** (-jnp.arange(0, half, dtype=jnp.float32) / half)
    angles = positions[..., None].astype(jnp.float32) * freq  # (B, S, half)
    cos = jnp.cos(angles)[:, :, None, :]
    sin = jnp.sin(angles)[:, :, None, :]
    x1, x2 = x[..., :half], x[..., half:]
    out = jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], axis=-1)
    return out.astype(x.dtype)


def _mask(q_pos, k_pos, q_seg, k_seg, window: Optional[int], causal: bool):
    """(B, Sq, Sk) boolean mask."""
    m = (q_seg[:, :, None] == k_seg[:, None, :]) & (k_seg[:, None, :] > 0)
    if causal:
        m &= q_pos[:, :, None] >= k_pos[:, None, :]
    if window is not None:
        m &= q_pos[:, :, None] - k_pos[:, None, :] < window
    return m


def _sdpa(q, k, v, mask, softcap: float = 0.0) -> jax.Array:
    """Grouped scaled dot-product attention; mask: (B, Sq, Sk)."""
    B, Sq, H, Dh = q.shape
    KV = k.shape[2]
    qg = q.reshape(B, Sq, KV, H // KV, Dh)
    logits = jnp.einsum("bqkgd,bskd->bkgqs", qg, k).astype(jnp.float32)
    logits *= Dh ** -0.5
    if softcap > 0.0:
        logits = jnp.tanh(logits / softcap) * softcap
    logits = jnp.where(mask[:, None, None, :, :], logits, NEG_INF)
    probs = jax.nn.softmax(logits, axis=-1).astype(v.dtype)
    out = jnp.einsum("bkgqs,bskd->bqkgd", probs, v)
    return out.reshape(B, Sq, H, v.shape[-1])


def attention_naive(q, k, v, q_pos, k_pos, q_seg, k_seg, *,
                    window: Optional[int] = None, causal: bool = True,
                    softcap: float = 0.0) -> jax.Array:
    return _sdpa(q, k, v, _mask(q_pos, k_pos, q_seg, k_seg, window, causal), softcap)


def attention_chunked(q, k, v, q_pos, k_pos, q_seg, k_seg, *,
                      chunk: int = 1024, window: Optional[int] = None,
                      causal: bool = True, softcap: float = 0.0,
                      unroll: bool = False,
                      logits_dtype=jnp.float32) -> jax.Array:
    """Flash-style online-softmax over KV chunks (memory O(Sq·chunk) instead of
    O(Sq·Sk)); pure jnp so HLO cost analysis sees the real FLOPs.

    ``logits_dtype`` controls the materialized tile dtype: bf16 halves the
    dominant HBM traffic on serve paths (softmax stats stay fp32)."""
    B, Sq, H, Dh = q.shape
    Sk, KV = k.shape[1], k.shape[2]
    if Sk % chunk:
        chunk = Sk  # fallback: single chunk
    n_chunks = Sk // chunk
    G = H // KV
    qg = q.reshape(B, Sq, KV, G, Dh)
    scale = Dh ** -0.5
    neg = jnp.asarray(-3e4 if logits_dtype == jnp.bfloat16 else NEG_INF,
                      logits_dtype)

    # index-scan + dynamic_slice instead of pre-transposed scan xs: the
    # (nc, B, chunk, ...) transpose materializes full-S copies of K/V every
    # layer (measured 0.9 TB per 5 llama layers — the dominant memory term);
    # slicing in the body reads only the live chunk.
    def body(carry, idx):
        acc, m_prev, l_prev = carry
        k_i = jax.lax.dynamic_slice_in_dim(k, idx * chunk, chunk, axis=1)
        v_i = jax.lax.dynamic_slice_in_dim(v, idx * chunk, chunk, axis=1)
        kp_i = jax.lax.dynamic_slice_in_dim(k_pos, idx * chunk, chunk, axis=1)
        ks_i = jax.lax.dynamic_slice_in_dim(k_seg, idx * chunk, chunk, axis=1)
        logits = jnp.einsum("bqkgd,bskd->bkgqs", qg, k_i,
                            preferred_element_type=logits_dtype)
        logits = logits * jnp.asarray(scale, logits_dtype)
        if softcap > 0.0:
            logits = jnp.tanh(logits / softcap) * softcap
        msk = _mask(q_pos, kp_i, q_seg, ks_i, window, causal)
        logits = jnp.where(msk[:, None, None, :, :], logits, neg)
        m_cur = jnp.maximum(m_prev, logits.max(axis=-1).astype(jnp.float32))
        alpha = jnp.exp(m_prev - m_cur)
        p = jnp.exp(logits.astype(jnp.float32) - m_cur[..., None]).astype(logits_dtype)
        l_cur = l_prev * alpha + p.astype(jnp.float32).sum(axis=-1)
        pv = jnp.einsum("bkgqs,bskd->bkgqd", p.astype(v_i.dtype), v_i).astype(jnp.float32)
        acc = acc * alpha[..., None] + pv
        return (acc, m_cur, l_cur), None

    Dv = v.shape[-1]
    acc0 = jnp.zeros((B, KV, G, Sq, Dv), jnp.float32)
    m0 = jnp.full((B, KV, G, Sq), NEG_INF, jnp.float32)
    l0 = jnp.zeros((B, KV, G, Sq), jnp.float32)
    (acc, m, l), _ = jax.lax.scan(body, (acc0, m0, l0),
                                  jnp.arange(n_chunks, dtype=jnp.int32),
                                  unroll=n_chunks if unroll else 1)
    out = acc / jnp.maximum(l[..., None], 1e-30)
    return out.transpose(0, 3, 1, 2, 4).reshape(B, Sq, H, Dv).astype(q.dtype)


def attention_local(q, k, v, q_pos, k_pos, q_seg, k_seg, *, window: int,
                    softcap: float = 0.0) -> jax.Array:
    """Exact sliding-window attention in O(S·window): queries in block i attend
    keys in blocks i-1 and i only (block size = window).  Sub-quadratic — the
    long-context path for SWA/local archs (DESIGN.md §4)."""
    B, Sq, H, Dh = q.shape
    Sk, KV = k.shape[1], k.shape[2]
    if Sq != Sk or Sq % window or Sq // window < 2:
        return attention_chunked(q, k, v, q_pos, k_pos, q_seg, k_seg,
                                 chunk=min(Sq, 4096), window=window, softcap=softcap)
    nb = Sq // window
    G = H // KV

    def blocked(x, d):
        return x.reshape(B, nb, window, *x.shape[2:]) if d else x.reshape(B, nb, window)

    qb = blocked(q, True).reshape(B, nb, window, KV, G, Dh)
    kb, vb = blocked(k, True), blocked(v, True)
    kprev = jnp.concatenate([jnp.zeros_like(kb[:, :1]), kb[:, :-1]], axis=1)
    vprev = jnp.concatenate([jnp.zeros_like(vb[:, :1]), vb[:, :-1]], axis=1)
    k2 = jnp.concatenate([kprev, kb], axis=2)           # (B, nb, 2w, KV, Dh)
    v2 = jnp.concatenate([vprev, vb], axis=2)
    qp, ks, qs = blocked(q_pos, False), blocked(k_seg, False), blocked(q_seg, False)
    kp = blocked(k_pos, False)
    kp2 = jnp.concatenate([jnp.concatenate(
        [jnp.full_like(kp[:, :1], -10**9), kp[:, :-1]], axis=1), kp], axis=2)
    ks2 = jnp.concatenate([jnp.zeros_like(ks[:, :1]).at[:].set(0).astype(ks.dtype)
                           if False else jnp.concatenate(
        [jnp.zeros_like(ks[:, :1]), ks[:, :-1]], axis=1), ks], axis=2)

    logits = jnp.einsum("bnqkgd,bnskd->bnkgqs", qb, k2).astype(jnp.float32)
    logits *= Dh ** -0.5
    if softcap > 0.0:
        logits = jnp.tanh(logits / softcap) * softcap
    msk = ((qs[:, :, :, None] == ks2[:, :, None, :]) & (ks2[:, :, None, :] > 0)
           & (qp[:, :, :, None] >= kp2[:, :, None, :])
           & (qp[:, :, :, None] - kp2[:, :, None, :] < window))
    logits = jnp.where(msk[:, :, None, None, :, :], logits, NEG_INF)
    probs = jax.nn.softmax(logits, axis=-1)
    # fully-masked rows (first tokens of padding segments) -> zeros
    probs = jnp.where(msk[:, :, None, None, :, :], probs, 0.0).astype(v.dtype)
    out = jnp.einsum("bnkgqs,bnskd->bnqkgd", probs, v2)
    return out.reshape(B, Sq, H, Dh)


def attention_decode(q, k_cache, v_cache, cache_len, *, window: Optional[int] = None,
                     softcap: float = 0.0) -> jax.Array:
    """One-token decode: q (B, 1, H, Dh) against cache (B, Smax, KV, Dh).
    ``cache_len`` (B,) gives the number of valid cache entries per row."""
    B, _, H, Dh = q.shape
    Smax, KV = k_cache.shape[1], k_cache.shape[2]
    G = H // KV
    qg = q.reshape(B, KV, G, Dh)
    logits = jnp.einsum("bkgd,bskd->bkgs", qg, k_cache).astype(jnp.float32)
    logits *= Dh ** -0.5
    if softcap > 0.0:
        logits = jnp.tanh(logits / softcap) * softcap
    pos = jnp.arange(Smax)[None, :]
    valid = pos < cache_len[:, None]
    if window is not None:
        valid &= pos >= (cache_len[:, None] - window)
    logits = jnp.where(valid[:, None, None, :], logits, NEG_INF)
    probs = jax.nn.softmax(logits, axis=-1).astype(v_cache.dtype)
    out = jnp.einsum("bkgs,bskd->bkgd", probs, v_cache)
    return out.reshape(B, 1, H, v_cache.shape[-1])


def attention_cross(q, k, v, q_seg, *, softcap: float = 0.0) -> jax.Array:
    """Cross attention to encoder embeddings: no causal mask; padding queries
    masked by segment 0."""
    B, Sq = q.shape[:2]
    Sk = k.shape[1]
    mask = jnp.broadcast_to((q_seg > 0)[:, :, None], (B, Sq, Sk))
    return _sdpa(q, k, v, mask, softcap)
