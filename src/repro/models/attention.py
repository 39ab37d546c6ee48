"""Causal self-attention in jnp: the whole logits tile, or chunked.

Both run wherever the Pallas flash-attention kernel
(kernels/flash_attention) does not: on the CPU and on a mesh
(``models.model._attention_path``).  ``attention_naive`` is also the
tests' reference.

Conventions:
  q: (B, Sq, H, Dh)   k/v: (B, Sk, KV, Dh)   H = KV * (query heads per kv head)
  (v may have a head size Dv of its own, as latent attention has: the
  output is then (B, Sq, H, Dv))
  q_pos/k_pos: global positions within the packed block (causality),
  q_seg/k_seg: segment ids (packing isolation; 0 = padding).
"""
from __future__ import annotations

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


def _mask(q_pos, k_pos, q_seg, k_seg):
    """(B, Sq, Sk) boolean mask: causal, within the query's segment."""
    m = (q_seg[:, :, None] == k_seg[:, None, :]) & (k_seg[:, None, :] > 0)
    m &= q_pos[:, :, None] >= k_pos[:, None, :]
    return m


def attention_naive(q, k, v, q_pos, k_pos, q_seg, k_seg) -> jax.Array:
    """Grouped scaled dot-product attention over the whole (Sq, Sk) tile."""
    B, Sq, H, Dh = q.shape
    KV = k.shape[2]
    mask = _mask(q_pos, k_pos, q_seg, k_seg)
    qg = q.reshape(B, Sq, KV, H // KV, Dh)
    logits = jnp.einsum("bqkgd,bskd->bkgqs", qg, k).astype(jnp.float32)
    logits *= Dh ** -0.5
    logits = jnp.where(mask[:, None, None, :, :], logits, NEG_INF)
    probs = jax.nn.softmax(logits, axis=-1).astype(v.dtype)
    out = jnp.einsum("bkgqs,bskd->bqkgd", probs, v)
    return out.reshape(B, Sq, H, v.shape[-1])


def attention_chunked(q, k, v, q_pos, k_pos, q_seg, k_seg, *,
                      chunk: int = 1024) -> jax.Array:
    """Flash-style online-softmax over KV chunks (memory O(Sq·chunk) instead of
    O(Sq·Sk)); float32 logits tiles and softmax statistics."""
    B, Sq, H, Dh = q.shape
    Sk, KV = k.shape[1], k.shape[2]
    if Sk % chunk:
        chunk = Sk  # fallback: single chunk
    n_chunks = Sk // chunk
    G = H // KV
    qg = q.reshape(B, Sq, KV, G, Dh)
    scale = Dh ** -0.5
    neg = jnp.asarray(NEG_INF, jnp.float32)

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
                            preferred_element_type=jnp.float32)
        logits = logits * jnp.asarray(scale, jnp.float32)
        msk = _mask(q_pos, kp_i, q_seg, ks_i)
        logits = jnp.where(msk[:, None, None, :, :], logits, neg)
        m_cur = jnp.maximum(m_prev, logits.max(axis=-1))
        alpha = jnp.exp(m_prev - m_cur)
        p = jnp.exp(logits - m_cur[..., None])
        l_cur = l_prev * alpha + p.sum(axis=-1)
        pv = jnp.einsum("bkgqs,bskd->bkgqd", p.astype(v_i.dtype), v_i).astype(jnp.float32)
        acc = acc * alpha[..., None] + pv
        return (acc, m_cur, l_cur), None

    Dv = v.shape[-1]
    acc0 = jnp.zeros((B, KV, G, Sq, Dv), jnp.float32)
    m0 = jnp.full((B, KV, G, Sq), NEG_INF, jnp.float32)
    l0 = jnp.zeros((B, KV, G, Sq), jnp.float32)
    (acc, m, l), _ = jax.lax.scan(body, (acc0, m0, l0),
                                  jnp.arange(n_chunks, dtype=jnp.int32))
    out = acc / jnp.maximum(l[..., None], 1e-30)
    return out.transpose(0, 3, 1, 2, 4).reshape(B, Sq, H, Dv).astype(q.dtype)
