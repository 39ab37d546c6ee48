"""Pallas TPU kernel: blockwise flash attention (online softmax).

The serving-path hot spot.  Unlike the pure-jnp chunked attention in
models/attention.py (which materializes (Sq, bk) logits tiles in HBM when Sq
is large), this kernel tiles BOTH the query and key dimensions so the live
working set is (bq, d) + (bk, d) + (bq, bk) in VMEM — the standard
flash-attention memory shape, adapted to the TPU hierarchy (HBM -> VMEM ->
VREG, MXU-aligned 128-multiple tiles).

Layout: grid = (B*H, Sq//bq); the kv loop is a fori_loop inside the kernel so
only causally-needed kv blocks are visited.  GQA is handled by the wrapper
(kv heads repeated logically via index maps, never materialized).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

NEG_INF = -1.0e30


def _kernel(q_ref, k_ref, v_ref, o_ref, *, bq: int, bk: int, scale: float,
            causal: bool):
    qi = pl.program_id(1)
    Sk = k_ref.shape[1]
    q = q_ref[0].astype(jnp.float32) * scale            # (bq, d)

    def body(j, carry):
        acc, m_prev, l_prev = carry
        k = k_ref[0, pl.ds(j * bk, bk), :].astype(jnp.float32)
        v = v_ref[0, pl.ds(j * bk, bk), :].astype(jnp.float32)
        s = q @ k.T                                      # (bq, bk) on the MXU
        if causal:
            qpos = qi * bq + jax.lax.broadcasted_iota(jnp.int32, (bq, bk), 0)
            kpos = j * bk + jax.lax.broadcasted_iota(jnp.int32, (bq, bk), 1)
            s = jnp.where(qpos >= kpos, s, NEG_INF)
        m_cur = jnp.maximum(m_prev, s.max(axis=-1))
        alpha = jnp.exp(m_prev - m_cur)
        p = jnp.exp(s - m_cur[:, None])
        l_cur = l_prev * alpha + p.sum(axis=-1)
        acc = acc * alpha[:, None] + p @ v
        return acc, m_cur, l_cur

    d = q_ref.shape[-1]
    acc0 = jnp.zeros((bq, d), jnp.float32)
    m0 = jnp.full((bq,), NEG_INF, jnp.float32)
    l0 = jnp.zeros((bq,), jnp.float32)
    # causal: only visit kv blocks up to (and including) this q block
    n_blocks = (qi + 1) * bq // bk if causal else Sk // bk
    acc, m, l = jax.lax.fori_loop(0, n_blocks, body, (acc0, m0, l0))
    o_ref[0, ...] = (acc / jnp.maximum(l, 1e-30)[:, None]).astype(o_ref.dtype)


def flash_attention(q: jax.Array, k: jax.Array, v: jax.Array, *,
                    causal: bool = True, bq: int = 512, bk: int = 512,
                    interpret: bool = False) -> jax.Array:
    """q (B, Sq, H, d), k/v (B, Sk, KV, d) -> (B, Sq, H, d).

    GQA: q head h reads kv head h // (H // KV) via the kv index map."""
    B, Sq, H, d = q.shape
    Sk, KV = k.shape[1], k.shape[2]
    g = H // KV
    bq = min(bq, Sq)
    bk = min(bk, Sk)
    assert Sq % bq == 0 and Sk % bk == 0, (Sq, bq, Sk, bk)
    scale = d ** -0.5

    qf = q.transpose(0, 2, 1, 3).reshape(B * H, Sq, d)
    kf = k.transpose(0, 2, 1, 3).reshape(B * KV, Sk, d)
    vf = v.transpose(0, 2, 1, 3).reshape(B * KV, Sk, d)

    def kv_map(bh, qi):
        return (bh // g, 0, 0)   # collapse q-head to its kv head

    out = pl.pallas_call(
        functools.partial(_kernel, bq=bq, bk=bk, scale=scale, causal=causal),
        grid=(B * H, Sq // bq),
        in_specs=[
            pl.BlockSpec((1, bq, d), lambda bh, qi: (bh, qi, 0)),
            pl.BlockSpec((1, Sk, d), kv_map),
            pl.BlockSpec((1, Sk, d), kv_map),
        ],
        out_specs=pl.BlockSpec((1, bq, d), lambda bh, qi: (bh, qi, 0)),
        out_shape=jax.ShapeDtypeStruct((B * H, Sq, d), q.dtype),
        interpret=interpret,
    )(qf, kf, vf)
    return out.reshape(B, H, Sq, d).transpose(0, 2, 1, 3)
