"""Pallas TPU flash attention for the train step: causal, packed rows, GQA.

The training self-attention's score, softmax and value product, forward and
backward, as Pallas kernels under one ``jax.custom_vjp``: logits and
probabilities exist only as VMEM tiles, never in HBM.  Built on JAX's splash
attention (``jax.experimental.pallas.ops.tpu.splash_attention``): a forward
kernel that also writes the log-sum-exp, and dq and dkv backward kernels
that recompute the probabilities from it.  On the device the three kernels
are named ``splash_mha_fwd_segmented_residuals``,
``splash_mha_dq_segmented_no_residuals`` and
``splash_mha_dkv_segmented_no_residuals``.

Residuals.  The forward's output and log-sum-exp, all the backward kernels
need of it, carry the ``checkpoint_name`` ``RESIDUALS``.  A remat policy
that saves that name keeps them through a layer checkpoint, and the
backward then runs no forward kernel of its own; under one that saves
nothing, the backward recomputes them with the forward kernel.

Mask.  Key j is visible to query i when both lie in the same segment and
j <= i, both computed inside the kernel from the segment ids and the
indices; KV blocks wholly above the diagonal are skipped.  The model's mask
(``models.attention._mask``) is ``q_seg == k_seg & k_seg > 0 & q_pos >=
k_pos``.  In a packed row each piece has an id of its own, and positions
count from 0 within it and rise with the index, so within a segment
``q_pos >= k_pos`` is ``i >= j``: the two masks agree at every real query
(segment > 0).  Padding queries (segment 0) attend the padding keys at or
before them, where the model's mask hides every key from them and they
average all keys.  No real query attends a padding key, and padding carries
no loss, so the loss and its gradients are those of the model's mask in
exact arithmetic.  No query attends across segments.

Precision.  q, k and v keep their dtype (bf16 in training).  Logits, the
running max and sum, and every accumulator are float32.  The scale
``head_dim ** -0.5`` is folded into q in float32, then q is rounded once to
k's dtype: a bf16 q at a power-of-two scale (head 64 or 256) keeps its
values exactly, and a caller may pass q in float32 (latent attention, qk
head 192) so that the scale enters before q's one rounding.  v may have a
head size of its own (128 against qk 192).  The forward's value product
takes the probabilities in float32; the backward rounds the probabilities
and their gradient to the input dtype only as operands of its dv, dq and dk
products, where the jnp path's backward rounds them too.

GQA: query head h reads key/value head ``h // (H // KV)`` through the
kernels' index maps; repeated K/V are never materialised.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental.pallas.ops.tpu import splash_attention as splash

# the (q and k, v) head sizes the kernel takes
HEAD_DIMS = ((64, 64), (256, 256), (192, 128))
# q and kv block of every kernel, forward and backward (at most the sequence)
BLOCK = 512
# splash blocks must tile the MXU's lanes
_LANES = 128
# the checkpoint name of the forward's output and log-sum-exp
RESIDUALS = "attention_kernel_residuals"


def block_size(seq_len: int) -> int:
    return min(seq_len, BLOCK)


def supports(seq_len: int, head_dim: int, head_dim_v: int = 0) -> bool:
    """Whether the kernel takes a sequence of ``seq_len`` at ``head_dim``
    (q and k) and ``head_dim_v`` (v; 0: the same)."""
    b = block_size(seq_len)
    return ((head_dim, head_dim_v or head_dim) in HEAD_DIMS
            and b % _LANES == 0 and seq_len % b == 0)


@functools.lru_cache(maxsize=None)
def _kernel(seq_len: int, n_heads: int, b: int, interpret: bool):
    mask = splash.MultiHeadMask([splash.CausalMask((seq_len, seq_len))]
                                * n_heads)
    blocks = splash.BlockSizes(
        block_q=b, block_kv=b, block_kv_compute=b,
        block_q_dkv=b, block_kv_dkv=b, block_kv_dkv_compute=b,
        block_q_dq=b, block_kv_dq=b)
    # the kernel holds its mask tables as arrays: make them concrete, so
    # that the cache holds no tracer of the trace that first built it
    with jax.ensure_compile_time_eval():
        return splash.make_splash_mha(mask, block_sizes=blocks, head_shards=1,
                                      q_seq_shards=1, interpret=interpret,
                                      residual_checkpoint_name=RESIDUALS)


def flash_attention(q: jax.Array, k: jax.Array, v: jax.Array,
                    seg: jax.Array, *, interpret: bool = False) -> jax.Array:
    """q (B, S, H, Dh), k (B, S, KV, Dh), v (B, S, KV, Dv), seg (B, S) ->
    (B, S, H, Dv).

    ``seg`` holds each token's segment id (0 = padding); see the module
    docstring for the mask and what it means for padding queries.  q may
    be float32 (scaled before its one rounding to k's dtype)."""
    B, S, H, Dh = q.shape
    assert supports(S, Dh, v.shape[-1]), (S, Dh, v.shape[-1])
    kernel = _kernel(S, H, block_size(S), interpret)
    q = (q.astype(jnp.float32) * Dh ** -0.5).astype(k.dtype)
    heads_first = lambda x: x.transpose(0, 2, 1, 3)
    seg = seg.astype(jnp.int32)
    out = jax.vmap(lambda q, k, v, s: kernel(
        q, k, v, segment_ids=splash.SegmentIds(s, s)))(
            heads_first(q), heads_first(k), heads_first(v), seg)
    return heads_first(out)
