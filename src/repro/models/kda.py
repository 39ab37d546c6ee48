"""Kimi Delta Attention as a layer's mixer (Kimi-Linear's
``KimiDeltaAttention``; the Kimi Linear report, arXiv:2510.26692).

Per layer, on the normed hidden states x (B, S, D), with H heads of
d = ``head_dim`` and conv width w:

    q, k, v = SiLU(ShortConv(x W_q)), SiLU(ShortConv(x W_k)), SiLU(ShortConv(x W_v))
    beta    = sigmoid(x W_beta)                               (B, S, H), float32
    log a   = -exp(A_log) * softplus(x W_f_a W_f_b + dt_bias)  (B, S, H, d), float32
    o       = KDA(q, k, v, log a, beta)                        (``kernels/kda.py``)
    out     = (RMSNorm_d(o) * (1 + g_norm) * sigmoid(x W_g_a W_g_b + g_bias)) W_o

The projections are bf16; the decays, the chunked KDA and the gated norm
run in float32.

ShortConv is causal and depthwise over each of the H d channels, weight
w_j (j = 0 .. w-1) applied to the token j places back, and reads only
tokens of the token's own segment.  The KDA state starts at zero and
resets at every segment start, inside the kernel; q and k are
L2-normalised there and q scaled by d^-1/2.

``KDA_PATHS`` counts the kernel's calls at trace time, by whether the
Pallas kernels run compiled (``kernel``) or in the interpreter
(``interpret``, off the TPU).
"""
from __future__ import annotations

from collections import Counter
from functools import partial
from typing import Any, Dict, Tuple

import jax
import jax.numpy as jnp
from jax.ad_checkpoint import checkpoint_name

from ..kernels import ops as kernel_ops
from ..kernels.kda import segment_starts
from .config import ModelConfig
from .params import ParamDef

#: KDA mixer calls traced, by how the Pallas kernels run ("kernel",
#: "interpret"); counted at trace time, so a layer counts once per trace
KDA_PATHS: Counter = Counter()
#: ``checkpoint_name`` of a KDA mixer's output (``kda_mixer``)
OUTPUT = "kda_mixer_output"


def kda_defs(cfg: ModelConfig) -> Dict[str, Any]:
    a, D = cfg.kda, cfg.d_model
    H, d, r, w = a.n_heads, a.head_dim, a.gate_rank, a.conv_size
    dt = jnp.dtype(cfg.param_dtype)
    f32 = jnp.float32
    proj = lambda: ParamDef((D, H, d), ("embed", "heads", None), dt)
    conv = lambda: ParamDef((w, H, d), (None, "heads", None), dt)
    return {
        "wq": proj(), "wk": proj(), "wv": proj(),
        "conv_q": conv(), "conv_k": conv(), "conv_v": conv(),
        "w_beta": ParamDef((D, H), ("embed", "heads"), dt),
        "wf_a": ParamDef((D, r), ("embed", None), dt),
        "wf_b": ParamDef((r, H, d), (None, "heads", None), dt),
        "A_log": ParamDef((H,), ("heads",), f32, "zeros"),
        "dt_bias": ParamDef((H, d), ("heads", None), f32, "zeros"),
        "wg_a": ParamDef((D, r), ("embed", None), dt),
        "wg_b": ParamDef((r, H, d), (None, "heads", None), dt),
        "g_bias": ParamDef((H, d), ("heads", None), f32, "zeros"),
        "o_norm": {"scale": ParamDef((d,), (None,), f32, "zeros")},
        "wo": ParamDef((H, d, D), ("heads", None, "embed"), dt, "scaled"),
    }


def short_conv(x: jax.Array, w: jax.Array, sid: jax.Array) -> jax.Array:
    """Causal depthwise convolution over the sequence, within segments:
    x (B, H, S, d), w (width, H, d), sid (B, S) contiguous segment numbers
    -> (B, H, S, d); w[j] weighs the token j places back."""
    S = x.shape[2]
    f32 = jnp.float32
    tap = lambda j: w[j].astype(f32)[None, :, None, :]
    out = x.astype(f32) * tap(0)
    for j in range(1, w.shape[0]):
        back = jnp.pad(x, ((0, 0), (0, 0), (j, 0), (0, 0)))[:, :, :S]
        same = jnp.pad(sid, ((0, 0), (j, 0)), constant_values=-1)[:, :S] == sid
        out = out + jnp.where(same[:, None, :, None], back.astype(f32) * tap(j), 0.0)
    return out.astype(x.dtype)


def _path() -> str:
    return "kernel" if jax.default_backend() == "tpu" else "interpret"


def _mixer(cfg: ModelConfig, p: Dict[str, Any], x: jax.Array, seg: jax.Array
           ) -> Tuple[jax.Array, jax.Array]:
    """The mixer's output (B, S, D) and the resets the kernel made."""
    a = cfg.kda
    sid = jnp.cumsum(segment_starts(seg).astype(jnp.int32), axis=1)

    def branch(w, conv):
        h = jnp.einsum("bsd,dhk->bhsk", x, w)
        return jax.nn.silu(short_conv(h, conv, sid))

    q, k, v = (branch(p["wq"], p["conv_q"]), branch(p["wk"], p["conv_k"]),
               branch(p["wv"], p["conv_v"]))
    beta = jax.nn.sigmoid(jnp.einsum("bsd,dh->bhs", x, p["w_beta"],
                                     preferred_element_type=jnp.float32))
    f = jnp.einsum("bsr,rhk->bhsk", x @ p["wf_a"], p["wf_b"])
    log_a = -jnp.exp(p["A_log"])[:, None, None] * jax.nn.softplus(
        f.astype(jnp.float32) + p["dt_bias"][:, None])
    KDA_PATHS[_path()] += 1
    o, resets = kernel_ops.kda(q, k, v, log_a, beta, seg, scale=a.head_dim ** -0.5)
    gate = jnp.einsum("bsr,rhk->bhsk", x @ p["wg_a"], p["wg_b"])
    o = o * jax.lax.rsqrt(jnp.mean(o * o, axis=-1, keepdims=True) + cfg.norm_eps)
    o = o * (1.0 + p["o_norm"]["scale"]) * jax.nn.sigmoid(
        gate.astype(jnp.float32) + p["g_bias"][:, None])
    out = jnp.einsum("bhsk,hkd->bsd", o.astype(x.dtype), p["wo"])
    return out, resets


def kda_mixer(cfg: ModelConfig, p: Dict[str, Any], x: jax.Array,
              seg: jax.Array) -> Tuple[jax.Array, Dict[str, jax.Array]]:
    """One KDA mixer: x (B, S, D) normed -> (B, S, D), and the layer's
    stats: ``kda_resets``, the state resets the kernel's segment masks
    made.  The mixer is a checkpoint of its own whose output carries the
    name ``OUTPUT``: under a layer checkpoint that keeps it, the layer's
    backward runs the expert layer's backward without the mixer's
    residuals live, and rebuilds them (one more forward of the mixer) for
    the mixer's own."""
    out, resets = jax.checkpoint(partial(_mixer, cfg))(p, x, seg)
    return checkpoint_name(out, OUTPUT), {"kda_resets": resets.astype(jnp.float32)}
