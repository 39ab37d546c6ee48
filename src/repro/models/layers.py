"""Shared layers: RMSNorm, the dense SwiGLU MLP, embeddings.

Pure-functional: every layer is ``fn(params_subtree, x, cfg) -> y`` with
parameter *definitions* provided by matching ``*_defs`` functions so model.py
can build the full ParamDef tree (shapes + logical sharding axes) in one place.

Logical axis names (mapped to mesh axes by the sharding rules):
  "embed"   — d_model dim          (FSDP: sharded over the data axis)
  "ffn"     — feed-forward hidden  (TP: sharded over the model axis)
  "heads"   — attention head dim   (TP)
  "kv"      — kv head dim          (TP)
  "vocab"   — vocabulary dim       (TP)
  "experts" — MoE expert dim       (EP)
  "layers"  — scanned layer stack  (never sharded)
"""
from __future__ import annotations

from typing import Dict

import jax
import jax.numpy as jnp

from .config import ModelConfig
from .params import ParamDef


# ------------------------------------------------------------------ norms
def rmsnorm_defs(d: int) -> Dict[str, ParamDef]:
    return {"scale": ParamDef((d,), ("embed",), jnp.float32, "zeros")}


def rmsnorm(p: Dict[str, jax.Array], x: jax.Array, eps: float) -> jax.Array:
    """RMSNorm with (1 + scale) parameterization (gemma/llama convention)."""
    dt = x.dtype
    xf = x.astype(jnp.float32)
    var = jnp.mean(xf * xf, axis=-1, keepdims=True)
    xf = xf * jax.lax.rsqrt(var + eps)
    return (xf * (1.0 + p["scale"].astype(jnp.float32))).astype(dt)


# ------------------------------------------------------------------- MLPs
def mlp_defs(cfg: ModelConfig) -> Dict[str, ParamDef]:
    """SwiGLU of width d_ff."""
    D, F = cfg.d_model, cfg.d_ff
    dt = jnp.dtype(cfg.param_dtype)
    return {
        "wi_gate": ParamDef((D, F), ("embed", "ffn"), dt),
        "wi_up": ParamDef((D, F), ("embed", "ffn"), dt),
        "wo": ParamDef((F, D), ("ffn", "embed"), dt, "scaled"),
    }


def mlp(p: Dict[str, jax.Array], x: jax.Array) -> jax.Array:
    g = jax.nn.silu(x @ p["wi_gate"])
    return (g * (x @ p["wi_up"])) @ p["wo"]


# ------------------------------------------------------------- embeddings
def embedding_defs(cfg: ModelConfig) -> Dict[str, ParamDef]:
    dt = jnp.dtype(cfg.param_dtype)
    out = {"embedding": ParamDef((cfg.vocab_size, cfg.d_model), ("vocab", "embed"), dt)}
    if not cfg.tied_embeddings:
        out["unembed"] = ParamDef((cfg.d_model, cfg.vocab_size), ("embed", "vocab"), dt)
    return out


def embed(p: Dict[str, jax.Array], tokens: jax.Array, cfg: ModelConfig) -> jax.Array:
    return jnp.take(p["embedding"], tokens, axis=0).astype(cfg.activation_dtype)


def unembed(p: Dict[str, jax.Array], x: jax.Array, cfg: ModelConfig) -> jax.Array:
    """Final projection to logits (fp32)."""
    w = p["embedding"].T if cfg.tied_embeddings else p["unembed"]
    return (x @ w.astype(x.dtype)).astype(jnp.float32)
