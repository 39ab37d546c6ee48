"""Optimizers in pure JAX: AdamW and Adafactor.

State trees mirror the parameter tree, so pjit shards optimizer state with
the same PartitionSpecs as the parameters (via ``opt_state_specs``).

Adafactor keeps factored second moments: row and column factors,
~(rows+cols)/(rows·cols) of AdamW's float32 state.  No registered config
selects it.
"""
from __future__ import annotations

from dataclasses import dataclass
from typing import Any, Callable, Dict, Optional, Tuple

import jax
import jax.numpy as jnp

from ..models.params import ParamDef, logical_to_spec, tree_map_defs


@dataclass(frozen=True)
class OptConfig:
    name: str = "adamw"
    lr: float = 3e-4
    b1: float = 0.9
    b2: float = 0.95
    eps: float = 1e-8
    weight_decay: float = 0.1
    grad_clip: float = 1.0
    # adafactor
    decay_rate: float = 0.8
    min_dim_factored: int = 128
    warmup_steps: int = 100


def _lr(c: OptConfig, step: jax.Array) -> jax.Array:
    warm = jnp.minimum(1.0, (step + 1) / max(1, c.warmup_steps))
    return c.lr * warm


def global_norm(tree: Any) -> jax.Array:
    leaves = jax.tree.leaves(tree)
    return jnp.sqrt(sum(jnp.sum(jnp.square(l.astype(jnp.float32))) for l in leaves))


def clip_by_global_norm(tree: Any, max_norm: float) -> Tuple[Any, jax.Array]:
    norm = global_norm(tree)
    scale = jnp.minimum(1.0, max_norm / jnp.maximum(norm, 1e-9))
    return jax.tree.map(lambda g: (g.astype(jnp.float32) * scale), tree), norm


# ------------------------------------------------------------------- AdamW
def adamw_init(params: Any) -> Dict[str, Any]:
    zeros = lambda p: jnp.zeros(p.shape, jnp.float32)
    return {"mu": jax.tree.map(zeros, params),
            "nu": jax.tree.map(zeros, params),
            "step": jnp.zeros((), jnp.int32)}


def adamw_update(c: OptConfig, grads: Any, state: Dict[str, Any], params: Any
                 ) -> Tuple[Any, Dict[str, Any], Dict[str, jax.Array]]:
    grads, gnorm = clip_by_global_norm(grads, c.grad_clip)
    step = state["step"] + 1
    lr = _lr(c, step)
    t = step.astype(jnp.float32)
    bc1 = 1.0 - c.b1 ** t
    bc2 = 1.0 - c.b2 ** t

    def upd(g, mu, nu, p):
        mu = c.b1 * mu + (1 - c.b1) * g
        nu = c.b2 * nu + (1 - c.b2) * g * g
        u = (mu / bc1) / (jnp.sqrt(nu / bc2) + c.eps)
        u = u + c.weight_decay * p.astype(jnp.float32)
        return (p.astype(jnp.float32) - lr * u).astype(p.dtype), mu, nu

    out = jax.tree.map(upd, grads, state["mu"], state["nu"], params)
    new_p = jax.tree.map(lambda o: o[0], out, is_leaf=lambda x: isinstance(x, tuple))
    new_mu = jax.tree.map(lambda o: o[1], out, is_leaf=lambda x: isinstance(x, tuple))
    new_nu = jax.tree.map(lambda o: o[2], out, is_leaf=lambda x: isinstance(x, tuple))
    return new_p, {"mu": new_mu, "nu": new_nu, "step": step}, {"grad_norm": gnorm, "lr": lr}


# --------------------------------------------------------------- Adafactor
def _factored(shape: Tuple[int, ...], min_dim: int) -> bool:
    return len(shape) >= 2 and shape[-1] >= min_dim and shape[-2] >= min_dim


def adafactor_init(params: Any, min_dim: int = 128) -> Dict[str, Any]:
    def per_leaf(p):
        if _factored(p.shape, min_dim):
            return {"vr": jnp.zeros(p.shape[:-1], jnp.float32),
                    "vc": jnp.zeros(p.shape[:-2] + p.shape[-1:], jnp.float32)}
        return {"v": jnp.zeros(p.shape, jnp.float32)}

    return {"v": jax.tree.map(per_leaf, params),
            "step": jnp.zeros((), jnp.int32)}


def adafactor_update(c: OptConfig, grads: Any, state: Dict[str, Any], params: Any
                     ) -> Tuple[Any, Dict[str, Any], Dict[str, jax.Array]]:
    grads, gnorm = clip_by_global_norm(grads, c.grad_clip)
    step = state["step"] + 1
    lr = _lr(c, step)
    t = step.astype(jnp.float32)
    beta2 = 1.0 - t ** (-c.decay_rate)

    def upd(g, v, p):
        g2 = g * g + 1e-30
        if "vr" in v:
            vr = beta2 * v["vr"] + (1 - beta2) * g2.mean(axis=-1)
            vc = beta2 * v["vc"] + (1 - beta2) * g2.mean(axis=-2)
            denom = vr.mean(axis=-1, keepdims=True)
            pre = (vr / jnp.maximum(denom, 1e-30))[..., None] * vc[..., None, :]
            u = g * jax.lax.rsqrt(jnp.maximum(pre, 1e-30))
            nv = {"vr": vr, "vc": vc}
        else:
            vv = beta2 * v["v"] + (1 - beta2) * g2
            u = g * jax.lax.rsqrt(jnp.maximum(vv, 1e-30))
            nv = {"v": vv}
        # update clipping (Adafactor's RMS-1 rule)
        rms = jnp.sqrt(jnp.mean(u * u) + 1e-30)
        u = u / jnp.maximum(1.0, rms)
        u = u + c.weight_decay * p.astype(jnp.float32)
        return (p.astype(jnp.float32) - lr * u).astype(p.dtype), nv

    is_state = lambda x: isinstance(x, dict) and ("v" in x or "vr" in x)
    out = jax.tree.map(upd, grads, state["v"], params, is_leaf=lambda x: False or is_state(x))
    # out leaves are (new_p, new_v) tuples at param positions
    is_pair = lambda x: isinstance(x, tuple) and len(x) == 2
    new_p = jax.tree.map(lambda o: o[0], out, is_leaf=is_pair)
    new_v = jax.tree.map(lambda o: o[1], out, is_leaf=is_pair)
    return new_p, {"v": new_v, "step": step}, {"grad_norm": gnorm, "lr": lr}


# --------------------------------------------------------------- factories
def make_optimizer(name: str, **kw: Any):
    """Returns (init_fn, update_fn, opt_cfg)."""
    c = OptConfig(name=name, **kw)
    if name == "adamw":
        return adamw_init, lambda g, s, p: adamw_update(c, g, s, p), c
    if name == "adafactor":
        return (lambda p: adafactor_init(p, c.min_dim_factored),
                lambda g, s, p: adafactor_update(c, g, s, p), c)
    raise ValueError(f"unknown optimizer {name!r}")


def opt_state_defs(name: str, param_defs: Any, min_dim: int = 128) -> Any:
    """ParamDef tree for the optimizer state (its shardings and checkpoint
    restore)."""
    if name == "adamw":
        f32 = lambda d: ParamDef(d.shape, d.axes, jnp.float32, "zeros")
        return {"mu": tree_map_defs(f32, param_defs),
                "nu": tree_map_defs(f32, param_defs),
                "step": ParamDef((), (), jnp.int32, "zeros")}
    if name == "adafactor":
        def per_def(d: ParamDef):
            if _factored(d.shape, min_dim):
                return {"vr": ParamDef(d.shape[:-1], d.axes[:-1], jnp.float32, "zeros"),
                        "vc": ParamDef(d.shape[:-2] + d.shape[-1:],
                                       d.axes[:-2] + d.axes[-1:], jnp.float32, "zeros")}
            return {"v": ParamDef(d.shape, d.axes, jnp.float32, "zeros")}
        return {"v": tree_map_defs(per_def, param_defs),
                "step": ParamDef((), (), jnp.int32, "zeros")}
    raise ValueError(name)
