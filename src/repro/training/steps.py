"""Train step builder: loss, grads, optimizer update, metrics.

``make_train_step(cfg)`` returns a pure function
    (params, opt_state, batch, rng) -> (params, opt_state, metrics)
suitable for ``jax.jit`` with in/out shardings from the launch layer.

The cross-entropy is computed in sequence chunks (``loss_chunk``) so the
(B, S, V) logits tensor never materializes at once — with V up to 256 k this
is the difference between fitting and OOM on a 16 GB chip.  FLOPs are
unchanged (same matmuls, scanned).
"""
from __future__ import annotations

from typing import Any, Callable, Dict, Optional, Tuple

import jax
import jax.numpy as jnp

from ..models.config import ModelConfig
from ..models.layers import unembed
from ..models.model import forward
from .optim import make_optimizer


def _chunked_xent(cfg: ModelConfig, params: Dict[str, Any], hidden: jax.Array,
                  labels: jax.Array, valid: jax.Array, chunk: int,
                  constrain=None) -> Tuple[jax.Array, jax.Array]:
    """Sum NLL + count over valid positions, scanning over sequence chunks."""
    B, S, D = hidden.shape
    if S % chunk:
        chunk = S
    n = S // chunk
    hc = hidden.reshape(B, n, chunk, D).transpose(1, 0, 2, 3)
    lc = labels.reshape(B, n, chunk).transpose(1, 0, 2)
    vc = valid.reshape(B, n, chunk).transpose(1, 0, 2)

    def body(carry, xs):
        h, l, v = xs
        logits = unembed(params["embed"], h, cfg)              # (B, chunk, V) fp32
        if constrain is not None:
            # pin (batch -> data, vocab -> model): without this, a tied
            # embedding's FSDP-sharded contracting dim makes GSPMD replicate
            # the batch through the loss/backward (verified on gemma-7b)
            logits = constrain("logits", logits)
        lse = jax.nn.logsumexp(logits, axis=-1)
        picked = jnp.take_along_axis(logits, l[..., None], axis=-1)[..., 0]
        nll = jnp.where(v, lse - picked, 0.0)
        nloss, ncount = carry
        return (nloss + nll.sum(), ncount + v.sum()), None

    if cfg.remat_loss:
        body = jax.checkpoint(body)
    (loss_sum, count), _ = jax.lax.scan(
        body, (jnp.zeros((), jnp.float32), jnp.zeros((), jnp.float32)),
        (hc, lc, vc.astype(jnp.float32)))
    return loss_sum, count


def _router_stats(router: Dict[str, Any]) -> Dict[str, Any]:
    """Per-step routing counters over the MoE layers that report them
    (dropless dispatch), and each MoE layer's expert loads in the params'
    layout (``load_tree``: None at layers without a router)."""
    flat = [st for section in router.values() for st in section if st]
    out: Dict[str, Any] = {}
    if any("computed" in st for st in flat):
        out["moe_computed"] = sum(jnp.sum(st["computed"]) for st in flat)
        out["moe_max_load"] = jnp.max(jnp.stack(
            [jnp.max(st["max_held_load"]) for st in flat]))
        out["moe_dropped"] = sum(jnp.sum(st["dropped"]) for st in flat)
    if flat:
        out["load_tree"] = {sec: [st.get("load") if st else None for st in lst]
                            for sec, lst in router.items()}
    return out


def balance_bias(cfg: ModelConfig, new_params: Dict[str, Any],
                 old_params: Dict[str, Any], loads: Dict[str, Any]
                 ) -> Dict[str, Any]:
    """The aux-loss-free balancing step (DeepSeek-V3 §2.1.2): each router
    bias moves by ``bias_rate * sign(mean load - load_i)`` from its value
    before the step, whatever the optimizer made of it (its gradient is
    zero: the bias only chooses experts)."""
    rate = cfg.moe.bias_rate
    out = dict(new_params)
    for sec, lst in loads.items():
        layers = list(out[sec])
        for i, load in enumerate(lst):
            if load is None or "router_bias" not in old_params[sec][i]["mlp"]:
                continue
            b = old_params[sec][i]["mlp"]["router_bias"]
            step = rate * jnp.sign(jnp.mean(load, axis=-1, keepdims=True) - load)
            mlp_p = dict(layers[i]["mlp"], router_bias=(b + step).astype(b.dtype))
            layers[i] = dict(layers[i], mlp=mlp_p)
        out[sec] = layers
    return out


def loss_fn(cfg: ModelConfig, params: Dict[str, Any], batch: Dict[str, jax.Array],
            *, loss_chunk: int = 1024,
            constrain=None) -> Tuple[jax.Array, Dict[str, jax.Array]]:
    """Mean next-token NLL over valid (segment>0) positions + MoE aux loss
    weighted by ``cfg.moe.aux_weight``."""
    hidden, aux = forward(cfg, params, batch, constrain=constrain)
    moe_aux = aux["moe_aux"]
    moe_aux_weight = cfg.moe.aux_weight if cfg.moe is not None else 0.01
    labels = batch["labels"]
    valid = (batch["segments"] > 0) & (labels >= 0)
    loss_sum, count = _chunked_xent(cfg, params, hidden, labels,
                                    valid, loss_chunk, constrain)
    xent = loss_sum / jnp.maximum(count, 1.0)
    total = xent + moe_aux_weight * moe_aux
    return total, {"loss": total, "xent": xent, "moe_aux": moe_aux,
                   "tokens": count, **_router_stats(aux["router"])}


def make_train_step(cfg: ModelConfig, *, loss_chunk: int = 1024,
                    grad_accum: int = 1, optimizer_kw: Optional[Dict[str, Any]] = None,
                    constrain=None, grad_shardings=None) -> Callable:
    """Build the jit-able train step (with optional gradient accumulation:
    the global batch is split into ``grad_accum`` microbatches scanned
    sequentially — the standard activation-memory lever).

    ``constrain(name, x)`` optionally pins activation shardings (supplied by
    the launch layer, which knows the mesh)."""
    _, opt_update, _ = make_optimizer(cfg.optimizer, **(optimizer_kw or {}))

    def single_loss(params, batch):
        return loss_fn(cfg, params, batch, loss_chunk=loss_chunk,
                       constrain=constrain)

    def _pin_grads(grads):
        # Pin gradient shardings to the parameter shardings so GSPMD lowers
        # the data-axis gradient reduction as reduce-scatter fused into the
        # FSDP layout instead of a full all-reduce (the standard FSDP fix;
        # saves ~half the gradient collective traffic).
        if grad_shardings is None:
            return grads
        return jax.tree.map(jax.lax.with_sharding_constraint, grads,
                            grad_shardings)

    def train_step(params, opt_state, batch):
        if grad_accum == 1:
            (loss, metrics), grads = jax.value_and_grad(
                single_loss, has_aux=True)(params, batch)
            grads = _pin_grads(grads)
        else:
            B = batch["tokens"].shape[0]
            mb = B // grad_accum
            micro = jax.tree.map(
                lambda x: x.reshape(grad_accum, mb, *x.shape[1:])
                if x.ndim >= 1 and x.shape[0] == B else x, batch)

            def accum(carry, mbatch):
                g_acc, l_acc = carry
                (l, m), g = jax.value_and_grad(single_loss, has_aux=True)(
                    params, mbatch)
                g = _pin_grads(g)
                return (jax.tree.map(jnp.add, g_acc, g), l_acc + l), m

            g0 = jax.tree.map(lambda p: jnp.zeros(p.shape, jnp.float32), params)
            (grads, loss), ms = jax.lax.scan(accum, (g0, jnp.zeros(())), micro)
            grads = jax.tree.map(lambda g: g / grad_accum, grads)
            loss = loss / grad_accum
            metrics = jax.tree.map(lambda m: m[-1], ms)
            if "load_tree" in ms:       # the bias follows the whole batch
                metrics["load_tree"] = jax.tree.map(lambda m: m.sum(0),
                                                    ms["load_tree"])
            metrics["loss"] = loss
        loads = metrics.pop("load_tree", None)
        new_params, new_opt, opt_metrics = opt_update(grads, opt_state, params)
        if loads is not None and cfg.moe is not None and cfg.moe.bias_rate:
            new_params = balance_bias(cfg, new_params, params, loads)
        metrics.update(opt_metrics)
        return new_params, new_opt, metrics

    return train_step
