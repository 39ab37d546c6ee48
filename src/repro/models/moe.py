"""Mixture-of-Experts feed-forward: a router, and two dispatch paths.

Routers (``MoEConfig.router``):

- ``softmax`` -- top-k of the softmax probabilities, renormalised; the
  Switch load-balance loss.
- ``sigmoid`` -- DeepSeek-V3's ``noaux_tc`` (arXiv:2412.19437 §2.1.2):
  scores ``s = sigmoid(x W_r)`` in float32; the top-k is chosen on
  ``s + b`` where ``b`` is a balancing bias the gradient does not move
  (the train step moves it from each step's expert loads); the weights are
  the chosen unbiased scores over their sum, times ``routed_scaling``; the
  sequence-wise balance loss ``sum_i f_i P_i`` per packed row.

Dispatch (``MoEConfig.dispatch``):

- ``capacity`` -- GShard/Switch style: tokens are dispatched to experts
  through dense one-hot einsums within fixed-size *groups* of tokens
  (``group_size``), so the layer is static-shaped and shards with pjit; the
  expert dim is sharded over the "model" mesh axis (expert parallelism) when
  ``num_experts % model_shards == 0``, else experts are replicated and the
  expert hidden dim is tensor-parallel.  Tokens over an expert's capacity
  are dropped (counted in the aux stats).
- ``dropless`` -- the layer holds experts ``[held_first, held_first +
  held_count)`` of ``num_experts`` (one chip's share under expert
  parallelism).  It routes over all of them, sorts the assignments that
  fall on its own experts by expert, runs the three expert products as
  grouped matrix products over those groups (megablox ``gmm``, its
  backward ``gmm``/``tgmm``: ``kernels/ops.grouped_matmul``), and adds each
  result back to its token with its weight.  Every assignment to a held
  expert is computed: nothing is dropped, at any skew.  The assignments to
  experts held elsewhere are not computed here and nothing stands in for
  them: the layer's output is this share's part (plus the shared experts,
  which every share computes alike).

``MOE_PATHS`` counts the dispatch path of each MoE layer traced.
"""
from __future__ import annotations

from collections import Counter
from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp

from ..kernels import ops as kernel_ops
from .config import ModelConfig, MoEConfig
from .params import ParamDef

#: MoE layers traced, by dispatch path ("dropless", "capacity"); counted at
#: trace time, so a scanned layer counts once per trace of the step
MOE_PATHS: Counter = Counter()


def moe_defs(cfg: ModelConfig) -> Dict[str, ParamDef]:
    m = cfg.moe or MoEConfig()
    D, F, E = cfg.d_model, m.d_ff_expert, m.num_experts
    dt = jnp.dtype(cfg.param_dtype)
    H = m.held
    defs: Dict[str, ParamDef] = {
        "router": ParamDef((D, E), ("embed", None), jnp.float32),
        "wi_gate": ParamDef((H, D, F), ("experts", "embed", "ffn"), dt),
        "wi_up": ParamDef((H, D, F), ("experts", "embed", "ffn"), dt),
        "wo": ParamDef((H, F, D), ("experts", "ffn", "embed"), dt, "scaled"),
    }
    if m.router == "sigmoid":
        defs["router_bias"] = ParamDef((E,), (None,), jnp.float32, "zeros")
    if m.num_shared_experts:
        S = m.num_shared_experts * F
        defs["shared_wi_gate"] = ParamDef((D, S), ("embed", "ffn"), dt)
        defs["shared_wi_up"] = ParamDef((D, S), ("embed", "ffn"), dt)
        defs["shared_wo"] = ParamDef((S, D), ("ffn", "embed"), dt, "scaled")
    return defs


def _capacity(group: int, m: MoEConfig) -> int:
    cap = int(group * m.top_k * m.capacity_factor / m.num_experts)
    return max(4, ((cap + 3) // 4) * 4)  # 4-aligned, never zero


def route(p: Dict[str, jax.Array], x: jax.Array, m: MoEConfig
          ) -> Tuple[jax.Array, jax.Array, jax.Array]:
    """x (..., D) -> (weights (..., k) float32, expert ids (..., k), the
    float32 probabilities the balance loss reads (..., E))."""
    if m.router == "sigmoid":
        scores = jax.nn.sigmoid(jnp.matmul(
            x.astype(jnp.float32), p["router"].astype(jnp.float32),
            precision=jax.lax.Precision.HIGHEST))
        _, idx = jax.lax.top_k(scores + jax.lax.stop_gradient(p["router_bias"]),
                               m.top_k)
        w = jnp.take_along_axis(scores, idx, axis=-1)
        w = w / jnp.sum(w, axis=-1, keepdims=True) * m.routed_scaling
        return w, idx, scores / jnp.sum(scores, axis=-1, keepdims=True)
    if m.router != "softmax":
        raise ValueError(f"unknown router {m.router!r}")
    logits = x.astype(jnp.float32) @ p["router"].astype(jnp.float32)
    probs = jax.nn.softmax(logits, axis=-1)
    w, idx = jax.lax.top_k(probs, m.top_k)
    return w / jnp.maximum(w.sum(-1, keepdims=True), 1e-9), idx, probs


def _shared(p: Dict[str, jax.Array], x: jax.Array) -> jax.Array:
    g = jax.nn.silu(x @ p["shared_wi_gate"])
    return (g * (x @ p["shared_wi_up"])) @ p["shared_wo"]


def moe_ffn(p: Dict[str, jax.Array], x: jax.Array, cfg: ModelConfig,
            group_size: int = 2048, constrain=None,
            valid: Optional[jax.Array] = None
            ) -> Tuple[jax.Array, Dict[str, jax.Array]]:
    """x: (B, S, D) -> (B, S, D), aux stats: ``lb_loss`` (unweighted),
    ``drop_frac``; the dropless path adds the routing counters (see
    ``moe_dropless``).  ``valid`` (B, S) marks the real tokens (segment >
    0), which the sigmoid router's balance loss and loads count."""
    m = cfg.moe or MoEConfig()
    if m.dispatch == "dropless":
        MOE_PATHS["dropless"] += 1
        return moe_dropless(p, x, m, valid)
    if m.dispatch != "capacity" or m.held_count:
        raise ValueError(f"MoE dispatch {m.dispatch!r} with {m.held_count} "
                         f"experts held: capacity dispatch holds them all")
    MOE_PATHS["capacity"] += 1
    return _moe_capacity(p, x, cfg, group_size, constrain)


def _seq_balance(idx: jax.Array, probs: jax.Array, valid: Optional[jax.Array],
                 m: MoEConfig) -> Tuple[jax.Array, jax.Array]:
    """DeepSeek-V3's sequence-wise balance loss, mean over rows, and each
    expert's load (real tokens that chose it).  idx (B, S, k), probs
    (B, S, E) the scores normalised per token."""
    B, S, _ = idx.shape
    E = m.num_experts
    vf = (jnp.ones((B, S), jnp.float32) if valid is None
          else valid.astype(jnp.float32))
    chose = jnp.sum(jax.nn.one_hot(idx, E, dtype=jnp.float32), axis=2) * vf[..., None]
    n = jnp.maximum(jnp.sum(vf, axis=1), 1.0)[:, None]          # (B, 1)
    f = jnp.sum(chose, axis=1) * (E / m.top_k) / n               # (B, E)
    P = jnp.sum(probs * vf[..., None], axis=1) / n               # (B, E)
    return jnp.mean(jnp.sum(f * P, axis=-1)), jnp.sum(chose, axis=(0, 1))


def moe_dropless(p: Dict[str, jax.Array], x: jax.Array, m: MoEConfig,
                 valid: Optional[jax.Array] = None
                 ) -> Tuple[jax.Array, Dict[str, jax.Array]]:
    """The held experts' part of the layer, every assignment computed.

    Aux stats: ``lb_loss`` (the router's balance loss), ``load`` (E,) real
    tokens per expert over all E (the balancing bias's input), and the
    routing counters ``computed`` (assignments computed here),
    ``max_held_load`` (the largest held expert's assignments) and
    ``dropped`` (assignments to held experts whose row lies past the
    products' rows: 0 at any routing, counted where the result is read)."""
    if m.router != "sigmoid":
        raise ValueError("the dropless layer balances with the sigmoid "
                         "router's bias and sequence-wise loss")
    B, S, D = x.shape
    T, k, H = B * S, m.top_k, m.held
    w, idx, probs = route(p, x, m)                               # (B, S, k)
    lb_loss, load = _seq_balance(idx, probs, valid, m)
    local = idx.reshape(T * k) - m.held_first
    here = (local >= 0) & (local < H)
    key = jnp.where(here, local, H)                  # H: held elsewhere
    order = jnp.argsort(key, stable=True)            # held experts first
    sizes = jnp.bincount(key, length=H + 1)[:H].astype(jnp.int32)
    # a token chooses k distinct experts, so at most min(k, H) are here:
    # the static bound that makes the layer dropless at any routing
    R = T * min(k, H)
    computed = jnp.sum(sizes)
    live = (jnp.arange(R) < computed)[:, None]
    # rows past the groups are never visited by the kernels: every product's
    # output is masked there before it meets another product, forward and
    # backward (a select, so that whatever those rows hold stays out)
    gmm = lambda a, wt: jnp.where(live, kernel_ops.grouped_matmul(a, wt, sizes), 0)
    xs = jnp.where(live, x.reshape(T, D)[order[:R] // k], 0)
    ys = gmm(jax.nn.silu(gmm(xs, p["wi_gate"])) * gmm(xs, p["wi_up"]), p["wo"])
    # back to the tokens: each (token, choice) held here reads its row of
    # the sorted products (a gather, whose backward writes each row once),
    # one choice at a time into a float32 sum
    slot = jnp.zeros((T * k,), jnp.int32).at[order].set(
        jnp.arange(T * k, dtype=jnp.int32))
    dropped = jnp.sum(here & (slot >= R))           # held here, not in a row
    slot = jnp.where(here, jnp.minimum(slot, R - 1), 0).reshape(T, k)
    wk = jnp.where(here.reshape(T, k), w.reshape(T, k), 0.0)
    out = jnp.zeros((T, D), jnp.float32)
    for j in range(k):
        out = out + wk[:, j:j + 1] * ys[slot[:, j]].astype(jnp.float32)
    out = out.astype(x.dtype).reshape(B, S, D)
    if m.num_shared_experts:
        out = out + _shared(p, x)
    stats = {"lb_loss": lb_loss, "load": load, "computed": computed,
             "max_held_load": jnp.max(sizes),
             "dropped": dropped,
             "drop_frac": jnp.zeros((), jnp.float32)}
    return out, stats


def _moe_capacity(p: Dict[str, jax.Array], x: jax.Array, cfg: ModelConfig,
                  group_size: int, constrain
                  ) -> Tuple[jax.Array, Dict[str, jax.Array]]:
    """Grouped dispatch: (n_groups, G, D) tokens -> (n_groups, E, C, D)
    expert slices -> expert MLP -> combined back.  All einsums are
    static-shaped."""
    m = cfg.moe or MoEConfig()
    B, S, D = x.shape
    T = B * S
    G = min(group_size, T)
    if T % G:
        G = T  # fallback: single group (tiny smoke configs)
    n = T // G
    C = _capacity(G, m)
    xg = x.reshape(n, G, D)
    if constrain is not None:
        # GShard layout: groups sharded over data AND model so dispatch/
        # combine lower as all-to-alls instead of dense partial-sum
        # all-reduces (the combine AR moves the full (n,G,D) stream twice;
        # the a2a moves each expert slot once)
        xg = constrain("moe_tokens", xg)

    # ---- router (fp32 for numerics)
    gate_vals, gate_idx, probs = route(p, xg, m)                  # (n, G, k)

    # ---- capacity assignment: position of each (token, k) within its expert.
    # Counting is exact int32 (bf16 cumsum breaks past 256); the one-hot
    # masks are bf16 — they only ever hold 0/1, and f32 masks doubled the
    # router-side HBM/collective bytes (kimi: 1.6 GB f32 all-gathers).
    onehot_i = jax.nn.one_hot(gate_idx, m.num_experts, dtype=jnp.int32)  # (n,G,k,E)
    flat = onehot_i.reshape(n, G * m.top_k, m.num_experts)
    pos = jnp.cumsum(flat, axis=1) * flat - 1                  # slot per assignment
    pos = pos.reshape(n, G, m.top_k, m.num_experts)
    in_cap = (pos >= 0) & (pos < C)
    slot_oh = jax.nn.one_hot(pos, C, dtype=x.dtype)
    slot_oh = slot_oh * in_cap[..., None].astype(x.dtype)      # (n,G,k,E,C)

    # combine weights: (n, G, E, C); dispatch mask is its support
    onehot = onehot_i.astype(x.dtype)
    combine = jnp.einsum("ngk,ngkec->ngec", gate_vals.astype(x.dtype),
                         slot_oh * onehot[..., None])
    dispatch = (combine > 0.0).astype(x.dtype)

    # ---- dispatch -> expert MLP -> combine
    pin = constrain if constrain is not None else (lambda name, v: v)
    expert_in = pin("moe_ecd", jnp.einsum("ngec,ngd->necd", dispatch, xg))
    h = jax.nn.silu(jnp.einsum("necd,edf->necf", expert_in, p["wi_gate"]))
    h = h * jnp.einsum("necd,edf->necf", expert_in, p["wi_up"])
    expert_out = pin("moe_ecd", jnp.einsum("necf,efd->necd", h, p["wo"]))  # (n,E,C,D)
    out = jnp.einsum("ngec,necd->ngd", combine.astype(x.dtype), expert_out)

    if m.num_shared_experts:
        out = out + _shared(p, xg)

    # ---- aux: load-balance loss (Switch, per group) + dropped fraction
    me = probs.mean(axis=1)                                    # (n, E)
    ce = onehot_i.sum(axis=2).mean(axis=1).astype(jnp.float32)  # (n, E) routed
    lb_loss = m.num_experts * jnp.mean(jnp.sum(me * ce, axis=-1))
    load = onehot_i.sum(axis=(0, 1, 2)).astype(jnp.float32)
    dropped = 1.0 - jnp.sum(in_cap & (onehot_i > 0)) / (n * G * m.top_k)
    return out.reshape(B, S, D), {"lb_loss": lb_loss, "drop_frac": dropped,
                                  "load": load}
