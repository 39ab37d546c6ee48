"""Plain reference of the fed training step: a Llama-style decoder (SmolLM's
block) on packed rows, its loss, gradients and AdamW, in float32.

Written from the published description, in straightforward ``jax.numpy``
with every matrix product at ``precision="highest"``; it imports nothing of
the program under test.  Semantics, as the configuration file states them:

- token embedding E (vocab, d); output logits h @ E^T (tied);
- per layer: x += Attn(RMSNorm(x)); x += MLP(RMSNorm(x)); then a final
  RMSNorm.  RMSNorm(x) = x / sqrt(mean(x^2) + eps) * (1 + g), g starting at 0;
- attention: q, k, v projections with grouped KV heads (query head j reads
  KV head j // (heads / kv_heads)), rotary embedding of each half-split
  pair at the row's ``positions`` (inverse frequencies theta^(-2i/hd)),
  softmax(q k^T / sqrt(hd)) over keys of the same segment (segment > 0) at
  positions <= the query's, then the output projection;
- MLP: (silu(h W_gate) * (h W_up)) W_down;
- loss: mean cross-entropy of predicting token t+1 at t, over positions
  whose token and next token are in the same segment and carry loss;
- AdamW: global-norm clip, then m, v with bias correction, update
  m_hat / (sqrt(v_hat) + eps) + wd * p at lr * min(1, (t + 1) / warmup)
  for step t = 1, 2, ...; each parameter is kept in its stated dtype.

``quantize`` rounds both operands of every matrix product (the control
uses it to compute in a lower precision); the identity otherwise.
Gradients are summed over blocks of rows, and each layer is recomputed in
the backward pass, so that the step fits beside nothing else on one chip.
"""
from __future__ import annotations

from functools import partial
from typing import Any, Callable, Dict, Optional

import jax
import jax.numpy as jnp
import numpy as np

Params = Dict[str, Any]


def _identity(x):
    return x


def _rmsnorm(x, g, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps) * (1.0 + g)


def _rope(x, pos, theta):
    half = x.shape[-1] // 2
    inv = theta ** (-jnp.arange(half, dtype=jnp.float32) * 2.0 / x.shape[-1])
    ang = pos[..., None].astype(jnp.float32) * inv          # (B, S, half)
    cos, sin = jnp.cos(ang)[:, :, None], jnp.sin(ang)[:, :, None]
    a, b = x[..., :half], x[..., half:]
    return jnp.concatenate([a * cos - b * sin, b * cos + a * sin], axis=-1)


def _layer(cfg, q8, x, p, pos, seg):
    dot = partial(jnp.einsum, precision="highest")
    eps, theta = cfg["rms_norm_eps"], cfg["rope_theta"]
    H, KV = cfg["num_attention_heads"], cfg["num_key_value_heads"]
    hd = cfg["head_dim"]
    B, S, _ = x.shape
    h = _rmsnorm(x, p["pre_norm"]["scale"], eps)
    a = p["attn"]
    q = _rope(dot("bsd,dhk->bshk", q8(h), q8(a["wq"])), pos, theta)
    k = _rope(dot("bsd,dhk->bshk", q8(h), q8(a["wk"])), pos, theta)
    v = dot("bsd,dhk->bshk", q8(h), q8(a["wv"]))
    k = jnp.repeat(k, H // KV, axis=2)
    v = jnp.repeat(v, H // KV, axis=2)
    logits = dot("bqhk,bshk->bhqs", q8(q), q8(k)) / np.sqrt(hd)
    mask = ((seg[:, :, None] == seg[:, None, :]) & (seg[:, None, :] > 0)
            & (pos[:, :, None] >= pos[:, None, :]))
    logits = jnp.where(mask[:, None], logits, -1e30)
    probs = jax.nn.softmax(logits, axis=-1)
    o = dot("bhqs,bshk->bqhk", q8(probs), q8(v))
    x = x + dot("bshk,hkd->bsd", q8(o), q8(a["wo"]))
    h = _rmsnorm(x, p["mlp_norm"]["scale"], eps)
    f = p["mlp"]
    gate = jax.nn.silu(dot("bsd,df->bsf", q8(h), q8(f["wi_gate"])))
    up = dot("bsd,df->bsf", q8(h), q8(f["wi_up"]))
    return x + dot("bsf,fd->bsd", q8(gate * up), q8(f["wo"]))


def targets(tokens, loss_mask, segment_ids):
    """Next-token labels and the positions that carry loss."""
    nxt = np.concatenate([tokens[:, 1:], np.zeros_like(tokens[:, :1])], axis=1)
    nseg = np.concatenate([segment_ids[:, 1:], np.zeros_like(segment_ids[:, :1])],
                          axis=1)
    valid = (loss_mask > 0) & (segment_ids > 0) & (segment_ids == nseg)
    valid[:, -1] = False
    return nxt.astype(np.int32), valid


def nll_sum(cfg, q8, params, tokens, positions, segments, labels, valid):
    """Summed next-token NLL over the valid positions of a block of rows."""
    x = jnp.take(params["embed"]["embedding"], tokens, axis=0)

    def body(x, p):
        return jax.checkpoint(partial(_layer, cfg, q8))(x, p, positions,
                                                        segments), None

    x, _ = jax.lax.scan(body, x, params["pattern"][0])
    h = _rmsnorm(x, params["final_norm"]["scale"], cfg["rms_norm_eps"])
    logits = jnp.einsum("bsd,vd->bsv", q8(h), q8(params["embed"]["embedding"]),
                        precision="highest")
    lse = jax.nn.logsumexp(logits, axis=-1)
    picked = jnp.take_along_axis(logits, labels[..., None], axis=-1)[..., 0]
    return jnp.sum(jnp.where(valid, lse - picked, 0.0))


class Reference:
    """The reference training loop over host batches of packed rows."""

    def __init__(self, cfg: Dict[str, Any], *, rows_per_block: int = 2,
                 quantize: Optional[Callable] = None) -> None:
        self.cfg = cfg
        self.opt = cfg["optimizer"]
        self.rows_per_block = rows_per_block
        q8 = quantize or _identity
        self._grad = jax.jit(jax.value_and_grad(partial(nll_sum, cfg, q8)))
        self._update = jax.jit(self._adamw)

    def grads(self, params: Params, batch: Dict[str, np.ndarray]):
        """(mean loss, mean gradient) of one batch, summed block by block."""
        labels, valid = targets(batch["tokens"], batch["loss_mask"],
                                batch["segment_ids"])
        count = float(valid.sum())
        total, acc = 0.0, None
        n = len(batch["tokens"])
        for i in range(0, n, self.rows_per_block):
            sl = slice(i, i + self.rows_per_block)
            loss, g = self._grad(params, batch["tokens"][sl],
                                 batch["positions"][sl], batch["segment_ids"][sl],
                                 labels[sl], valid[sl])
            total += float(loss)
            acc = g if acc is None else jax.tree.map(jnp.add, acc, g)
        return total / count, jax.tree.map(lambda x: x / count, acc)

    def _adamw(self, params, grads, state):
        o = self.opt
        leaves = jax.tree.leaves(grads)
        norm = jnp.sqrt(sum(jnp.sum(g * g) for g in leaves))
        scale = jnp.minimum(1.0, o["grad_clip"] / jnp.maximum(norm, 1e-9))
        grads = jax.tree.map(lambda g: g * scale, grads)
        t = state["step"] + 1
        lr = o["lr"] * jnp.minimum(1.0, (t + 1) / o["warmup_steps"])
        tf = t.astype(jnp.float32)
        bc1, bc2 = 1.0 - o["b1"] ** tf, 1.0 - o["b2"] ** tf

        def one(p, g, m, v):
            m = o["b1"] * m + (1.0 - o["b1"]) * g
            v = o["b2"] * v + (1.0 - o["b2"]) * g * g
            pf = p.astype(jnp.float32)
            u = (m / bc1) / (jnp.sqrt(v / bc2) + o["eps"]) + o["weight_decay"] * pf
            return (pf - lr * u).astype(p.dtype), m, v

        out = jax.tree.map(one, params, grads, state["m"], state["v"])
        pick = lambda i: jax.tree.map(lambda x: x[i], out,
                                      is_leaf=lambda x: isinstance(x, tuple))
        return pick(0), {"m": pick(1), "v": pick(2), "step": t}, grads

    def train(self, params: Params, batches):
        """Run ``len(batches)`` steps.  Returns the losses, the per-leaf
        norms of the first (clipped) gradient, and the final parameters."""
        zeros = lambda p: jnp.zeros(p.shape, jnp.float32)
        state = {"m": jax.tree.map(zeros, params),
                 "v": jax.tree.map(zeros, params),
                 "step": jnp.zeros((), jnp.int32)}
        losses, first = [], None
        for batch in batches:
            f32 = jax.tree.map(lambda p: p.astype(jnp.float32), params)
            loss, g = self.grads(f32, batch)
            params, state, clipped = self._update(params, g, state)
            losses.append(loss)
            if first is None:
                first = leaf_norms(clipped)
        return losses, first, params


def leaf_norms(tree) -> np.ndarray:
    """The float32 norm of each leaf, in ``jax.tree.leaves`` order."""
    return np.array([float(jnp.sqrt(jnp.sum(jnp.square(x.astype(jnp.float32)))))
                     for x in jax.tree.leaves(tree)])


def _round_fp8(x):
    amax = jnp.max(jnp.abs(x))
    scale = jnp.where(amax > 0, 448.0 / amax, 1.0)
    return (x * scale).astype(jnp.float8_e4m3fn).astype(jnp.float32) / scale


@jax.custom_vjp
def fp8(x):
    """Round to float8 (e4m3) with one scale per tensor, and back: a matrix
    product of such operands is what an fp8 matrix unit computes, with exact
    accumulation.  The gradient passes through, rounded the same way, so the
    backward products run on fp8 operands too."""
    return _round_fp8(x)


def _fp8_fwd(x):
    return _round_fp8(x), None


def _fp8_bwd(_, g):
    return (_round_fp8(g),)


fp8.defvjp(_fp8_fwd, _fp8_bwd)


# ----------------------------------------------------------------- weights
def param_shapes(cfg: Dict[str, Any]) -> Params:
    """(shape, dtype, init) of every parameter, the layers stacked on a
    leading axis: matrices in the stated parameter dtype, drawn from
    N(0, initializer_range^2); the RMSNorm offsets g in float32, zero."""
    D, F = cfg["hidden_size"], cfg["intermediate_size"]
    H, KV, hd = (cfg["num_attention_heads"], cfg["num_key_value_heads"],
                 cfg["head_dim"])
    L, V = cfg["num_hidden_layers"], cfg["vocab_size"]
    w = lambda *s: (tuple(s), cfg["param_dtype"], "normal")
    g = lambda *s: (tuple(s), "float32", "zeros")
    return {
        "embed": {"embedding": w(V, D)},
        "final_norm": {"scale": g(D)},
        "pattern": [{
            "pre_norm": {"scale": g(L, D)},
            "attn": {"wq": w(L, D, H, hd), "wk": w(L, D, KV, hd),
                     "wv": w(L, D, KV, hd), "wo": w(L, H, hd, D)},
            "mlp_norm": {"scale": g(L, D)},
            "mlp": {"wi_gate": w(L, D, F), "wi_up": w(L, D, F),
                    "wo": w(L, F, D)},
        }],
        "remainder": [],
    }


def _is_spec(x) -> bool:
    return isinstance(x, tuple) and len(x) == 3 and isinstance(x[0], tuple)


def seed_words(seed: int) -> np.ndarray:
    """A seed of any size as two uint32 words (low, high)."""
    s = int(seed) & 0xFFFFFFFFFFFFFFFF
    return np.array([s & 0xFFFFFFFF, s >> 32], np.uint32)


def init_params(cfg: Dict[str, Any], words) -> Params:
    """Weights from the seed's ``seed_words``, in their stated dtypes (trace
    under ``jit`` to make them on the device in one call)."""
    specs = jax.tree.leaves(param_shapes(cfg), is_leaf=_is_spec)
    treedef = jax.tree.structure(param_shapes(cfg), is_leaf=_is_spec)
    key = jax.random.fold_in(jax.random.key(words[0]), words[1])
    out = []
    for i, (shape, dtype, kind) in enumerate(specs):
        if kind == "zeros":
            out.append(jnp.zeros(shape, dtype))
        else:
            x = jax.random.normal(jax.random.fold_in(key, i), shape, jnp.float32)
            out.append((x * cfg["initializer_range"]).astype(dtype))
    return jax.tree.unflatten(treedef, out)
