"""Plain reference of the Moonlight (DeepSeek-V3 family) train step on one
chip's share: latent attention, a leading dense layer, expert layers of
which this chip holds some experts, the balance loss and bias, AdamW; in
float32.

Written from the published description (``configs/moonlight_16b_a3b.py``'s
equations, as the configuration file states them), in straightforward
``jax.numpy`` with every matrix product at ``precision="highest"``; it
imports nothing of the program under test.  Semantics:

- token embedding E (vocab, d); output logits h @ U (untied, U (d, vocab));
- per layer: x += Attn(RMSNorm(x)); x += FFN(RMSNorm(x)); a final RMSNorm.
  RMSNorm(x) = x / sqrt(mean(x^2) + eps) * (1 + g), g starting at 0;
- attention: q = h W_q split into q_nope and q_pe; [c, k_pe] = h W_kva,
  c = RMSNorm(c); [k_nope, v] = c W_kvb; rotary embedding of each
  half-split pair of q_pe and k_pe at the row's ``positions`` (inverse
  frequencies theta^(-2i/rope_dim)), k_pe shared by every head;
  softmax((q . k) / sqrt(nope + rope)) over keys of the same segment
  (segment > 0) at positions <= the query's, times v, then W_o;
- leading layer FFN: (silu(h W_gate) * (h W_up)) W_down;
- expert layer FFN: scores s = sigmoid(h W_r) over all routed experts; the
  top k of s + b chosen; weights w = scaling * s / sum of the chosen s;
  the sum over the chosen experts that this chip holds of w times that
  expert's SwiGLU, computed here for every token and expert and masked,
  plus the shared experts' SwiGLU;
- loss: mean cross-entropy of predicting token t+1 at t over positions
  whose token and next token are in the same segment and carry loss, plus
  alpha times, per expert layer, the mean over rows of sum_i f_i P_i,
  f_i = (E / k) * (real tokens of the row choosing i) / (real tokens),
  P_i = mean over the row's real tokens of s_i / sum_j s_j;
- balance bias: after each step b_i += gamma * sign(mean load - load_i),
  loads counting the step's real tokens that chose expert i;
- AdamW as ``bench/reference/decoder.py`` states it, on every parameter
  but the router biases, each kept in its stated dtype.

``quantize`` rounds both operands of every matrix product (the control
computes in fp8 through it); the identity otherwise.  Gradients are
summed row by row, each layer recomputed in the backward pass, attention
computed a block of queries at a time, and the optimizer's moments kept
on the host while a gradient is computed, so that the step fits beside
nothing else on one chip.
"""
from __future__ import annotations

from functools import partial
from typing import Any, Callable, Dict, List, Optional

import jax
import jax.numpy as jnp
import numpy as np

from . import decoder

Params = Dict[str, Any]
#: queries per attention block
Q_BLOCK = 1024


def _dims(cfg: Dict[str, Any]) -> Dict[str, int]:
    dep = cfg["deployment"]
    return {"D": cfg["hidden_size"], "H": cfg["num_attention_heads"],
            "nope": cfg["qk_nope_head_dim"], "rope": cfg["qk_rope_head_dim"],
            "vd": cfg["v_head_dim"], "R": cfg["kv_lora_rank"],
            "F": cfg["intermediate_size"], "Fe": cfg["moe_intermediate_size"],
            "E": dep["n_routed_experts"], "held": cfg["n_routed_experts"],
            "first": dep["experts_held_first"], "k": cfg["num_experts_per_tok"],
            "shared": cfg["n_shared_experts"] * cfg["moe_intermediate_size"],
            "L": cfg["num_hidden_layers"], "dense": cfg["first_k_dense_replace"],
            "V": cfg["vocab_size"]}


def _rope(x, pos, theta):
    """x (S, ..., d) at positions pos (S,): half-split pairs."""
    half = x.shape[-1] // 2
    inv = theta ** (-jnp.arange(half, dtype=jnp.float32) * 2.0 / x.shape[-1])
    ang = pos.astype(jnp.float32)[:, None] * inv                # (S, half)
    ang = ang.reshape(ang.shape[:1] + (1,) * (x.ndim - 2) + ang.shape[1:])
    cos, sin = jnp.cos(ang), jnp.sin(ang)
    a, b = x[..., :half], x[..., half:]
    return jnp.concatenate([a * cos - b * sin, b * cos + a * sin], axis=-1)


def _attention(cfg, q8, h, a, pos, seg):
    """One row's latent attention; h (S, D)."""
    d = _dims(cfg)
    dot = partial(jnp.einsum, precision="highest")
    eps, theta = cfg["rms_norm_eps"], cfg["rope_theta"]
    q = dot("sd,dhk->shk", q8(h), q8(a["wq"]))
    q = jnp.concatenate([q[..., :d["nope"]],
                         _rope(q[..., d["nope"]:], pos, theta)], axis=-1)
    ckv = dot("sd,dr->sr", q8(h), q8(a["wkv_a"]))
    c = decoder._rmsnorm(ckv[:, :d["R"]], a["kv_norm"]["scale"], eps)
    k_pe = _rope(ckv[:, d["R"]:], pos, theta)                  # (S, rope)
    kv = dot("sr,rhk->shk", q8(c), q8(a["wkv_b"]))
    k = jnp.concatenate([kv[..., :d["nope"]],
                         jnp.broadcast_to(k_pe[:, None], kv.shape[:2] + (d["rope"],))],
                        axis=-1)
    v = kv[..., d["nope"]:]
    S = h.shape[0]
    scale = 1.0 / np.sqrt(d["nope"] + d["rope"])

    @jax.checkpoint
    def block(qb, pb, sb):
        logits = dot("qhk,shk->hqs", q8(qb), q8(k)) * scale
        mask = (sb[:, None] == seg[None, :]) & (seg[None, :] > 0) & (pb[:, None] >= pos[None, :])
        probs = jax.nn.softmax(jnp.where(mask[None], logits, -1e30), axis=-1)
        return dot("hqs,shk->qhk", q8(probs), q8(v))

    nb = S // Q_BLOCK if S % Q_BLOCK == 0 and S > Q_BLOCK else 1
    o = jax.lax.map(lambda t: block(*t), (q.reshape(nb, S // nb, *q.shape[1:]),
                                          pos.reshape(nb, -1), seg.reshape(nb, -1)))
    o = o.reshape(S, d["H"], d["vd"])
    return dot("shk,hkd->sd", q8(o), q8(a["wo"]))


def _swiglu(q8, h, wg, wu, wo):
    dot = partial(jnp.einsum, precision="highest")
    g = jax.nn.silu(dot("sd,df->sf", q8(h), q8(wg)))
    return dot("sf,fd->sd", q8(g * dot("sd,df->sf", q8(h), q8(wu))), q8(wo))


def _experts(cfg, q8, h, f, real):
    """One row's expert layer: (out, balance term, loads (E,))."""
    d = _dims(cfg)
    dot = partial(jnp.einsum, precision="highest")
    s = jax.nn.sigmoid(dot("sd,de->se", h, f["router"]))        # (S, E)
    _, idx = jax.lax.top_k(s + jax.lax.stop_gradient(f["router_bias"]), d["k"])
    chosen = jnp.sum(jax.nn.one_hot(idx, d["E"], dtype=jnp.float32), axis=1)
    w = chosen * s
    w = w / jnp.sum(w, axis=-1, keepdims=True) * cfg["routed_scaling_factor"]
    mine = w[:, d["first"]:d["first"] + d["held"]]             # (S, held)
    gate = jax.nn.silu(dot("sd,edf->esf", q8(h), q8(f["wi_gate"])))
    up = dot("sd,edf->esf", q8(h), q8(f["wi_up"]))
    y = dot("esf,efd->esd", q8(gate * up), q8(f["wo"]))
    out = jnp.einsum("se,esd->sd", mine, y, precision="highest")
    out = out + _swiglu(q8, h, f["shared_wi_gate"], f["shared_wi_up"], f["shared_wo"])
    n = jnp.maximum(jnp.sum(real), 1.0)
    f_i = jnp.sum(chosen * real[:, None], axis=0) * (d["E"] / d["k"]) / n
    P_i = jnp.sum(s / jnp.sum(s, axis=-1, keepdims=True) * real[:, None], axis=0) / n
    return out, jnp.sum(f_i * P_i), jnp.sum(chosen * real[:, None], axis=0)


def _layer(cfg, q8, moe, x, p, pos, seg):
    eps = cfg["rms_norm_eps"]
    h = decoder._rmsnorm(x, p["pre_norm"]["scale"], eps)
    x = x + _attention(cfg, q8, h, p["attn"], pos, seg)
    h = decoder._rmsnorm(x, p["mlp_norm"]["scale"], eps)
    f = p["mlp"]
    if moe:
        out, bal, load = _experts(cfg, q8, h, f, (seg > 0).astype(jnp.float32))
        return x + out, bal, load
    return x + _swiglu(q8, h, f["wi_gate"], f["wi_up"], f["wo"]), 0.0, None


def row_terms(cfg, q8, params, tokens, positions, segments, labels, valid):
    """One row's summed next-token NLL, its summed balance terms over the
    expert layers, and each expert layer's loads (layers, E)."""
    x = jnp.take(params["embed"]["embedding"], tokens, axis=0)
    lead = partial(_layer, cfg, q8, False)
    for p in params["leading"]:
        x, _, _ = jax.checkpoint(lead)(x, p, positions, segments)

    def body(x, p):
        x, bal, load = jax.checkpoint(partial(_layer, cfg, q8, True))(
            x, p, positions, segments)
        return x, (bal, load)

    x, (bal, loads) = jax.lax.scan(body, x, params["pattern"][0])
    h = decoder._rmsnorm(x, params["final_norm"]["scale"], cfg["rms_norm_eps"])
    logits = jnp.einsum("sd,dv->sv", q8(h), q8(params["embed"]["unembed"]),
                        precision="highest")
    lse = jax.nn.logsumexp(logits, axis=-1)
    picked = jnp.take_along_axis(logits, labels[:, None], axis=-1)[:, 0]
    return jnp.sum(jnp.where(valid, lse - picked, 0.0)), jnp.sum(bal), loads


class Reference:
    """The reference training loop over host batches of packed rows."""

    def __init__(self, cfg: Dict[str, Any], *,
                 quantize: Optional[Callable] = None) -> None:
        self.cfg = cfg
        self.opt = cfg["optimizer"]
        self.alpha = cfg["balance"]["seq_aux_alpha"]
        self.gamma = cfg["balance"]["bias_update_speed"]
        q8 = quantize or decoder._identity

        def objective(params, tokens, positions, segments, labels, valid,
                      count, rows):
            nll, bal, loads = row_terms(cfg, q8, params, tokens, positions,
                                        segments, labels, valid)
            return nll / count + self.alpha * bal / rows, (nll, bal, loads)

        grad = jax.value_and_grad(objective, has_aux=True)
        self._grad = jax.jit(grad)
        self._add = jax.jit(lambda a, b: jax.tree.map(jnp.add, a, b),
                            donate_argnums=0)

    def grads(self, params: Params, batch: Dict[str, np.ndarray]):
        """(mean loss, gradient, loads per expert layer) of one batch,
        summed row by row; params in float32 on the device."""
        labels, valid = decoder.targets(batch["tokens"], batch["loss_mask"],
                                        batch["segment_ids"])
        count = float(valid.sum())
        rows = float(len(batch["tokens"]))
        nll = bal = 0.0
        acc = loads = None
        for r in range(len(batch["tokens"])):
            (_, (n, b, ld)), g = self._grad(
                params, batch["tokens"][r], batch["positions"][r],
                batch["segment_ids"][r], labels[r], valid[r], count, rows)
            nll += float(n)
            bal += float(b)
            loads = np.asarray(ld) if loads is None else loads + np.asarray(ld)
            acc = g if acc is None else self._add(acc, g)
        return nll / count + self.alpha * bal / rows, acc, loads

    def _adamw(self, params, grads, m, v, loads, t):
        """One AdamW step on every leaf but the router biases, which take
        the balancing step from the loads.  Returns the new parameters and
        moments, and each leaf's norm of the clipped gradient."""
        o = self.opt
        paths = [jax.tree_util.keystr(k) for k, _ in
                 jax.tree_util.tree_flatten_with_path(params)[0]]
        leaves, treedef = jax.tree.flatten(params)
        g, m, v = jax.tree.leaves(grads), jax.tree.leaves(m), jax.tree.leaves(v)
        norm = jnp.sqrt(sum(jnp.sum(x * x) for x in g))
        scale = jnp.minimum(1.0, o["grad_clip"] / jnp.maximum(norm, 1e-9))
        g = [x * scale for x in g]
        lr = o["lr"] * jnp.minimum(1.0, (t + 1) / o["warmup_steps"])
        tf = t.astype(jnp.float32)
        bc1, bc2 = 1.0 - o["b1"] ** tf, 1.0 - o["b2"] ** tf
        out_p, out_m, out_v = [], [], []
        for path, p, gi, mi, vi in zip(paths, leaves, g, m, v):
            pf = p.astype(jnp.float32)
            if path.endswith("['router_bias']"):
                step = self.gamma * jnp.sign(jnp.mean(loads, -1, keepdims=True) - loads)
                out_p.append((pf + step.reshape(p.shape)).astype(p.dtype))
                out_m.append(mi)
                out_v.append(vi)
                continue
            mi = o["b1"] * mi + (1.0 - o["b1"]) * gi
            vi = o["b2"] * vi + (1.0 - o["b2"]) * gi * gi
            u = (mi / bc1) / (jnp.sqrt(vi / bc2) + o["eps"]) + o["weight_decay"] * pf
            out_p.append((pf - lr * u).astype(p.dtype))
            out_m.append(mi)
            out_v.append(vi)
        norms = jnp.stack([jnp.sqrt(jnp.sum(x * x)) for x in g])
        unflat = partial(jax.tree.unflatten, treedef)
        return unflat(out_p), unflat(out_m), unflat(out_v), norms

    def train(self, params: Params, batches):
        """Run ``len(batches)`` steps from ``params`` (in their stated
        dtypes).  Returns the losses, the per-leaf norms of the first
        (clipped) gradient, and the final parameters.  The moments wait
        on the host while a step's gradient is computed."""
        update = jax.jit(self._adamw, donate_argnums=(0, 1, 2, 3))
        to_f32 = jax.jit(lambda p: jax.tree.map(lambda x: x.astype(jnp.float32), p))
        zeros = lambda p: np.zeros(p.shape, np.float32)
        m = jax.tree.map(zeros, params)
        v = jax.tree.map(zeros, params)
        losses, first = [], None
        for t, batch in enumerate(batches, start=1):
            loss, g, loads = self.grads(to_f32(params), batch)
            params, m, v, norms = update(params, g, jax.device_put(m),
                                         jax.device_put(v), jnp.asarray(loads),
                                         jnp.asarray(t, jnp.int32))
            m, v = jax.device_get(m), jax.device_get(v)
            losses.append(loss)
            if first is None:
                first = np.asarray(norms, np.float64)
        return losses, first, params


# ----------------------------------------------------------------- weights
def param_shapes(cfg: Dict[str, Any]) -> Params:
    """(shape, dtype, init) of every parameter, in the program's layout:
    the leading dense layers each on their own, the expert layers stacked
    on a leading axis.  Matrices and the router are drawn from
    N(0, initializer_range^2) (matrices in the stated parameter dtype, the
    router in float32); RMSNorm offsets and router biases start at 0 in
    float32."""
    d = _dims(cfg)
    n = d["L"] - d["dense"]
    w = lambda *s: (tuple(s), cfg["param_dtype"], "normal")
    r = lambda *s: (tuple(s), "float32", "normal")
    z = lambda *s: (tuple(s), "float32", "zeros")
    qk = d["nope"] + d["rope"]

    def attn(*L):
        return {"wq": w(*L, d["D"], d["H"], qk),
                "wkv_a": w(*L, d["D"], d["R"] + d["rope"]),
                "kv_norm": {"scale": z(*L, d["R"])},
                "wkv_b": w(*L, d["R"], d["H"], d["nope"] + d["vd"]),
                "wo": w(*L, d["H"], d["vd"], d["D"])}

    lead = {"pre_norm": {"scale": z(d["D"])}, "attn": attn(),
            "mlp_norm": {"scale": z(d["D"])},
            "mlp": {"wi_gate": w(d["D"], d["F"]), "wi_up": w(d["D"], d["F"]),
                    "wo": w(d["F"], d["D"])}}
    moe = {"pre_norm": {"scale": z(n, d["D"])}, "attn": attn(n),
           "mlp_norm": {"scale": z(n, d["D"])},
           "mlp": {"router": r(n, d["D"], d["E"]), "router_bias": z(n, d["E"]),
                   "wi_gate": w(n, d["held"], d["D"], d["Fe"]),
                   "wi_up": w(n, d["held"], d["D"], d["Fe"]),
                   "wo": w(n, d["held"], d["Fe"], d["D"]),
                   "shared_wi_gate": w(n, d["D"], d["shared"]),
                   "shared_wi_up": w(n, d["D"], d["shared"]),
                   "shared_wo": w(n, d["shared"], d["D"])}}
    return {"embed": {"embedding": w(d["V"], d["D"]), "unembed": w(d["D"], d["V"])},
            "final_norm": {"scale": z(d["D"])},
            "leading": [lead] * d["dense"],
            "pattern": [moe],
            "remainder": []}


def init_params(cfg: Dict[str, Any], words) -> Params:
    """Weights from the seed's ``decoder.seed_words``, in their stated
    dtypes (trace under ``jit`` to make them on the device in one call)."""
    shapes = param_shapes(cfg)
    specs = jax.tree.leaves(shapes, is_leaf=decoder._is_spec)
    treedef = jax.tree.structure(shapes, is_leaf=decoder._is_spec)
    key = jax.random.fold_in(jax.random.key(words[0]), words[1])
    out: List[Any] = []
    for i, (shape, dtype, kind) in enumerate(specs):
        if kind == "zeros":
            out.append(jnp.zeros(shape, dtype))
        else:
            x = jax.random.normal(jax.random.fold_in(key, i), shape, jnp.float32)
            out.append((x * cfg["initializer_range"]).astype(dtype))
    return jax.tree.unflatten(treedef, out)
