"""Plain reference of the Kimi-Linear train step on one chip's share: Kimi
Delta Attention and latent attention without rotation, a leading dense
layer, expert layers of which this chip holds some experts, the balance
loss and bias, AdamW; in float32.

Written from the published description (the configuration file's
equations, ``configs/kimi_linear_48b_a3b.py``), in straightforward
``jax.numpy`` with every matrix product at ``precision="highest"``; it
imports nothing of the program under test, and takes the expert layer,
the SwiGLU, AdamW and the row-by-row gradient from ``reference/mla_moe.py``
(through the DeepSeek-V3 key names, ``_ds``), editing none of them.
Semantics, per row:

- token embedding E (vocab, d); logits h @ U (untied); per layer x +=
  Mixer(RMSNorm(x)); x += FFN(RMSNorm(x)); a final RMSNorm.  RMSNorm(x) =
  x / sqrt(mean(x^2) + eps) * (1 + g), g starting at 0;
- the layers held are the published layers 1 .. num_hidden_layers; a
  layer's mixer is KDA where ``linear_attn_config.kda_layers`` lists it,
  latent attention where ``full_attn_layers`` does; the first
  ``first_k_dense_replace`` layers have the dense FFN, the rest experts;
- KDA: segment numbers count the segment starts (t = 0, or the segment id
  changes).  q, k, v = silu(conv(h W_q)), ... with conv(y)_t =
  sum_j w_j y_{t-j} over the j < width whose token t-j lies in t's
  segment; q = q / sqrt(|q|^2 + 1e-6) / sqrt(d), k likewise unscaled;
  beta = sigmoid(h W_beta); log a = -exp(A_log) softplus(h W_f_a W_f_b +
  dt_bias); then token by token, per head, S = 0 at a segment start, S =
  Diag(a) S, S = S + beta k (v - S^T k)^T, o = S^T q -- the recurrence
  itself, never a chunked form, run in checkpointed blocks of
  ``KDA_BLOCK`` tokens so that its gradient fits; then o =
  RMSNorm_d(o) (1 + g_o) sigmoid(h W_g_a W_g_b + g_bias), out = o W_o;
- latent attention as ``reference/mla_moe.py``'s, with no rotation: q =
  h W_q; [c, k_pe] = h W_kva, c = RMSNorm(c); [k_nope, v] = c W_kvb; k =
  [k_nope, k_pe] with k_pe shared by every head; causal softmax at scale
  (nope + rope)^-1/2 over the keys of the query's segment (segment > 0);
- expert layers, the balance loss and bias, the loss and AdamW as
  ``reference/mla_moe.py`` states them, each expert layer's bias moved
  by its own loads.

``quantize`` rounds both operands of every matrix product (the control
computes in fp8 through it), and q, k and v as they enter the recurrence;
the identity otherwise.
"""
from __future__ import annotations

import math
from functools import partial
from typing import Any, Callable, Dict, List, Optional

import jax
import jax.numpy as jnp
import numpy as np

from . import decoder, mla_moe

Params = Dict[str, Any]
#: tokens per checkpointed block of the KDA recurrence
KDA_BLOCK = 256


def _ds(cfg: Dict[str, Any]) -> Dict[str, Any]:
    """The configuration under the DeepSeek-V3 key names that
    ``reference/mla_moe.py`` reads."""
    dep = cfg["deployment"]
    return dict(cfg, n_routed_experts=cfg["num_experts"],
                num_experts_per_tok=cfg["num_experts_per_token"],
                n_shared_experts=cfg["num_shared_experts"],
                deployment=dict(dep, n_routed_experts=dep["num_experts"]))


def layer_kinds(cfg: Dict[str, Any]) -> List[str]:
    """``kda`` or ``attn`` for each held layer (published layers 1 .. L)."""
    lin = cfg["linear_attn_config"]
    kinds = []
    for i in range(1, cfg["num_hidden_layers"] + 1):
        if i in lin["kda_layers"]:
            kinds.append("kda")
        elif i in lin["full_attn_layers"]:
            kinds.append("attn")
        else:
            raise ValueError(f"layer {i} is in neither list")
    return kinds


def _starts(seg):
    return jnp.concatenate([jnp.ones((1,), bool), seg[1:] != seg[:-1]])


def _conv(y, w, sid):
    """Causal depthwise conv within segments: y (S, H, d), w (width, H, d)."""
    out = y * w[0]
    for j in range(1, w.shape[0]):
        back = jnp.concatenate([jnp.zeros_like(y[:j]), y[:-j]])
        same = jnp.concatenate([jnp.full((j,), -1), sid[:-j]]) == sid
        out = out + jnp.where(same[:, None, None], back * w[j], 0.0)
    return out


def recurrence(q, k, v, beta, log_a, starts):
    """The KDA recurrence token by token: q, k, log_a (S, H, dk), v (S, H,
    dv), beta (S, H), starts (S,) -> o (S, H, dv)."""
    S, H, dk = q.shape
    dv = v.shape[-1]
    hi = partial(jnp.einsum, precision="highest")

    def step(state, x):
        qt, kt, vt, bt, at, st = x
        state = jnp.where(st, 0.0, state) * jnp.exp(at)[:, :, None]
        err = vt - hi("hk,hkv->hv", kt, state)
        state = state + bt[:, None, None] * kt[:, :, None] * err[:, None, :]
        return state, hi("hkv,hk->hv", state, qt)

    @jax.checkpoint
    def block(state, xs):
        return jax.lax.scan(step, state, xs)

    nb = S // KDA_BLOCK if S % KDA_BLOCK == 0 and S > KDA_BLOCK else 1
    xs = tuple(x.reshape(nb, S // nb, *x.shape[1:])
               for x in (q, k, v, beta, log_a, starts))
    _, o = jax.lax.scan(block, jnp.zeros((H, dk, dv), jnp.float32), xs)
    return o.reshape(S, H, dv)


def _kda(cfg, q8, h, a, seg):
    """One row's KDA mixer; h (S, D)."""
    dot = partial(jnp.einsum, precision="highest")
    eps = cfg["rms_norm_eps"]
    d = cfg["linear_attn_config"]["head_dim"]
    starts = _starts(seg)
    sid = jnp.cumsum(starts.astype(jnp.int32))
    hq = q8(h)

    def branch(w, conv):
        return jax.nn.silu(_conv(dot("sd,dhk->shk", hq, q8(w)), conv, sid))

    l2 = lambda x: x / jnp.sqrt(jnp.sum(x * x, axis=-1, keepdims=True) + 1e-6)
    q = l2(branch(a["wq"], a["conv_q"])) / math.sqrt(d)
    k = l2(branch(a["wk"], a["conv_k"]))
    v = branch(a["wv"], a["conv_v"])
    beta = jax.nn.sigmoid(dot("sd,dh->sh", hq, q8(a["w_beta"])))
    f = dot("sr,rhk->shk", q8(dot("sd,dr->sr", hq, q8(a["wf_a"]))), q8(a["wf_b"]))
    log_a = -jnp.exp(a["A_log"])[:, None] * jax.nn.softplus(f + a["dt_bias"])
    o = recurrence(q8(q), q8(k), q8(v), beta, log_a, starts)
    g = dot("sr,rhk->shk", q8(dot("sd,dr->sr", hq, q8(a["wg_a"]))), q8(a["wg_b"]))
    o = decoder._rmsnorm(o, a["o_norm"]["scale"], eps) * jax.nn.sigmoid(g + a["g_bias"])
    return dot("shk,hkd->sd", q8(o), q8(a["wo"]))


def _mla_nope(cfg, q8, h, a, pos, seg):
    """One row's latent attention with no rotation; h (S, D)."""
    dot = partial(jnp.einsum, precision="highest")
    nope, rope = cfg["qk_nope_head_dim"], cfg["qk_rope_head_dim"]
    R, H = cfg["kv_lora_rank"], cfg["num_attention_heads"]
    q = dot("sd,dhk->shk", q8(h), q8(a["wq"]))
    ckv = dot("sd,dr->sr", q8(h), q8(a["wkv_a"]))
    c = decoder._rmsnorm(ckv[:, :R], a["kv_norm"]["scale"], cfg["rms_norm_eps"])
    kv = dot("sr,rhk->shk", q8(c), q8(a["wkv_b"]))
    k = jnp.concatenate([kv[..., :nope],
                         jnp.broadcast_to(ckv[:, None, R:], kv.shape[:2] + (rope,))],
                        axis=-1)
    v = kv[..., nope:]
    S = h.shape[0]
    scale = 1.0 / np.sqrt(nope + rope)

    @jax.checkpoint
    def block(qb, pb, sb):
        logits = dot("qhk,shk->hqs", q8(qb), q8(k)) * scale
        mask = (sb[:, None] == seg[None, :]) & (seg[None, :] > 0) & (pb[:, None] >= pos[None, :])
        probs = jax.nn.softmax(jnp.where(mask[None], logits, -1e30), axis=-1)
        return dot("hqs,shk->qhk", q8(probs), q8(v))

    qb = mla_moe.Q_BLOCK
    nb = S // qb if S % qb == 0 and S > qb else 1
    o = jax.lax.map(lambda t: block(*t), (q.reshape(nb, S // nb, *q.shape[1:]),
                                          pos.reshape(nb, -1), seg.reshape(nb, -1)))
    o = o.reshape(S, H, cfg["v_head_dim"])
    return dot("shk,hkd->sd", q8(o), q8(a["wo"]))


def _layer(cfg, q8, kind, moe, x, p, pos, seg):
    eps = cfg["rms_norm_eps"]
    h = decoder._rmsnorm(x, p["pre_norm"]["scale"], eps)
    if kind == "kda":
        x = x + _kda(cfg, q8, h, p["attn"], seg)
    else:
        x = x + _mla_nope(cfg, q8, h, p["attn"], pos, seg)
    h = decoder._rmsnorm(x, p["mlp_norm"]["scale"], eps)
    f = p["mlp"]
    if moe:
        out, bal, load = mla_moe._experts(_ds(cfg), q8, h, f,
                                          (seg > 0).astype(jnp.float32))
        return x + out, bal, load
    return x + mla_moe._swiglu(q8, h, f["wi_gate"], f["wi_up"], f["wo"]), 0.0, None


def row_terms(cfg, q8, params, tokens, positions, segments, labels, valid):
    """One row's summed next-token NLL, its summed balance terms over the
    expert layers, and each expert layer's loads (layers, E)."""
    kinds = layer_kinds(cfg)
    dense = cfg["first_k_dense_replace"]
    x = jnp.take(params["embed"]["embedding"], tokens, axis=0)
    bal, loads = 0.0, []
    layers = params["leading"] + params["remainder"]
    for i, (kind, p) in enumerate(zip(kinds, layers)):
        fn = jax.checkpoint(partial(_layer, cfg, q8, kind, i >= dense))
        x, b, load = fn(x, p, positions, segments)
        if i >= dense:
            bal = bal + b
            loads.append(load)
    h = decoder._rmsnorm(x, params["final_norm"]["scale"], cfg["rms_norm_eps"])
    logits = jnp.einsum("sd,dv->sv", q8(h), q8(params["embed"]["unembed"]),
                        precision="highest")
    lse = jax.nn.logsumexp(logits, axis=-1)
    picked = jnp.take_along_axis(logits, labels[:, None], axis=-1)[:, 0]
    return jnp.sum(jnp.where(valid, lse - picked, 0.0)), bal, jnp.stack(loads)


class Reference(mla_moe.Reference):
    """``reference/mla_moe.py``'s training loop over this model's rows; each
    expert layer's router bias moves by that layer's own loads."""

    def __init__(self, cfg: Dict[str, Any], *,
                 quantize: Optional[Callable] = None) -> None:
        super().__init__(cfg, quantize=quantize)
        q8 = quantize or decoder._identity

        def objective(params, tokens, positions, segments, labels, valid,
                      count, rows):
            nll, bal, loads = row_terms(cfg, q8, params, tokens, positions,
                                        segments, labels, valid)
            return nll / count + self.alpha * bal / rows, (nll, bal, loads)

        self._grad = jax.jit(jax.value_and_grad(objective, has_aux=True))

    def _adamw(self, params, grads, m, v, loads, t):
        """AdamW as the base class's, with the router biases left to the
        balancing step of each layer's own loads (layers in stack order)."""
        params, m, v, norms = super()._adamw(params, grads, m, v,
                                             jnp.zeros_like(loads[0]), t)
        flat, treedef = jax.tree_util.tree_flatten_with_path(params)
        out, i = [], 0
        for path, p in flat:
            if jax.tree_util.keystr(path).endswith("['router_bias']"):
                load = loads[i]
                step = self.gamma * jnp.sign(jnp.mean(load) - load)
                p = (p.astype(jnp.float32) + step).astype(p.dtype)
                i += 1
            out.append(p)
        return jax.tree.unflatten(treedef, out), m, v, norms


# ----------------------------------------------------------------- weights
def param_shapes(cfg: Dict[str, Any]) -> Params:
    """(shape, dtype, init) of every parameter, in the program's layout:
    the leading dense layers in ``leading``, the expert layers in
    ``remainder``, each on its own.  Matrices and the router are drawn from
    N(0, initializer_range^2) (matrices in the stated parameter dtype, the
    router in float32); the short convolutions from U(-w^-1/2, w^-1/2), w
    the width (PyTorch's Conv1d default); A_log = log U(1, 16) per head;
    dt_bias the inverse softplus of dt, log dt uniform over [log 0.001, log
    0.1] (floor 1e-4) per channel; RMSNorm offsets, router biases and the
    output gate's bias start at 0; all of these in float32 but the convs."""
    D, H = cfg["hidden_size"], cfg["num_attention_heads"]
    nope, rope, vd = cfg["qk_nope_head_dim"], cfg["qk_rope_head_dim"], cfg["v_head_dim"]
    R, F, Fe = cfg["kv_lora_rank"], cfg["intermediate_size"], cfg["moe_intermediate_size"]
    lin = cfg["linear_attn_config"]
    Hk, d, wid = lin["num_heads"], lin["head_dim"], lin["short_conv_kernel_size"]
    r = d                                   # the decay and gate projections' rank
    E, held = cfg["deployment"]["num_experts"], cfg["num_experts"]
    shared = cfg["num_shared_experts"] * Fe
    w = lambda *s: (tuple(s), cfg["param_dtype"], "normal")
    f32 = lambda kind, *s: (tuple(s), "float32", kind)
    z = partial(f32, "zeros")

    def kda():
        return {"wq": w(D, Hk, d), "wk": w(D, Hk, d), "wv": w(D, Hk, d),
                "conv_q": (( wid, Hk, d), cfg["param_dtype"], "conv"),
                "conv_k": ((wid, Hk, d), cfg["param_dtype"], "conv"),
                "conv_v": ((wid, Hk, d), cfg["param_dtype"], "conv"),
                "w_beta": w(D, Hk), "wf_a": w(D, r), "wf_b": w(r, Hk, d),
                "A_log": f32("a_log", Hk), "dt_bias": f32("dt_bias", Hk, d),
                "wg_a": w(D, r), "wg_b": w(r, Hk, d), "g_bias": z(Hk, d),
                "o_norm": {"scale": z(d)}, "wo": w(Hk, d, D)}

    def mla():
        return {"wq": w(D, H, nope + rope), "wkv_a": w(D, R + rope),
                "kv_norm": {"scale": z(R)}, "wkv_b": w(R, H, nope + vd),
                "wo": w(H, vd, D)}

    def block(kind, moe):
        mlp = ({"router": f32("normal", D, E), "wi_gate": w(held, D, Fe),
                "wi_up": w(held, D, Fe), "wo": w(held, Fe, D), "router_bias": z(E),
                "shared_wi_gate": w(D, shared), "shared_wi_up": w(D, shared),
                "shared_wo": w(shared, D)} if moe else
               {"wi_gate": w(D, F), "wi_up": w(D, F), "wo": w(F, D)})
        return {"pre_norm": {"scale": z(D)},
                "attn": kda() if kind == "kda" else mla(),
                "mlp_norm": {"scale": z(D)}, "mlp": mlp}

    kinds = layer_kinds(cfg)
    dense = cfg["first_k_dense_replace"]
    return {"embed": {"embedding": w(cfg["vocab_size"], D),
                      "unembed": w(D, cfg["vocab_size"])},
            "final_norm": {"scale": z(D)},
            "leading": [block(k, False) for k in kinds[:dense]],
            "pattern": [],
            "remainder": [block(k, True) for k in kinds[dense:]]}


def init_params(cfg: Dict[str, Any], words) -> Params:
    """Weights from the seed's ``decoder.seed_words``, in their stated
    dtypes (trace under ``jit`` to make them on the device in one call)."""
    shapes = param_shapes(cfg)
    specs = jax.tree.leaves(shapes, is_leaf=decoder._is_spec)
    treedef = jax.tree.structure(shapes, is_leaf=decoder._is_spec)
    key = jax.random.fold_in(jax.random.key(words[0]), words[1])
    out: List[Any] = []
    for i, (shape, dtype, kind) in enumerate(specs):
        k = jax.random.fold_in(key, i)
        u = lambda lo, hi: jax.random.uniform(k, shape, jnp.float32, lo, hi)
        if kind == "zeros":
            x = jnp.zeros(shape, jnp.float32)
        elif kind == "normal":
            x = jax.random.normal(k, shape, jnp.float32) * cfg["initializer_range"]
        elif kind == "conv":
            x = u(-1.0, 1.0) / math.sqrt(shape[0])
        elif kind == "a_log":
            x = jnp.log(u(1.0, 16.0))
        elif kind == "dt_bias":
            dt = jnp.maximum(jnp.exp(u(math.log(1e-3), math.log(1e-1))), 1e-4)
            x = dt + jnp.log(-jnp.expm1(-dt))
        else:
            raise ValueError(kind)
        out.append(x.astype(dtype))
    return jax.tree.unflatten(treedef, out)
