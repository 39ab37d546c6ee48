"""Latent attention and the dropless held-expert layer (Moonlight's blocks)
against the plain reference of the benchmark (``bench/reference/
mla_moe.py``), at a smoke size on seeded random weights, float32 on the
CPU: loss, per-leaf gradients and two optimizer steps with the balancing
bias; the chip share's parts summing to the whole layer; no drops at a
skewed routing, on either branch of the bounded rows, and the bounded
layer's gradients those of the full-size one; the flash kernel at qk 192 and v 128; the parameter trees
of program and reference."""
import importlib
import json
import os
import sys
from functools import partial

import jax
import jax.numpy as jnp
import numpy as np
import pytest

BENCH = os.path.join(os.path.dirname(__file__), "..", "bench")
if BENCH not in sys.path:
    sys.path.insert(0, BENCH)

from reference import decoder, mla_moe as ref  # noqa: E402

from repro.models import model as model_mod  # noqa: E402
from repro.models.moe import MOE_PATHS, moe_ffn  # noqa: E402
from repro.training.steps import loss_fn, make_train_step  # noqa: E402

flash = importlib.import_module("repro.kernels.flash_attention")
attention = importlib.import_module("repro.models.attention")

S = 64


def _driver():
    import harness
    return harness.load_module(os.path.join(BENCH, "drivers", "train_moe.py"),
                               "driver_train_moe")


def _cfg(**over):
    """The benchmark's Moonlight configuration at a smoke size."""
    with open(os.path.join(BENCH, "configs", "moonlight-16b-a3b.ep8.json")) as f:
        cfg = json.load(f)
    cfg.update({"hidden_size": 64, "intermediate_size": 96,
                "num_attention_heads": 4, "num_key_value_heads": 4,
                "kv_lora_rank": 32, "qk_nope_head_dim": 16,
                "qk_rope_head_dim": 8, "v_head_dim": 16,
                "moe_intermediate_size": 32, "n_routed_experts": 3,
                "num_experts_per_tok": 3, "num_hidden_layers": 3,
                "vocab_size": 128, "dtype": "float32", "param_dtype": "float32",
                "initializer_range": 0.05})
    cfg["deployment"] = dict(cfg["deployment"], n_routed_experts=8,
                             experts_held_first=2)
    cfg["optimizer"] = dict(cfg["optimizer"], lr=0.05, warmup_steps=1)
    cfg.update(over)
    return cfg


def _rows(seed, n_rows=2):
    """Packed rows: a few documents a row, a padded tail."""
    rng = np.random.default_rng(seed)
    out = {p: np.zeros((n_rows, S), np.int32)
           for p in ("tokens", "loss_mask", "positions", "segment_ids")}
    for r in range(n_rows):
        lens = [20, 25, 12]
        fill = 0
        for i, n in enumerate(lens, start=1):
            out["tokens"][r, fill:fill + n] = rng.integers(1, 128, n)
            out["loss_mask"][r, fill:fill + n] = 1
            out["positions"][r, fill:fill + n] = np.arange(n)
            out["segment_ids"][r, fill:fill + n] = i
            fill += n
    return out


def _program_batch(rows):
    from repro.launch.train import make_batch
    return {k: jnp.asarray(v) for k, v in make_batch(rows, S).items()}


@pytest.fixture(scope="module")
def setup():
    cfg = _cfg()
    mcfg = _driver().model_config(cfg)
    params = ref.init_params(cfg, decoder.seed_words(2026))
    return cfg, mcfg, params


def _leaf_gap(a, b):
    """Largest difference over the leaf's norm (float32 round-off of
    differently ordered sums: ~1e-6)."""
    a, b = np.asarray(a, np.float64), np.asarray(b, np.float64)
    return np.max(np.abs(a - b)) / max(np.linalg.norm(b), 1e-30)


def test_loss_and_gradients_match_the_reference(setup):
    cfg, mcfg, params = setup
    rows = _rows(1)
    (loss, metrics), grads = jax.value_and_grad(
        partial(loss_fn, mcfg, loss_chunk=32), has_aux=True)(
            params, _program_batch(rows))
    want_loss, want_grads, want_loads = ref.Reference(cfg).grads(
        jax.tree.map(lambda x: x.astype(jnp.float32), params), rows)
    # float32 throughout: only the order of the sums differs
    assert float(loss) == pytest.approx(want_loss, rel=2e-6)
    gaps = [_leaf_gap(g, w) for g, w in zip(jax.tree.leaves(grads),
                                             jax.tree.leaves(want_grads))
            if np.linalg.norm(np.asarray(w)) > 0]
    assert max(gaps) < 2e-5, gaps
    # the loads that move the bias: every layer's, every expert's
    np.testing.assert_array_equal(
        np.asarray(metrics["load_tree"]["pattern"][0]), want_loads)
    assert float(metrics["moe_dropped"]) == 0.0


def test_two_optimizer_steps_with_the_balancing_bias(setup):
    cfg, mcfg, params = setup
    batches = [_rows(2), _rows(3)]
    step = jax.jit(make_train_step(mcfg, loss_chunk=32,
                                   optimizer_kw={"lr": cfg["optimizer"]["lr"],
                                                 "warmup_steps": 1}))
    from repro.training.optim import adamw_init
    p, o = params, adamw_init(params)
    for b in batches:
        p, o, m = step(p, o, _program_batch(b))
        # three held experts of eight at k = 3 compute ~1.1 T a layer at
        # this near-uniform routing, under the fast branch's 2.25 T rows
        assert int(m["moe_overflow"]) == 0
    losses, _, want = ref.Reference(cfg).train(params, batches)
    assert float(m["loss"]) == pytest.approx(losses[-1], rel=2e-5)
    for path_got, w in zip(jax.tree_util.tree_flatten_with_path(p)[0],
                           jax.tree.leaves(want)):
        path, got = path_got
        if jax.tree_util.keystr(path).endswith("['router_bias']"):
            # +-gamma steps from the loads: exactly the reference's
            np.testing.assert_array_equal(np.asarray(got), w)
            assert np.count_nonzero(w) > 0
        else:
            # Adam steps each entry by about lr * sign(gradient): an entry
            # whose gradient is round-off small may step the other way,
            # 2 lr = 0.1 of a single entry
            assert _leaf_gap(got, w) < 2e-4, jax.tree_util.keystr(path)


def _layer(cfg, held_first, held_count, shared=2):
    from repro.models.config import MoEConfig
    mcfg = _driver().model_config(cfg)
    m = mcfg.moe
    return mcfg.replace(moe=MoEConfig(
        num_experts=m.num_experts, top_k=m.top_k, d_ff_expert=m.d_ff_expert,
        num_shared_experts=shared, router="sigmoid",
        routed_scaling=m.routed_scaling, dispatch="dropless",
        held_first=held_first, held_count=held_count))


def _layer_params(cfg, seed, bias=None):
    """One expert layer's weights over all 8 experts."""
    E, D, F = 8, cfg["hidden_size"], cfg["moe_intermediate_size"]
    k = jax.random.split(jax.random.key(seed), 7)
    n = lambda i, *s: 0.1 * jax.random.normal(k[i], s, jnp.float32)
    return {"router": n(0, D, E),
            "router_bias": jnp.zeros((E,)) if bias is None else bias,
            "wi_gate": n(1, E, D, F), "wi_up": n(2, E, D, F), "wo": n(3, E, F, D),
            "shared_wi_gate": n(4, D, 2 * F), "shared_wi_up": n(5, D, 2 * F),
            "shared_wo": n(6, 2 * F, D)}


def _share(p, first, count):
    return dict(p, **{w: p[w][first:first + count]
                      for w in ("wi_gate", "wi_up", "wo")})


def test_chip_shares_add_up_to_the_uncut_layer(setup):
    """Eight chips of one expert each: their parts, the shared experts
    (which every chip computes alike) counted once, are the whole layer
    as the reference computes it uncut."""
    cfg = setup[0]
    p = _layer_params(cfg, 7)
    x = jax.random.normal(jax.random.key(8), (2, S, cfg["hidden_size"]))
    parts = [moe_ffn(_share(p, s, 1), x, _layer(cfg, s, 1))[0] for s in range(8)]
    shared = parts[0] - moe_ffn(_share(p, 0, 1), x, _layer(cfg, 0, 1, shared=0))[0]
    total = sum(parts) - 7 * shared
    whole = dict(cfg, n_routed_experts=8,
                 deployment=dict(cfg["deployment"], experts_held_first=0))
    real = jnp.ones((S,), jnp.float32)
    want = jnp.stack([ref._experts(whole, decoder._identity, x[b], p, real)[0]
                      for b in range(2)])
    np.testing.assert_allclose(np.asarray(total), np.asarray(want),
                               atol=2e-6, rtol=2e-5)
    uncut = moe_ffn(p, x, _layer(cfg, 0, 8))[0]
    np.testing.assert_allclose(np.asarray(uncut), np.asarray(want),
                               atol=2e-6, rtol=2e-5)


SKEWS = {"fast": ((2,), 0), "full": ((2, 3, 4), 1)}


def _skewed(cfg, biased):
    """A layer holding experts 2-4 of 8 (k = 3, T = 128: rows R = 384, the
    fast branch's 288), its inputs and the reference routing's top-k."""
    bias = jnp.zeros((8,)).at[jnp.asarray(biased)].set(10.0)
    p = _layer_params(cfg, 9, bias)
    x = jax.random.normal(jax.random.key(10), (2, S, cfg["hidden_size"]))
    idx = jax.lax.top_k(jax.nn.sigmoid(x @ p["router"]) + bias, 3)[1]
    return _share(p, 2, 3), x, idx


@pytest.mark.parametrize("skew", SKEWS)
def test_no_assignment_is_dropped_at_a_skewed_routing(setup, skew):
    """A bias that sends every token to expert 2 (held here), or to all
    three held experts (every choice held: 3 T assignments, past the fast
    branch's rows, so the layer takes the full-size branch): every
    assignment to the held experts is computed, the largest held load is
    every token, and the layer is the reference's."""
    cfg = setup[0]
    biased, overflow = SKEWS[skew]
    p, x, idx = _skewed(cfg, biased)
    before = MOE_PATHS.copy()
    out, stats = moe_ffn(p, x, _layer(cfg, 2, 3))
    assert MOE_PATHS - before == {"dropless": 1}
    held = int(jnp.sum((idx >= 2) & (idx < 5)))
    loads = [int(jnp.sum(idx == e)) for e in (2, 3, 4)]
    assert int(stats["computed"]) == held
    assert int(stats["max_held_load"]) == max(loads) == 2 * S
    assert int(stats["overflow"]) == overflow
    assert int(stats["dropped"]) == 0
    real = jnp.ones((S,), jnp.float32)
    want = jnp.stack([ref._experts(cfg, decoder._identity, x[b], p, real)[0]
                      for b in range(2)])
    np.testing.assert_allclose(np.asarray(out), np.asarray(want),
                               atol=2e-6, rtol=2e-5)


@pytest.mark.parametrize("skew", SKEWS)
def test_bounded_layer_has_the_full_size_layers_gradients(setup, skew,
                                                          monkeypatch):
    """Per leaf, the gradients of the layer with its fast branch are those
    of the layer forced to R rows (headroom past R: one branch), on the
    branch each skew takes: both are exact, so only round-off differs."""
    from repro.models import moe
    cfg = setup[0]
    p, x, _ = _skewed(cfg, SKEWS[skew][0])
    layer = _layer(cfg, 2, 3)
    cot = jax.random.normal(jax.random.key(11), x.shape)
    loss = lambda p, x: jnp.sum(moe_ffn(p, x, layer)[0] * cot)
    got = jax.grad(loss, argnums=(0, 1))(p, x)
    monkeypatch.setattr(moe, "HEADROOM", 100)
    want = jax.grad(loss, argnums=(0, 1))(p, x)
    gaps = [_leaf_gap(g, w) for g, w in zip(jax.tree.leaves(got),
                                             jax.tree.leaves(want))
            if np.linalg.norm(np.asarray(w)) > 0]
    assert len(gaps) == 8 and max(gaps) < 1e-5, gaps


def _cond_outputs(jaxpr):
    """The output shapes of every ``cond`` in ``jaxpr`` and the jaxprs it
    holds, kernels' bodies left out (their ``pl.when``s are conds too)."""
    out = []
    for e in jaxpr.eqns:
        if e.primitive.name == "pallas_call":
            continue
        if e.primitive.name == "cond":
            out.append([v.aval.shape for v in e.outvars])
        for sub in jax.core.jaxprs_in_params(e.params):
            out += _cond_outputs(sub)
    return out


def test_fast_path_carries_no_full_size_residuals(setup):
    """The layer's forward and backward (``jax.vjp``) at the smoke size:
    each picks its branch with a ``cond`` that outputs nothing of R rows
    (no zero-filled residuals of the full-size branch on the fast path);
    with every expert held there is one branch and no ``cond``."""
    cfg = setup[0]
    p = _layer_params(cfg, 9)
    x = jax.random.normal(jax.random.key(10), (2, S, cfg["hidden_size"]))
    cot = jnp.ones(x.shape)

    def conds(params, layer):
        f = lambda p, x: moe_ffn(p, x, layer)[0]
        return _cond_outputs(jax.make_jaxpr(
            lambda p, x: jax.vjp(f, p, x)[1](cot))(params, x).jaxpr)

    R = 2 * S * 3
    bounded = conds(_share(p, 2, 3), _layer(cfg, 2, 3))
    assert len(bounded) == 2, bounded            # forward and backward
    assert all(s[0] != R for shapes in bounded for s in shapes if s), bounded
    assert conds(p, _layer(cfg, 0, 8)) == []


class TestLatentAttentionKernel:
    """Splash at qk 192 and v 128, q scaled before the call, in interpret
    mode, against ``attention_naive`` (which scales its float32 logits)."""

    B, SEQ, H = 1, 256, 2

    def inputs(self, dtype):
        k = jax.random.key(0)
        q, kk = (jax.random.normal(jax.random.fold_in(k, i),
                                   (self.B, self.SEQ, self.H, 192)) for i in (1, 2))
        v = jax.random.normal(jax.random.fold_in(k, 3), (self.B, self.SEQ, self.H, 128))
        seg = jnp.concatenate([jnp.full((self.B, 100), 1), jnp.full((self.B, 120), 2),
                               jnp.zeros((self.B, 36))], 1).astype(jnp.int32)
        pos = jnp.concatenate([jnp.arange(100), jnp.arange(120),
                               jnp.zeros(36)])[None].astype(jnp.int32)
        return q.astype(dtype), kk.astype(dtype), v.astype(dtype), seg, pos

    @staticmethod
    def kernel(q, k, v, seg):
        return flash.flash_attention(q, k, v, seg, interpret=True)

    @pytest.mark.parametrize("dtype,tol", [(jnp.float32, 2e-5), (jnp.bfloat16, 3e-2)])
    def test_forward_and_gradients(self, dtype, tol):
        # f32: round-off of the blocked online softmax; bf16: the inputs'
        # rounding and bf16 probabilities in the backward products
        q, k, v, seg, pos = self.inputs(dtype)
        real = np.asarray(seg > 0)[0]
        f = lambda q, k, v: self.kernel(q, k, v, seg)
        g = lambda q, k, v: attention.attention_naive(q, k, v, pos, pos, seg, seg)
        np.testing.assert_allclose(np.asarray(f(q, k, v), np.float32)[0][real],
                                   np.asarray(g(q, k, v), np.float32)[0][real],
                                   atol=tol, rtol=tol)
        cot = jax.random.normal(jax.random.key(4), (self.B, self.SEQ, self.H, 128))
        cot = cot * (seg > 0)[..., None, None]
        loss = lambda fn: lambda *a: jnp.sum(fn(*a).astype(jnp.float32) * cot)
        got = jax.grad(loss(f), argnums=(0, 1, 2))(q, k, v)
        want = jax.grad(loss(g), argnums=(0, 1, 2))(q, k, v)
        for a, b in zip(got, want):
            a, b = np.asarray(a, np.float32), np.asarray(b, np.float32)
            assert np.max(np.abs(a - b)) <= tol * np.max(np.abs(b))

    def test_no_attention_across_segments(self):
        q, k, v, seg, _ = self.inputs(jnp.float32)
        out = self.kernel(q, k, v, seg)
        # change the second segment's keys and values: the first's output
        # does not move, bit for bit
        k2 = k.at[:, 100:220].set(0.0)
        v2 = v.at[:, 100:220].multiply(-3.0)
        out2 = self.kernel(q, k2, v2, seg)
        np.testing.assert_array_equal(np.asarray(out)[:, :100],
                                      np.asarray(out2)[:, :100])

    def test_supports(self):
        assert flash.supports(8192, 192, 128) and flash.supports(2048, 64)
        assert not flash.supports(2048, 128) and not flash.supports(2048, 192)
        assert not flash.supports(1000, 64)              # no whole blocks


def test_one_chip_takes_the_kernel_and_the_dropless_path(setup, monkeypatch):
    """With the backend seen as one TPU, the latent attention of every
    layer lowers to the kernel (interpreted here) and the expert layers to
    the dropless path; the hidden states are the CPU path's."""
    # the kernel's head sizes: qk 128 + 64, v 128
    cfg = _cfg(num_hidden_layers=2, num_attention_heads=2, num_key_value_heads=2,
               qk_nope_head_dim=128, qk_rope_head_dim=64, v_head_dim=128)
    mcfg = _driver().model_config(cfg)
    params = ref.init_params(cfg, decoder.seed_words(5))
    rows = _rows(6)
    n = 256 // S            # rows of 256: four packed rows end to end,
    real = rows["segment_ids"] > 0          # each piece with an id of its own
    seg = np.concatenate([rows["segment_ids"] + 3 * c * real for c in range(n)], 1)
    batch = {"tokens": jnp.asarray(np.tile(rows["tokens"], (1, n))),
             "segments": jnp.asarray(seg),
             "positions": jnp.asarray(np.tile(rows["positions"], (1, n)))}
    want = model_mod.forward(mcfg, params, batch)[0]
    # jitted, as ``kernel_ops.flash_attention`` is: the kernel is built in
    # the wrapper's own trace, once for the step's shapes
    monkeypatch.setattr(model_mod.kernel_ops, "flash_attention",
                        jax.jit(partial(flash.flash_attention, interpret=True)))
    gmm = model_mod.kernel_ops.grouped_matmul
    monkeypatch.setattr(model_mod.kernel_ops, "grouped_matmul",
                        partial(gmm, interpret=True))
    monkeypatch.setattr(model_mod.jax, "default_backend", lambda: "tpu")
    monkeypatch.setattr(model_mod.jax, "device_count", lambda: 1)
    paths, moe = model_mod.ATTENTION_PATHS.copy(), MOE_PATHS.copy()
    got = model_mod.forward(mcfg, params, batch)[0]
    assert model_mod.ATTENTION_PATHS - paths == {"kernel": 2}
    assert MOE_PATHS - moe == {"dropless": 1}
    real = np.asarray(batch["segments"] > 0)
    np.testing.assert_allclose(np.asarray(got)[real], np.asarray(want)[real],
                               atol=2e-5, rtol=2e-5)


def test_program_and_reference_parameter_trees_agree():
    """At the cell's own size (shapes only): every leaf's shape and dtype."""
    from repro.models.model import model_defs
    from repro.models.params import abstract_params
    with open(os.path.join(BENCH, "configs", "moonlight-16b-a3b.ep8.json")) as f:
        cfg = json.load(f)
    got = jax.tree.map(lambda d: (d.shape, str(d.dtype)),
                       abstract_params(model_defs(_driver().model_config(cfg))))
    want = jax.tree.map(lambda s: (s[0], s[1]), ref.param_shapes(cfg),
                        is_leaf=decoder._is_spec)
    assert got == want
    n = sum(int(np.prod(s)) for s, _ in jax.tree.leaves(
        got, is_leaf=lambda x: isinstance(x, tuple) and isinstance(x[0], tuple)))
    # the chip share's 568.5 M parameters (dense layer 83.0 M, expert
    # layers 100.4 M each, embedding and head 83.9 M)
    assert n == pytest.approx(568.5e6, rel=1e-3)
