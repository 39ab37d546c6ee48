"""Kimi Delta Attention: the chunked Pallas kernel (interpreted on the CPU)
against the token-by-token recurrence, forward and gradients, over the
packed-row layouts that exercise its resets; the short convolution's
segment mask; latent attention without rotation; and the Kimi-Linear
decoder's loss, gradients and optimizer steps against the benchmark's
plain reference (``bench/reference/kda_mla_moe.py``), float32, seeded
random weights.

Tolerances.  Program and recurrence are both float32 and differ only in
the order of their sums (chunked against sequential), so they agree to a
few 1e-6 of the largest value: the kernel is held to ``TOL`` = 1e-4 of
it.  A state kept in bf16 misses by ~1e-2 of it, which
``test_a_bf16_state_fails_the_tolerance`` shows."""
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

from reference import decoder, kda_mla_moe as ref  # noqa: E402

from repro.kernels import kda as kda_kernel  # noqa: E402
from repro.models import kda as kda_model  # noqa: E402
from repro.models import model as model_mod  # noqa: E402
from repro.training.steps import loss_fn, make_train_step  # noqa: E402

#: largest error of the kernel over the largest value compared (module doc)
TOL = 1e-4
H, D = 2, 16


def _recurrence(q, k, v, log_a, beta, seg, state_dtype=jnp.float32):
    """The reference's token-by-token recurrence over (B, S, ...) rows,
    q and k normalised and scaled as the kernel does; the state kept in
    ``state_dtype`` between tokens."""
    l2 = lambda x: x / jnp.sqrt(jnp.sum(x * x, -1, keepdims=True) + 1e-6)
    q, k = l2(q) * D ** -0.5, l2(k)

    def row(q, k, v, la, b, s):
        starts = jnp.concatenate([jnp.ones((1,), bool), s[1:] != s[:-1]])
        if state_dtype == jnp.float32:
            return ref.recurrence(q, k, v, b, la, starts)

        def step(st, x):
            qt, kt, vt, bt, at, first = x
            st = jnp.where(first, 0.0, st.astype(jnp.float32)) * jnp.exp(at)[:, :, None]
            err = vt - jnp.einsum("hk,hkv->hv", kt, st)
            st = st + bt[:, None, None] * kt[:, :, None] * err[:, None, :]
            return st.astype(state_dtype), jnp.einsum("hkv,hk->hv", st, qt)
        return jax.lax.scan(step, jnp.zeros((H, D, D), state_dtype),
                            (q, k, v, b, la, starts))[1]
    return jax.vmap(row)(q, k, v, log_a, beta, seg)


def _inputs(seed, seg, strong=False):
    rng = np.random.default_rng(seed)
    B, S = seg.shape
    n = lambda *s: jnp.asarray(rng.normal(size=s), jnp.float32)
    if strong:
        # A = 16 (the top of A_log's init, log U(1, 16)) and dt_bias at the
        # top of its init (softplus^-1 of 0.1), the decay projection adding
        # a few units: down to ~-100 nats a token and channel, so that the
        # factorised exp(g_t) exp(-g_i) would overflow within one chunk
        f = n(B, S, H, D) * 3.0
        log_a = -16.0 * jax.nn.softplus(f + np.log(np.expm1(0.1)))
    else:
        log_a = -jnp.asarray(rng.uniform(0.0, 0.5, (B, S, H, D)), jnp.float32)
    beta = jax.nn.sigmoid(n(B, S, H))
    return n(B, S, H, D), n(B, S, H, D), n(B, S, H, D), log_a, beta


def _segments(layout):
    S = 192
    seg = np.ones((2, S), np.int32)
    if layout == "one_document_over_chunks":
        pass                                     # 3 chunks, no start after 0
    elif layout == "starts_inside_and_on_chunks":
        seg[0, 40:] = 2                          # inside the first chunk
        seg[0, 64:] = 3                          # on a chunk boundary
        seg[1, 100:] = 5                         # inside the second chunk
        seg[1, 128:] = 6                         # on the next boundary
    elif layout == "one_token_document":
        seg[0, 70:] = 2
        seg[0, 71:] = 3                          # token 70 alone
        seg[1, 63:] = 4
        seg[1, 64:] = 5                          # the last token of a chunk alone
    elif layout == "padding":
        seg[:, 50:] = 2
        seg[0, 150:] = 0                         # a padded tail
        seg[1, 131:] = 0
    return jnp.asarray(seg)


def _gap(a, b):
    a, b = np.asarray(a, np.float64), np.asarray(b, np.float64)
    return np.max(np.abs(a - b)) / np.max(np.abs(b))


def _kernel(q, k, v, log_a, beta, seg):
    """The kernel on (B, S, H, ...) inputs (it takes heads first)."""
    hf = lambda x: jnp.swapaxes(x, 1, 2)
    o, _ = kda_kernel.kda(hf(q), hf(k), hf(v), hf(log_a), hf(beta), seg,
                          scale=D ** -0.5, interpret=True)
    return hf(o)


@pytest.mark.parametrize("layout,strong", [
    ("one_document_over_chunks", False),
    ("starts_inside_and_on_chunks", False),
    ("one_token_document", False),
    ("padding", False),
    ("starts_inside_and_on_chunks", True),
])
def test_chunked_kernel_matches_the_recurrence(layout, strong):
    seg = _segments(layout)
    args = _inputs(7, seg, strong)
    got = _kernel(*args, seg)
    want = _recurrence(*args, seg)
    assert np.isfinite(np.asarray(got)).all()
    assert _gap(got, want) < TOL
    w = jnp.asarray(np.random.default_rng(3).normal(size=got.shape), jnp.float32)
    g_got = jax.grad(lambda *a: jnp.sum(_kernel(*a, seg) * w), argnums=range(5))(*args)
    g_want = jax.grad(lambda *a: jnp.sum(_recurrence(*a, seg) * w),
                      argnums=range(5))(*args)
    for name, a, b in zip(("q", "k", "v", "log_a", "beta"), g_got, g_want):
        assert np.isfinite(np.asarray(a)).all(), name
        assert _gap(a, b) < TOL, (name, _gap(a, b))


def test_a_bf16_state_fails_the_tolerance():
    """What ``TOL`` tells apart: the recurrence with its state rounded to
    bf16 between tokens misses the float32 one by far more than TOL."""
    seg = _segments("starts_inside_and_on_chunks")
    args = _inputs(7, seg)
    assert _gap(_recurrence(*args, seg, state_dtype=jnp.bfloat16),
                _recurrence(*args, seg)) > 10 * TOL


def test_state_resets_at_every_segment_start():
    """Changing one document's tokens moves no output of another document,
    wherever the boundary falls against the chunks."""
    seg = _segments("starts_inside_and_on_chunks")
    q, k, v, log_a, beta = _inputs(11, seg)
    base = _kernel(q, k, v, log_a, beta, seg)
    moved = _kernel(q, k.at[0, 40:64].multiply(-3.0), v.at[0, 40:64].add(5.0),
                    log_a, beta, seg)
    diff = np.abs(np.asarray(moved - base))[0].max(axis=(1, 2))
    assert diff[:40].max() == 0 and diff[64:].max() == 0
    assert diff[40:64].min() > 0


@pytest.mark.parametrize("layout", ["starts_inside_and_on_chunks", "one_token_document",
                                    "padding"])
def test_resets_are_counted_from_the_kernels_masks(layout):
    """The kernel's reset count is the segment starts after each row's
    first token that its masks act on; rows of one segment (the state
    carried across documents) count none."""
    seg = _segments(layout)
    hf = lambda x: jnp.swapaxes(x, 1, 2)
    args = [hf(x) for x in _inputs(5, seg)]
    _, resets = kda_kernel.kda(*args, seg, scale=D ** -0.5, interpret=True)
    s = np.asarray(seg)
    assert int(resets) == np.count_nonzero(s[:, 1:] != s[:, :-1]) > 0
    _, none = kda_kernel.kda(*args, jnp.ones_like(seg), scale=D ** -0.5, interpret=True)
    assert int(none) == 0


def test_short_conv_stops_at_segment_starts():
    rng = np.random.default_rng(5)
    x = jnp.asarray(rng.normal(size=(1, 12, 2, 3)), jnp.float32)
    w = jnp.asarray(rng.normal(size=(4, 2, 3)), jnp.float32)
    seg = jnp.asarray([[1] * 5 + [2] * 7])
    sid = jnp.cumsum(kda_kernel.segment_starts(seg).astype(jnp.int32), axis=1)
    out = jnp.swapaxes(kda_model.short_conv(jnp.swapaxes(x, 1, 2), w, sid), 1, 2)
    # the first token of the second segment sees only itself
    np.testing.assert_allclose(out[0, 5], x[0, 5] * w[0], rtol=1e-6)
    # and a later one its own segment's three predecessors
    want = sum(x[0, 8 - j] * w[j] for j in range(4))
    np.testing.assert_allclose(out[0, 8], want, rtol=1e-5)
    want = sum(x[0, 6 - j] * w[j] for j in range(2))
    np.testing.assert_allclose(out[0, 6], want, rtol=1e-5)


# ------------------------------------------------------------------ model
def _cfg():
    """The benchmark's Kimi-Linear configuration at its CPU test size."""
    with open(os.path.join(BENCH, "configs", "kimi-linear-48b-a3b.ep32.json")) as f:
        cfg = json.load(f)
    with open(os.path.join(BENCH, "tests", "sizes", "configs",
                           "kimi-linear-48b-a3b.ep32.json")) as f:
        over = json.load(f)["over"]
    for key, value in over.items():
        cfg[key] = dict(cfg[key], **value) if isinstance(value, dict) else value
    cfg["num_experts"] = 3
    cfg["initializer_range"] = 0.05
    cfg["optimizer"] = dict(cfg["optimizer"], lr=0.05, warmup_steps=1)
    return cfg


def _driver():
    import harness
    return harness.load_module(os.path.join(BENCH, "drivers", "train_kda.py"),
                               "driver_train_kda")


S = 128


def _rows(seed, n_rows=2):
    """Packed rows: documents across the 64-token chunk, one of one token,
    a padded tail."""
    rng = np.random.default_rng(seed)
    out = {p: np.zeros((n_rows, S), np.int32)
           for p in ("tokens", "loss_mask", "positions", "segment_ids")}
    for r in range(n_rows):
        fill = 0
        for i, n in enumerate([30, 50, 1, 40], start=1):
            out["tokens"][r, fill:fill + n] = rng.integers(1, 256, n)
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
    params = ref.init_params(cfg, decoder.seed_words(2027))
    return cfg, mcfg, params


def _leaf_gap(a, b):
    """Largest difference over the leaf's norm (float32 round-off of
    differently ordered sums: ~1e-6)."""
    a, b = np.asarray(a, np.float64), np.asarray(b, np.float64)
    return np.max(np.abs(a - b)) / max(np.linalg.norm(b), 1e-30)


def test_parameter_trees_of_program_and_reference_agree(setup):
    _, mcfg, params = setup
    from repro.models.params import abstract_params
    want = jax.tree.map(lambda d: (d.shape, str(d.dtype)),
                        abstract_params(model_mod.model_defs(mcfg)))
    have = jax.tree.map(lambda x: (x.shape, str(x.dtype)), params)
    assert want == have
    assert mcfg.layer_kinds() == ("kda", "kda", "kda", "attn", "kda")


def test_nope_latent_attention_matches_the_reference(setup):
    """The latent-attention layer (published layer 4) without rotation:
    the program's mixer against the reference's, and blind to positions."""
    cfg, mcfg, params = setup
    p = params["remainder"][2]["attn"]
    rows = _rows(4)
    h = jnp.asarray(np.random.default_rng(8).normal(size=(2, S, cfg["hidden_size"])),
                    jnp.float32)
    seg, pos = jnp.asarray(rows["segment_ids"]), jnp.asarray(rows["positions"])
    got = model_mod._mla_attention(mcfg, p, h, seg, pos)
    want = jnp.stack([ref._mla_nope(cfg, decoder._identity, h[r], p, pos[r], seg[r])
                      for r in range(2)])
    assert _gap(got, want) < 1e-5
    shifted = model_mod._mla_attention(mcfg, p, h, seg, pos + 1000)
    np.testing.assert_array_equal(np.asarray(shifted), np.asarray(got))


def test_loss_and_gradients_match_the_reference(setup):
    cfg, mcfg, params = setup
    rows = _rows(1)
    before = kda_model.KDA_PATHS["interpret"]
    (loss, metrics), grads = jax.value_and_grad(
        partial(loss_fn, mcfg, loss_chunk=32), has_aux=True)(
            params, _program_batch(rows))
    assert kda_model.KDA_PATHS["interpret"] == before + 4      # one per KDA layer
    want_loss, want_grads, want_loads = ref.Reference(cfg).grads(
        jax.tree.map(lambda x: x.astype(jnp.float32), params), rows)
    # float32 throughout: only the order of the sums differs
    assert float(loss) == pytest.approx(want_loss, rel=2e-6)
    gaps = [_leaf_gap(g, w) for g, w in zip(jax.tree.leaves(grads),
                                             jax.tree.leaves(want_grads))
            if np.linalg.norm(np.asarray(w)) > 0]
    assert max(gaps) < 2e-5, gaps
    loads = np.stack([np.asarray(x) for x in metrics["load_tree"]["remainder"]])
    np.testing.assert_array_equal(loads, want_loads)
    assert float(metrics["moe_dropped"]) == 0.0
    # one reset per segment start after a row's first token (4 a row: three
    # documents and the padding), per KDA layer
    assert float(metrics["kda_resets"]) == 2 * 4 * 4


def test_two_optimizer_steps_with_the_balancing_bias(setup):
    """Two AdamW steps with the balancing bias.  Adam's first steps move an
    element by about lr whatever the size of its gradient, so an element
    whose gradient is round-off moves either way in program and reference:
    each leaf's change is compared by its norm (as the benchmark's
    ``change_gap`` compares it), the biases exactly."""
    cfg, mcfg, params = setup
    start = jax.device_get(params)
    batches = [_rows(2), _rows(3)]
    step = jax.jit(make_train_step(mcfg, loss_chunk=32,
                                   optimizer_kw={"lr": cfg["optimizer"]["lr"],
                                                 "warmup_steps": 1}))
    from repro.training.optim import adamw_init
    p, o = params, adamw_init(params)
    losses = []
    for b in batches:
        p, o, m = step(p, o, _program_batch(b))
        losses.append(float(m["loss"]))
    want_losses, _, want = ref.Reference(cfg).train(jax.device_put(start), batches)
    assert losses == pytest.approx(want_losses, rel=2e-5)
    gaps = {}
    for (path, got), w, s0 in zip(jax.tree_util.tree_flatten_with_path(p)[0],
                                  jax.tree.leaves(want), jax.tree.leaves(start)):
        name = jax.tree_util.keystr(path)
        if name.endswith("['router_bias']"):
            # +-gamma steps from each layer's own loads: the reference's
            np.testing.assert_array_equal(np.asarray(got), np.asarray(w))
            assert np.count_nonzero(np.asarray(w)) > 0
            continue
        moved = np.linalg.norm(np.asarray(got, np.float64) - s0)
        want_moved = np.linalg.norm(np.asarray(w, np.float64) - s0)
        gaps[name] = abs(moved - want_moved) / want_moved
    assert max(gaps.values()) < 2e-3, sorted(gaps.items(), key=lambda x: -x[1])[:3]


def test_the_step_runs_each_kda_kernel_forward_twice_and_backward_once(setup):
    """Per KDA layer the train step's gradient holds the forward kernel
    twice (the forward, and the mixer's recomputation for its backward:
    the layer checkpoint keeps the mixer's output) and the backward
    kernel once."""
    cfg, mcfg, params = setup
    txt = str(jax.make_jaxpr(jax.grad(lambda p, b: loss_fn(mcfg, p, b, loss_chunk=32)[0]))(
        params, _program_batch(_rows(1))))
    layers = ref.layer_kinds(cfg).count("kda")
    assert txt.count("name=kda_fwd") == 2 * layers
    assert txt.count("name=kda_bwd") == layers
