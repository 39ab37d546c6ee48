"""Per-arch smoke tests: every registered architecture instantiates its
REDUCED config of the same blocks and runs one forward + one train step on
CPU, asserting output shapes and finiteness; the published configs are
checked by their analytic sizes."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import ARCHS, get_config, get_smoke
from repro.models.config import ModelConfig
from repro.models.model import forward, model_defs
from repro.models.params import init_params
from repro.training.optim import make_optimizer
from repro.training.steps import make_train_step

B, S = 2, 64


def make_batch(cfg, rng):
    toks = rng.integers(1, cfg.vocab_size, (B, S)).astype(np.int32)
    return {
        "tokens": jnp.asarray(toks),
        "labels": jnp.asarray(np.roll(toks, -1, axis=1)),
        "segments": jnp.ones((B, S), jnp.int32),
        "positions": jnp.broadcast_to(jnp.arange(S), (B, S)).astype(jnp.int32),
    }


@pytest.mark.parametrize("arch", ARCHS)
class TestArchSmoke:
    def test_forward_and_train_step(self, arch, rng):
        cfg = get_smoke(arch)
        params = init_params(jax.random.PRNGKey(0), model_defs(cfg))
        batch = make_batch(cfg, rng)
        h, aux = forward(cfg, params, batch)
        assert h.shape == (B, S, cfg.d_model)
        assert np.isfinite(np.asarray(h, np.float32)).all(), f"{arch}: NaN fwd"

        init_opt, _, _ = make_optimizer(cfg.optimizer)
        step = jax.jit(make_train_step(cfg, loss_chunk=32))
        p2, o2, m = step(params, init_opt(params), batch)
        assert np.isfinite(float(m["loss"])), f"{arch}: NaN loss"
        # params actually moved
        moved = jax.tree.reduce(
            lambda a, b: a or b,
            jax.tree.map(lambda x, y: bool(jnp.any(x != y)), params, p2))
        assert moved


@pytest.mark.parametrize("field,kind", [
    ("pattern", "swa"), ("pattern", "local"), ("pattern", "cross"),
    ("pattern", "ssd"), ("pattern", "rec"),
    ("mlp_kind", "geglu"), ("mlp_kind", "gelu"), ("mlp_kind", "none"),
])
def test_unknown_block_and_mlp_kinds_are_refused(field, kind):
    """The decoder has one block kind and two MLP kinds: any other is an
    error when the config is made, not deep inside tracing."""
    value = (kind,) if field == "pattern" else kind
    with pytest.raises(ValueError, match=f"unknown .* kind {kind!r}"):
        ModelConfig(**{field: value})


def test_full_configs_match_published_sizes():
    """Analytic param counts stay within 10% of the published model sizes."""
    expected = {"smollm-135m": 135e6, "moonlight-16b-a3b": 16e9}
    for arch, n in expected.items():
        got = get_config(arch).param_count()
        assert abs(got - n) / n < 0.12, f"{arch}: {got/1e9:.2f}B vs {n/1e9:.2f}B"


def test_moe_active_params():
    """Moonlight-16B-A3B: 16 B parameters, 3 B of them active per token
    (6 of 64 routed experts and the 2 shared ones)."""
    moonlight = get_config("moonlight-16b-a3b")
    assert 15.9e9 < moonlight.param_count() < 16.0e9
    assert 2.9e9 < moonlight.active_param_count() < 3.0e9
