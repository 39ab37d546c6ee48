"""The main-path kernels compile for a TPU v5e at real sizes.

Nothing runs: each kernel is lowered and compiled for a described (not
attached) ``v5e:2x2`` topology, which refuses what the chip's compiler would
refuse — tiling, fast-memory use, unsupported ops — and the compiled program
must hold the Mosaic kernel (``tpu_custom_call``), not an XLA fallback.
"""
import os

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

SEQ = 2048


@pytest.fixture(scope="module")
def topo():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache
    try:
        desc = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    # a compile for a described chip can be cached but never read back here
    enabled = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    try:
        yield desc
    finally:
        jax.config.update("jax_enable_compilation_cache", enabled)
        compilation_cache.reset_cache()


@pytest.fixture(scope="module")
def shape(topo):
    one_chip = SingleDeviceSharding(topo.devices[0])
    return lambda dims, dtype: jax.ShapeDtypeStruct(dims, dtype,
                                                    sharding=one_chip)


def compiled_text(fn, *args) -> str:
    return jax.jit(fn).lower(*args).compile().as_text()


@pytest.mark.parametrize("tokens,rows", [(1 << 20, 520), (1 << 21, 1024)])
def test_pack_tokens_compiles(shape, tokens, rows):
    from repro.kernels.pack_tokens import pack_tokens
    assert rows % 8 == 0
    text = compiled_text(lambda t, s, n: pack_tokens(t, s, n, SEQ),
                         shape((tokens,), jnp.int32),
                         shape((rows,), jnp.int32), shape((rows,), jnp.int32))
    assert "tpu_custom_call" in text


def test_gf256_matmul_compiles_rs_10_3(shape):
    from repro.kernels.gf256_matmul import gf256_matmul
    text = compiled_text(gf256_matmul, shape((3, 10), jnp.uint8),
                         shape((10, 4 << 20), jnp.uint8))
    assert "tpu_custom_call" in text


@pytest.mark.parametrize("pass_", ["forward", "grad"])
def test_flash_attention_compiles_at_the_fed_cell_shape(shape, pass_):
    """The train step's attention at the fed cell's shape: 8 rows of 2048
    tokens, SmolLM-135M's 9 query and 3 key/value heads of 64."""
    from repro.configs import get_config
    from repro.kernels.flash_attention import flash_attention
    cfg = get_config("smollm-135m")
    q = shape((8, SEQ, cfg.n_heads, cfg.head_dim), jnp.bfloat16)
    kv = shape((8, SEQ, cfg.n_kv_heads, cfg.head_dim), jnp.bfloat16)
    seg = shape((8, SEQ), jnp.int32)
    fn = flash_attention
    if pass_ == "grad":
        fn = jax.grad(lambda q, k, v, s: flash_attention(q, k, v, s).astype(
            jnp.float32).sum(), argnums=(0, 1, 2))
    assert "tpu_custom_call" in compiled_text(fn, q, kv, kv, seg)
