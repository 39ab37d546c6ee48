"""Per-kernel shape/dtype sweeps vs the ref.py oracles (interpret mode)."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.kernels import ref
from repro.kernels.ops import bucket, flash_attention, gf256_matmul, pack_tokens


class TestGF256Matmul:
    @pytest.mark.parametrize("P,K,N", [(1, 2, 256), (3, 10, 5000),
                                       (4, 8, 2048), (2, 5, 131)])
    def test_matches_table_oracle(self, P, K, N, rng):
        code = rng.integers(0, 256, (P, K)).astype(np.uint8)
        data = rng.integers(0, 256, (K, N)).astype(np.uint8)
        out = np.asarray(gf256_matmul(jnp.asarray(code), jnp.asarray(data),
                                      block_n=1024))
        assert np.array_equal(out, ref.gf256_matmul_ref(code, data))

    def test_identity_code_matrix(self, rng):
        K, N = 4, 512
        code = np.eye(K, dtype=np.uint8)
        data = rng.integers(0, 256, (K, N)).astype(np.uint8)
        out = np.asarray(gf256_matmul(jnp.asarray(code), jnp.asarray(data)))
        assert np.array_equal(out, data)


class TestFlashAttention:
    @pytest.mark.parametrize("B,S,H,KV,d", [
        (1, 128, 2, 2, 64),    # MHA
        (2, 256, 4, 2, 64),    # GQA 2:1
        (1, 512, 8, 1, 128),   # MQA
    ])
    @pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
    def test_matches_dense_oracle(self, B, S, H, KV, d, dtype, rng):
        q = jnp.asarray(rng.normal(size=(B, S, H, d)), dtype)
        k = jnp.asarray(rng.normal(size=(B, S, KV, d)), dtype)
        v = jnp.asarray(rng.normal(size=(B, S, KV, d)), dtype)
        out = flash_attention(q, k, v, bq=128, bk=64)
        exp = ref.flash_attention_ref(q, k, v)
        tol = 2e-2 if dtype == jnp.bfloat16 else 2e-5
        np.testing.assert_allclose(np.asarray(out, np.float32),
                                   np.asarray(exp, np.float32), atol=tol)

    def test_non_causal(self, rng):
        q = jnp.asarray(rng.normal(size=(1, 128, 2, 32)), jnp.float32)
        k = jnp.asarray(rng.normal(size=(1, 128, 2, 32)), jnp.float32)
        v = jnp.asarray(rng.normal(size=(1, 128, 2, 32)), jnp.float32)
        out = flash_attention(q, k, v, causal=False, bq=64, bk=64)
        exp = ref.flash_attention_ref(q, k, v, causal=False)
        np.testing.assert_allclose(np.asarray(out), np.asarray(exp), atol=2e-5)

    def test_matches_model_attention(self, rng):
        """The kernel is a drop-in for models/attention.attention_chunked."""
        from repro.models.attention import attention_chunked
        B, S, H, d = 1, 256, 4, 64
        q = jnp.asarray(rng.normal(size=(B, S, H, d)), jnp.float32)
        k = jnp.asarray(rng.normal(size=(B, S, H, d)), jnp.float32)
        v = jnp.asarray(rng.normal(size=(B, S, H, d)), jnp.float32)
        pos = jnp.broadcast_to(jnp.arange(S), (B, S)).astype(jnp.int32)
        seg = jnp.ones((B, S), jnp.int32)
        out_model = attention_chunked(q, k, v, pos, pos, seg, seg, chunk=64)
        out_kernel = flash_attention(q, k, v, bq=64, bk=64)
        np.testing.assert_allclose(np.asarray(out_model), np.asarray(out_kernel),
                                   atol=3e-5)


class TestPackTokens:
    @pytest.mark.parametrize("seq_len", [64, 128, 1024])
    def test_matches_oracle(self, seq_len, rng):
        T = 4000
        flat = rng.integers(1, 1000, T).astype(np.int32)
        starts, lens, cur = [], [], 0
        while cur < T - seq_len:
            ln = int(rng.integers(1, seq_len + 1))
            starts.append(cur)
            lens.append(ln)
            cur += ln
        starts, lens = np.array(starts, np.int32), np.array(lens, np.int32)
        t, s, p = pack_tokens(jnp.asarray(flat), jnp.asarray(starts),
                              jnp.asarray(lens), seq_len)
        te, se, pe = ref.pack_tokens_ref(flat, starts, lens, seq_len)
        assert np.array_equal(np.asarray(t), te)
        assert np.array_equal(np.asarray(s), se)
        assert np.array_equal(np.asarray(p), pe)


class TestBucket:
    def test_pads_at_most_a_quarter_and_keeps_few_shapes(self):
        sizes = [bucket(n) for n in range(1, 1 << 16)]
        assert all(b >= max(n, 128) for n, b in zip(range(1, 1 << 16), sizes))
        assert all(b <= 1.25 * max(n, 128)
                   for n, b in zip(range(1, 1 << 16), sizes))
        assert sizes == sorted(sizes)
        assert len(set(sizes)) <= 4 * 9 + 1   # four per octave above 128
        assert bucket(1 << 20) == 1 << 20 and bucket((1 << 20) + 1) == 1310720


class TestProgramNames:
    """The device trace names each kernel's program by its jitted wrapper;
    kernel time is read from the trace under these names."""

    def test_ingest_kernels_lower_to_their_named_modules(self):
        spec = jax.ShapeDtypeStruct
        pack = pack_tokens.lower(spec((1024,), jnp.int32),
                                 spec((8,), jnp.int32), spec((8,), jnp.int32),
                                 128)
        gf = gf256_matmul.lower(spec((3, 4), jnp.uint8),
                                spec((4, 1024), jnp.uint8))
        assert "module @jit_pack_tokens" in pack.as_text()
        assert "module @jit_gf256_matmul" in gf.as_text()
