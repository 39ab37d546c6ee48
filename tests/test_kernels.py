"""Per-kernel shape/dtype sweeps vs the ref.py oracles (interpret mode)."""
import importlib

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.kernels import ref
from repro.kernels.ops import bucket, gf256_matmul, pack_tokens

# the module (the package exports its entry point under the same name)
flash = importlib.import_module("repro.kernels.flash_attention")


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


def _rows(layout: str, B: int = 2, S: int = 256):
    """Segment ids and positions of packed rows.  ``packed``: pieces that
    cross the 128-token blocks (one of a single token), then padding;
    ``single``: one piece filling the row."""
    seg = np.zeros((B, S), np.int32)
    pos = np.zeros((B, S), np.int32)
    pieces = {"packed": [[100, 60, 40, 20], [1, 130, 70]],
              "single": [[S]] * B}[layout]
    for b, lens in enumerate(pieces):
        cur = 0
        for sid, ln in enumerate(lens, start=1):
            seg[b, cur:cur + ln] = sid
            pos[b, cur:cur + ln] = np.arange(ln)
            cur += ln
    return jnp.asarray(seg), jnp.asarray(pos)


class TestFlashAttention:
    """The train step's kernel (interpret mode) against the model's own
    attention, ``attention_naive``, at real positions: GQA 9/3, head_dim 64,
    S 256 in blocks of 128, rows of packed segments."""

    B, S, H, KV, D = 2, 256, 9, 3, 64
    TOL = {jnp.float32: 1e-5, jnp.bfloat16: 1.5e-2}

    @pytest.fixture(autouse=True)
    def blocks_of_128(self, monkeypatch):
        monkeypatch.setattr(flash, "BLOCK", 128)

    def inputs(self, dtype, rng):
        q = jnp.asarray(rng.normal(size=(self.B, self.S, self.H, self.D)), dtype)
        k = jnp.asarray(rng.normal(size=(self.B, self.S, self.KV, self.D)), dtype)
        v = jnp.asarray(rng.normal(size=(self.B, self.S, self.KV, self.D)), dtype)
        return q, k, v

    @staticmethod
    def kernel(q, k, v, seg):
        return flash.flash_attention(q, k, v, seg, interpret=True)

    @staticmethod
    def model(q, k, v, seg, pos):
        from repro.models.attention import attention_naive
        f32 = lambda x: x.astype(jnp.float32)
        return attention_naive(f32(q), f32(k), f32(v), pos, pos, seg, seg)

    @pytest.mark.parametrize("layout", ["packed", "single"])
    @pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
    def test_forward_matches_model_at_real_positions(self, layout, dtype, rng):
        seg, pos = _rows(layout, self.B, self.S)
        q, k, v = self.inputs(dtype, rng)
        out = np.asarray(self.kernel(q, k, v, seg), np.float32)
        want = np.asarray(self.model(q, k, v, seg, pos))
        real = np.asarray(seg > 0)
        np.testing.assert_allclose(out[real], want[real],
                                   atol=self.TOL[dtype], rtol=self.TOL[dtype])

    @pytest.mark.parametrize("layout", ["packed", "single"])
    @pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
    def test_grads_match_model(self, layout, dtype, rng):
        """d/dq, d/dk, d/dv of a loss weighted by ``seg > 0``."""
        seg, pos = _rows(layout, self.B, self.S)
        q, k, v = self.inputs(dtype, rng)
        w = (jnp.asarray(rng.normal(size=q.shape), jnp.float32)
             * (seg > 0)[:, :, None, None])

        def grads(attend):
            loss = lambda q, k, v: (attend(q, k, v).astype(jnp.float32)
                                    * w).sum()
            return jax.grad(loss, argnums=(0, 1, 2))(q, k, v)

        got = grads(lambda q, k, v: self.kernel(q, k, v, seg))
        want = grads(lambda q, k, v: self.model(q, k, v, seg, pos))
        for name, g, e in zip("qkv", got, want):
            g, e = np.asarray(g, np.float32), np.asarray(e, np.float32)
            err = np.linalg.norm(g - e) / np.linalg.norm(e)
            assert err < self.TOL[dtype], (name, err)

    def test_never_attends_across_segments(self, rng):
        """Changing one segment's keys and values leaves every other
        segment's outputs bit for bit as they were."""
        seg, _ = _rows("packed", self.B, self.S)
        q, k, v = self.inputs(jnp.float32, rng)
        inside = (seg == 2)[:, :, None, None]
        out = np.asarray(self.kernel(q, k, v, seg))
        moved = np.asarray(self.kernel(q, jnp.where(inside, -k, k),
                                       jnp.where(inside, 3 * v, v), seg))
        others = np.asarray(seg != 2)
        assert np.array_equal(out[others], moved[others])
        assert not np.array_equal(out[~others], moved[~others])


class TestAttentionDispatch:
    """``models.model._attention_path``: the kernel on a TPU only; the jnp
    paths on the CPU and on a mesh."""

    @staticmethod
    def cfg(**kw):
        from repro.configs import get_config
        return get_config("smollm-135m").replace(**kw)

    # heads: the (q/k, v) head sizes of the call, () for the config's own
    @pytest.mark.parametrize("backend,devices,kw,heads,S,path", [
        pytest.param("tpu", 1, {}, (), 2048, "kernel",
                     id="tpu-1-kw0-None-2048-kernel"),
        pytest.param("tpu", 1, {}, (), 1024, "kernel",
                     id="tpu-1-kw1-None-1024-kernel"),
        pytest.param("cpu", 1, {}, (), 2048, "chunked",
                     id="cpu-1-kw2-None-2048-chunked"),
        pytest.param("tpu", 4, {}, (), 2048, "chunked",
                     id="tpu-4-kw3-None-2048-chunked"),
        pytest.param("tpu", 1, {}, (), 2000, "chunked",
                     id="tpu-1-kw6-None-2000-chunked"),
        pytest.param("tpu", 1, {"head_dim": 128}, (), 2048, "chunked",
                     id="tpu-1-kw8-None-2048-chunked"),
        pytest.param("cpu", 1, {}, (), 1024, "naive", id="cpu-1-1024-naive"),
        pytest.param("tpu", 1, {}, (192, 128), 8192, "kernel",
                     id="tpu-1-mla-8192-kernel"),
    ])
    def test_path(self, monkeypatch, backend, devices, kw, heads, S, path):
        from repro.models import model
        monkeypatch.setattr(model.jax, "default_backend", lambda: backend)
        monkeypatch.setattr(model.jax, "device_count", lambda: devices)
        assert model._attention_path(self.cfg(**kw), S, *heads) == path

    @staticmethod
    def run_forward(cfg, seg, pos):
        from repro.models.model import forward, model_defs
        from repro.models.params import init_params
        params = init_params(jax.random.PRNGKey(0), model_defs(cfg))
        toks = (jnp.arange(seg.size).reshape(seg.shape) % 97 + 1) * (seg > 0)
        return forward(cfg, params, {"tokens": toks.astype(jnp.int32),
                                     "segments": seg, "positions": pos})[0]

    def test_cpu_model_runs_the_jnp_paths(self):
        from repro.models.model import ATTENTION_PATHS
        seg, pos = _rows("packed")
        before = ATTENTION_PATHS.copy()
        for chunk in (64, 1024):   # S above, then at most, the chunk
            cfg = self.cfg(num_layers=2, d_model=64, d_ff=128, vocab_size=128,
                           attn_chunk=chunk, dtype="float32",
                           param_dtype="float32")
            self.run_forward(cfg, seg, pos)
        assert ATTENTION_PATHS - before == {"chunked": 1, "naive": 1}

    def test_tpu_dispatch_runs_the_kernel_like_the_jnp_path(self, monkeypatch):
        """With the backend seen as a TPU, a model of the fed cell's heads
        lowers its attention to the kernel (interpreted here), counted once
        per trace of the scanned layer; its hidden states at real positions
        match the CPU's jnp path."""
        from functools import partial

        from repro.models import model
        seg, pos = _rows("packed")
        cfg = self.cfg(num_layers=2, d_model=64, d_ff=128, vocab_size=128,
                       attn_chunk=64, dtype="float32", param_dtype="float32")
        want = self.run_forward(cfg, seg, pos)
        monkeypatch.setattr(flash, "BLOCK", 128)
        monkeypatch.setattr(model.kernel_ops, "flash_attention",
                            partial(flash.flash_attention, interpret=True))
        monkeypatch.setattr(model.jax, "default_backend", lambda: "tpu")
        monkeypatch.setattr(model.jax, "device_count", lambda: 1)
        before = model.ATTENTION_PATHS.copy()
        got = self.run_forward(cfg, seg, pos)
        assert model.ATTENTION_PATHS - before == {"kernel": 1}
        real = np.asarray(seg > 0)
        np.testing.assert_allclose(np.asarray(got)[real],
                                   np.asarray(want)[real], atol=1e-4, rtol=1e-4)


def _pallas_calls(jaxpr, name: str) -> int:
    """The ``pallas_call`` equations named ``name`` in ``jaxpr`` and in
    every jaxpr nested in its equations."""
    n = 0
    for eqn in jaxpr.eqns:
        n += eqn.primitive.name == "pallas_call" and eqn.params["name"] == name
        for v in eqn.params.values():
            for sub in v if isinstance(v, (tuple, list)) else (v,):
                if isinstance(sub, jax.extend.core.ClosedJaxpr):
                    sub = sub.jaxpr
                if isinstance(sub, jax.extend.core.Jaxpr):
                    n += _pallas_calls(sub, name)
    return n


class TestAttentionResiduals:
    """The layer checkpoint keeps the kernel's output and log-sum-exp
    (``flash.RESIDUALS``) under every policy but ``nothing``: the
    backward then runs no forward kernel of its own, and its gradients are
    those of the recomputation, bit for bit."""

    B, S, H = 2, 256, 4
    FWD = "splash_mha_fwd_segmented_residuals"

    @pytest.fixture(autouse=True)
    def blocks_of_128(self, monkeypatch):
        monkeypatch.setattr(flash, "BLOCK", 128)

    @staticmethod
    def cfg(policy):
        from repro.configs import get_config
        return get_config("smollm-135m").replace(remat_policy=policy)

    def grad(self, policy, q, k, v, seg):
        """d/dq, d/dk, d/dv of a loss weighted by ``seg > 0``, with the
        kernel under a layer checkpoint of ``policy``."""
        from repro.models.model import _remat_policy
        w = jnp.asarray(np.random.default_rng(1).normal(size=q.shape[:3]
                                                        + v.shape[3:]),
                        jnp.float32) * (seg > 0)[:, :, None, None]
        layer = jax.checkpoint(
            lambda q, k, v: (flash.flash_attention(q, k, v, seg,
                                                   interpret=True)
                             .astype(jnp.float32) * w).sum(),
            policy=_remat_policy(self.cfg(policy)), prevent_cse=False)
        return jax.grad(layer, argnums=(0, 1, 2))

    def inputs(self, dh, dv, kv, rng):
        """Two segments a row; q in float32 for latent attention, as the
        model passes it, else bf16."""
        seg = jnp.asarray(np.repeat([[1, 2]], self.B, 0).repeat(self.S // 2, 1),
                          jnp.int32)
        q = jnp.asarray(rng.normal(size=(self.B, self.S, self.H, dh)),
                        jnp.float32 if dh != dv else jnp.bfloat16)
        k = jnp.asarray(rng.normal(size=(self.B, self.S, kv, dh)), jnp.bfloat16)
        v = jnp.asarray(rng.normal(size=(self.B, self.S, kv, dv)), jnp.bfloat16)
        return q, k, v, seg

    def forward_kernels(self, policy, q, k, v, seg) -> int:
        jaxpr = jax.make_jaxpr(self.grad(policy, q, k, v, seg))(q, k, v)
        return _pallas_calls(jaxpr.jaxpr, self.FWD)

    @pytest.mark.parametrize("dh,dv,kv", [(64, 64, 2), (192, 128, 4)])
    def test_backward_runs_no_forward_kernel(self, dh, dv, kv, rng):
        """GQA at head 64, and latent attention's qk 192 / v 128."""
        q, k, v, seg = self.inputs(dh, dv, kv, rng)
        assert self.forward_kernels("save_layer_inputs", q, k, v, seg) == 1
        assert self.forward_kernels("nothing", q, k, v, seg) == 2
        saved = self.grad("save_layer_inputs", q, k, v, seg)(q, k, v)
        again = self.grad("nothing", q, k, v, seg)(q, k, v)
        for name, g, e in zip("qkv", saved, again):
            assert g.dtype == e.dtype, name
            assert np.array_equal(np.asarray(g), np.asarray(e)), name

    @pytest.mark.parametrize("policy", ["dots", "dots_no_batch"])
    def test_dot_policies_keep_the_residuals(self, policy, rng):
        q, k, v, seg = self.inputs(64, 64, 2, rng)
        assert self.forward_kernels(policy, q, k, v, seg) == 1

    def test_nothing_saves_nothing(self):
        from repro.models.model import _remat_policy
        assert (_remat_policy(self.cfg("nothing"))
                is jax.checkpoint_policies.nothing_saveable)

    @pytest.mark.parametrize("policy,backend,counted", [
        ("save_layer_inputs", "tpu", {"saved": 1}),
        ("nothing", "tpu", {"recomputed": 1}),
        ("save_layer_inputs", "cpu", {}),
    ])
    def test_counter(self, monkeypatch, policy, backend, counted):
        """``ATTENTION_RESIDUALS`` counts each kernel-path call traced (the
        scanned layer once) by the policy in force; the chunked path, none."""
        from repro.models import model
        from repro.models.params import init_params
        monkeypatch.setattr(model.jax, "default_backend", lambda: backend)
        monkeypatch.setattr(model.jax, "device_count", lambda: 1)
        cfg = self.cfg(policy).replace(num_layers=2, d_model=64, d_ff=128,
                                       vocab_size=128, attn_chunk=64)
        seg, pos = _rows("packed")
        params = init_params(jax.random.PRNGKey(0), model.model_defs(cfg))
        batch = {"tokens": jnp.ones(seg.shape, jnp.int32), "segments": seg,
                 "positions": pos}
        paths = model.ATTENTION_PATHS.copy()
        before = model.ATTENTION_RESIDUALS.copy()
        jax.eval_shape(lambda p: model.forward(cfg, p, batch)[0], params)
        assert model.ATTENTION_RESIDUALS - before == counted
        assert sum((model.ATTENTION_PATHS - paths).values()) == 1


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
