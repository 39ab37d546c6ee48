"""Optimizers, checkpointing (async + elastic), feeder, and sharding helpers."""
import json
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.training.checkpoint import CheckpointManager
from repro.training.optim import (adafactor_init, adafactor_update, adamw_init,
                                  adamw_update, make_optimizer, opt_state_defs,
                                  OptConfig)


# ---------------------------------------------------------------- optimizers
class TestOptimizers:
    def quad_loss(self, p):
        return sum(jnp.sum((x - 3.0) ** 2) for x in jax.tree.leaves(p))

    @pytest.mark.parametrize("name", ["adamw", "adafactor"])
    def test_converges_on_quadratic(self, name):
        params = {"w": jnp.zeros((256, 256)), "b": jnp.zeros((8,))}
        init, update, _ = make_optimizer(name, lr=0.5, weight_decay=0.0,
                                         warmup_steps=1)
        state = init(params)
        l0 = float(self.quad_loss(params))
        for _ in range(60):
            g = jax.grad(self.quad_loss)(params)
            params, state, m = update(g, state, params)
        assert float(self.quad_loss(params)) < 0.05 * l0

    def test_adafactor_state_is_factored(self):
        params = {"big": jnp.zeros((512, 256)), "small": jnp.zeros((8,))}
        state = adafactor_init(params, min_dim=128)
        assert set(state["v"]["big"]) == {"vr", "vc"}
        assert state["v"]["big"]["vr"].shape == (512,)
        assert set(state["v"]["small"]) == {"v"}

    def test_opt_state_defs_match_runtime_state(self):
        """ShapeDtypeStructs from opt_state_defs == actual optimizer state
        (so the trainer's state shardings fit the real state)."""
        from repro.models.params import ParamDef, abstract_params, init_params
        pdefs = {"w": ParamDef((256, 192), ("embed", "ffn"), jnp.float32),
                 "s": ParamDef((16,), (None,), jnp.float32)}
        params = init_params(jax.random.PRNGKey(0), pdefs)
        for name in ("adamw", "adafactor"):
            odefs = opt_state_defs(name, pdefs)
            abstract = abstract_params(odefs)
            init, _, _ = make_optimizer(name)
            real = init(params)
            ab_tree = jax.tree.map(lambda x: (x.shape, str(x.dtype)), abstract)
            re_tree = jax.tree.map(lambda x: (x.shape, str(x.dtype)), real)
            assert ab_tree == re_tree, name

    def test_grad_clipping(self):
        params = {"w": jnp.zeros((4,))}
        g = {"w": jnp.full((4,), 100.0)}
        init, update, _ = make_optimizer("adamw", grad_clip=1.0)
        _, _, m = update(g, init(params), params)
        assert float(m["grad_norm"]) > 1.0  # reports pre-clip norm


# -------------------------------------------------------------- checkpointing
class TestCheckpoint:
    def tree(self, seed=0):
        k = jax.random.PRNGKey(seed)
        return {"params": {"w": jax.random.normal(k, (32, 16)),
                           "stack": jax.random.normal(k, (4, 8, 8))},
                "opt": {"mu": jnp.zeros((32, 16)), "step": jnp.asarray(7)}}

    def test_roundtrip(self, tmp_path):
        mgr = CheckpointManager(str(tmp_path), async_write=False)
        t = self.tree()
        mgr.save(10, t)
        out = mgr.restore(10, t)
        jax.tree.map(lambda a, b: np.testing.assert_array_equal(
            np.asarray(a), np.asarray(b)), t, out)

    def test_async_write_and_retention(self, tmp_path):
        mgr = CheckpointManager(str(tmp_path), keep=2, async_write=True)
        for s in (1, 2, 3, 4):
            mgr.save(s, self.tree(s))
        mgr.wait()
        assert mgr.all_steps() == [3, 4]  # retention gc

    def test_elastic_restore_across_meshes(self, tmp_path):
        """A checkpoint written with one sharding restores onto another mesh
        (here: 1-device mesh with different PartitionSpecs) — the elastic
        scaling path."""
        from jax.sharding import NamedSharding, PartitionSpec as P
        from repro.training.checkpoint import place_on_mesh
        mgr = CheckpointManager(str(tmp_path), async_write=False)
        t = self.tree()
        mgr.save(5, t)
        mesh = jax.make_mesh((1, 1), ("data", "model"))
        specs = jax.tree.map(lambda _: P(), t)
        out = mgr.restore(5, t, place=place_on_mesh(mesh, specs))
        jax.tree.map(lambda a, b: np.testing.assert_array_equal(
            np.asarray(a), np.asarray(b)), t, out)
        leaf = out["params"]["w"]
        assert isinstance(leaf.sharding, NamedSharding)

    def test_interrupted_write_not_published(self, tmp_path):
        """A .tmp dir (simulated mid-write crash) is never listed as a step."""
        mgr = CheckpointManager(str(tmp_path), async_write=False)
        mgr.save(1, self.tree())
        os.makedirs(str(tmp_path / "step_000000002.tmp"))
        assert mgr.all_steps() == [1]
        assert mgr.latest_step() == 1


# -------------------------------------------------------------------- feeder
class TestFeeder:
    def _ingest(self, tmp_path, n_docs=300, seq_len=128):
        from repro.core import DataStore
        from repro.data.feeder import ingest_corpus
        from repro.data.generators import gen_token_documents
        ds = DataStore(str(tmp_path / "c"), nodes=["n0", "n1"])
        docs = gen_token_documents(n_docs, vocab=1000, max_len=seq_len)
        ingest_corpus(docs, ds, seq_len=seq_len, rows_per_block=8)
        return ds

    def test_batches_have_model_shape(self, tmp_path):
        from repro.data.feeder import BlockFeeder
        ds = self._ingest(tmp_path)
        f = BlockFeeder(ds, batch_rows=4)
        b = next(iter(f.batches(1)))
        assert b["tokens"].shape == (4, 128)
        assert set(b) == {"tokens", "loss_mask", "positions", "segment_ids"}

    def test_resumable_position(self, tmp_path):
        from repro.data.feeder import BlockFeeder
        ds = self._ingest(tmp_path)
        f1 = BlockFeeder(ds, batch_rows=4, seed=1)
        first = [b["tokens"].sum() for b in f1.batches(4)]
        # resume from step 2: same stream suffix
        f2 = BlockFeeder(ds, batch_rows=4, seed=1, start_step=f1.step)
        nxt = next(iter(f2.batches(1)))
        f3 = BlockFeeder(ds, batch_rows=4, seed=1)
        replay = [b["tokens"].sum() for b in f3.batches(5)]
        assert replay[:4] == first

    def test_resume_equivalence_at_every_step(self, tmp_path):
        """Stop/restart at EVERY step yields the exact reference stream.

        batch_rows=3 never divides the 8-row blocks, so every batch leaves
        carry rows; before the (step, offset) cursor those rows were dropped
        or replayed on restart (bugfix, ISSUE 6)."""
        from repro.data.feeder import BlockFeeder
        ds = self._ingest(tmp_path)
        n = 12
        ref = list(BlockFeeder(ds, batch_rows=3, seed=7).batches(n))
        assert len(ref) == n
        for stop in range(n):
            f1 = BlockFeeder(ds, batch_rows=3, seed=7)
            head = list(f1.batches(stop))
            f2 = BlockFeeder(ds, batch_rows=3, seed=7,
                             start_step=f1.step, start_offset=f1.offset)
            stream = head + list(f2.batches(n - stop))
            assert len(stream) == n, stop
            for want, got in zip(ref, stream):
                for field in want:
                    np.testing.assert_array_equal(want[field], got[field])

    def test_work_stealing_queue_yields_all(self, tmp_path):
        from repro.data.feeder import BlockFeeder
        ds = self._ingest(tmp_path)
        feeders = [BlockFeeder(ds, num_tasks=2, task=t, batch_rows=4)
                   for t in range(2)]
        q = BlockFeeder.stealing_queue(feeders, num_steps=6)
        got = [q.get(timeout=10) for _ in range(6)]
        assert len(got) == 6
        for t in q.workers:
            t.join(timeout=5)
        assert not any(t.is_alive() for t in q.workers)
        assert q.delivered() == 6

    def test_work_stealing_queue_consumer_abandons(self, tmp_path):
        """A consumer that walks away mid-stream must not strand the workers.

        Before the fix the done event was never set and workers blocked
        forever on q.put() into the full queue (bugfix, ISSUE 6)."""
        from repro.data.feeder import BlockFeeder
        ds = self._ingest(tmp_path)
        feeders = [BlockFeeder(ds, num_tasks=2, task=t, batch_rows=4)
                   for t in range(2)]
        q = BlockFeeder.stealing_queue(feeders, num_steps=50)
        for _ in range(3):
            q.get(timeout=10)
        q.stop()   # the consumer abandons the stream
        for t in q.workers:
            t.join(timeout=5)
        assert not any(t.is_alive() for t in q.workers)
        # delivered counts only batches actually placed: at most the 3 we
        # consumed + the queue capacity (8) + one in-flight put per worker
        assert q.delivered() <= 3 + 8 + len(feeders)


# ------------------------------------------------------- sharding helpers
class TestDryrunHelpers:
    def test_sharding_rules_divisibility(self):
        """9 heads never shard 16 ways; vocab multiples of 256 do."""
        from repro.models.params import logical_to_spec
        rules = {"heads": "model", "vocab": "model", "embed": "data"}
        sizes = {"data": 16, "model": 16}
        spec = logical_to_spec(("vocab", "embed"), rules, (49152, 576), sizes)
        assert spec == jax.sharding.PartitionSpec("model", "data")
        spec = logical_to_spec(("embed", "heads", None), rules, (576, 9, 64), sizes)
        assert spec == jax.sharding.PartitionSpec("data",)

    def test_sharding_rules_skip_axes_the_mesh_lacks(self):
        """On a data-only mesh a rule naming "model" leaves the dim whole."""
        from repro.models.params import logical_to_spec
        rules = {"vocab": "model", "embed": "data"}
        spec = logical_to_spec(("vocab", "embed"), rules, (49152, 576),
                               {"data": 4})
        assert spec == jax.sharding.PartitionSpec(None, "data")


class TestChipPeaks:
    def test_v5e_peaks_by_device_kind(self):
        from repro.launch.mesh import chip_peaks
        p = chip_peaks("TPU v5 lite")
        assert p.flops_bf16 == 197e12 and p.hbm_bw == 819e9

    def test_unknown_kind_is_an_error(self):
        from repro.launch.mesh import chip_peaks
        with pytest.raises(KeyError, match="no published peaks"):
            chip_peaks("cpu")
