"""Multiprocess node backend (ISSUE 3): the shared-memory item codec, plan
shipping over the pickle seam, coordinator-routed commits, worker-death
mapping onto epoch replay, and thread/process output equivalence.

The streaming classes here are the acceptance subset: shuffle, epoch commit
ordering, and node-death replay all running with ``backend="process"``.
"""
import pickle
import threading
import time

import numpy as np
import pytest

from repro.core import (DataAccess, DataStore, FaultInjection, IngestPlan,
                        RuntimeEngine, StreamFaultInjection,
                        StreamingRuntimeEngine, chain_stage, create_stage,
                        decode_items, encode_items, format_, resolve_op,
                        select, serialize_plans)
from repro.core import store as store_stmt
from repro.core.items import Granularity, IngestItem
from repro.core.ops_select import FilterOp, MapOp
from repro.data.generators import gen_lineitem


def columnar_plan(ds, *, name="proc"):
    p = IngestPlan(name)
    s1 = select(p)
    s2 = format_(p, s1, chunk={"target_rows": 256}, serialize="columnar")
    s3 = store_stmt(p, s2, locate="roundrobin",
                    locate_args={"num_locations": len(ds.nodes)}, upload=ds)
    create_stage(p, using=[s1, s2, s3], name="main")
    return p


def shuffled_plan(ds):
    """Ingest segment (parse + partition + shuffle, chunk + serialize) and
    store segment (upload) — every op picklable for the process seam."""
    p = IngestPlan("shuf")
    s1 = p.add_statement([
        resolve_op("identity_parser"),
        resolve_op("partition", scheme="hash", key="orderkey", num_partitions=4),
        resolve_op("map", fn="repro.core.ops_select:identity_columns",
                   shuffle_by="partition"),
    ], kind="select")
    s2 = p.add_statement([
        resolve_op("chunk", target_rows=256),
        resolve_op("serialize", layout="columnar"),
    ], kind="format", inputs=[s1])
    s3 = p.add_statement([resolve_op("upload", store=ds)],
                         kind="store", inputs=[s2])
    create_stage(p, using=[s1], name="a")
    chain_stage(p, to=["a"], using=[s2], name="b")
    chain_stage(p, to=["b"], using=[s3], name="c")
    return p


def shard_source(n_shards, rows=100, delay_s=0.0):
    for i in range(n_shards):
        if delay_s:
            time.sleep(delay_s)
        yield IngestItem(gen_lineitem(rows, seed=i))


# ---------------------------------------------------------------------------
class TestShmCodec:
    def test_large_batch_rides_shared_memory_zero_copy(self):
        items = [IngestItem({"x": np.arange(20000, dtype=np.int64),
                             "y": np.ones(20000, dtype=np.float32)}
                            ).with_label("parser", i) for i in range(3)]
        payload, lease = encode_items(items, shm_min_bytes=1024)
        assert payload["kind"] == "shm"
        lease.detach()
        out, rlease = decode_items(payload)
        assert rlease is not None
        assert all(np.array_equal(a.data["x"], b.data["x"])
                   and np.array_equal(a.data["y"], b.data["y"])
                   and a.labels == b.labels for a, b in zip(items, out))
        # receive side is zero-copy: arrays view the mapped segment
        assert out[0].data["x"].base is not None
        del out
        rlease.release()

    def test_small_batch_inlines_as_pickle(self):
        items = [IngestItem({"x": np.arange(4)})]
        payload, lease = encode_items(items)
        assert payload["kind"] == "pickle" and lease is None
        out, rlease = decode_items(payload)
        assert rlease is None
        np.testing.assert_array_equal(out[0].data["x"], np.arange(4))

    def test_copy_mode_destroys_segment(self):
        from multiprocessing import shared_memory
        items = [IngestItem({"x": np.arange(50000, dtype=np.int64)})]
        payload, lease = encode_items(items, shm_min_bytes=1024)
        lease.detach()
        out, rlease = decode_items(payload, copy=True)
        assert rlease is None
        np.testing.assert_array_equal(out[0].data["x"], np.arange(50000))
        with pytest.raises(FileNotFoundError):   # consumed exactly once
            shared_memory.SharedMemory(name=payload["shm"])

    def test_non_array_payloads_roundtrip(self):
        items = [IngestItem(b"raw file bytes" * 10000),
                 IngestItem({"x": np.arange(30000, dtype=np.int64)})]
        payload, lease = encode_items(items, shm_min_bytes=1024)
        if lease is not None:
            lease.detach()
        out, rlease = decode_items(payload, copy=True)
        assert out[0].data == items[0].data
        np.testing.assert_array_equal(out[1].data["x"], items[1].data["x"])
        assert rlease is None


# ---------------------------------------------------------------------------
class TestPlanShipping:
    def test_ops_pickle_by_spec(self):
        op = FilterOp(predicate=("quantity", ">", 10))
        clone = pickle.loads(pickle.dumps(op))
        cols = {"quantity": np.array([5, 20, 30], dtype=np.int32)}
        out = clone.run([IngestItem(cols, Granularity.CHUNK)])
        assert out[0].nrows() == 2
        m = pickle.loads(pickle.dumps(
            MapOp(fn="repro.core.ops_select:identity_columns")))
        assert m.run([IngestItem(cols, Granularity.CHUNK)])[0].data is not None

    def test_closure_param_raises_named_error(self, store):
        p = IngestPlan("bad")
        p.add_statement([resolve_op("identity_parser"),
                         resolve_op("map", fn=lambda c: c)], kind="select")
        create_stage(p, using=["s1"], name="main")
        with pytest.raises(TypeError, match=r"stage 'main' op \[1\].*MapOp"):
            serialize_plans(p.compile())

    def test_process_backend_rejects_foreign_store(self, store, tmp_path):
        other = DataStore(str(tmp_path / "other"), nodes=store.nodes)
        p = columnar_plan(other)
        eng = StreamingRuntimeEngine(store, epoch_items=4, backend="process")
        try:
            with pytest.raises(ValueError, match="engine's store"):
                eng.run_stream(p, shard_source(4))
        finally:
            eng.close()

    def test_process_backend_refuses_device_kernel_op(self, store):
        """Kernel ops run in the process that owns the chip: shipping one
        to a forked worker is refused, naming the op."""
        p = IngestPlan("kern")
        s1 = select(p)
        s2 = format_(p, s1, chunk={"target_rows": 256}, serialize="columnar",
                     erasure={"k": 2, "m": 1, "use_pallas": True})
        s3 = store_stmt(p, s2, upload=store)
        create_stage(p, using=[s1, s2, s3], name="main")
        eng = StreamingRuntimeEngine(store, epoch_items=4, backend="process")
        try:
            with pytest.raises(ValueError,
                               match=r"stage 'main': op 'erasure'.*device "
                                     r"kernel"):
                eng.run_stream(p, shard_source(4))
        finally:
            eng.close()
        assert store.committed_epoch_ids() == []


# ---------------------------------------------------------------------------
class TestProcessStreaming:
    def test_matches_thread_backend_output(self, tmp_path):
        rows = {}
        for backend in ("thread", "process"):
            ds = DataStore(str(tmp_path / backend), nodes=["n0", "n1", "n2", "n3"])
            eng = StreamingRuntimeEngine(ds, epoch_items=4, queue_capacity=8,
                                         backend=backend)
            rep = eng.run_stream(shuffled_plan(ds), shard_source(12, rows=100))
            assert rep.committed_epoch_ids() == [0, 1, 2]
            cols = DataAccess(ds).since_epoch(-1).read_all(projection=["quantity"])
            rows[backend] = np.sort(cols["quantity"])
            eng.close()
        np.testing.assert_array_equal(rows["thread"], rows["process"])

    def test_shuffle_exact_once(self, store):
        eng = StreamingRuntimeEngine(store, epoch_items=4, queue_capacity=8,
                                     backend="process")
        rep = eng.run_stream(shuffled_plan(store), shard_source(8, rows=100))
        assert sum(e.run.shuffled_items for e in rep.epochs) > 0
        cols = DataAccess(store).since_epoch(-1).read_all(projection=["quantity"])
        assert len(cols["quantity"]) == 8 * 100
        eng.close()

    def test_commit_ordering_under_concurrent_reader(self, store):
        """Epoch commit ordering: a reader polling mid-stream only ever sees
        gap-free committed prefixes while process workers ingest."""
        stop = threading.Event()
        bad: list = []

        def poll():
            while not stop.is_set():
                ids = store.committed_epoch_ids()
                if ids != list(range(len(ids))):
                    bad.append(ids)
                time.sleep(0.002)

        reader = threading.Thread(target=poll, daemon=True)
        reader.start()
        eng = StreamingRuntimeEngine(store, epoch_items=4, queue_capacity=8,
                                     backend="process")
        rep = eng.run_stream(shuffled_plan(store), shard_source(16, rows=60))
        stop.set()
        reader.join(timeout=5)
        eng.close()
        assert not bad, f"non-contiguous commit observations: {bad[:5]}"
        assert rep.committed_epoch_ids() == [0, 1, 2, 3]

    def test_injected_node_death_replays_epoch(self, store):
        eng = StreamingRuntimeEngine(store, epoch_items=4, queue_capacity=8,
                                     backend="process")
        faults = StreamFaultInjection(node_death_in_epoch={"n2": 1})
        rep = eng.run_stream(shuffled_plan(store), shard_source(16, rows=100),
                             faults=faults)
        ids = rep.committed_epoch_ids()
        assert ids == [0, 1, 2, 3]
        assert rep.node_failures == ["n2"]
        assert rep.replayed_epochs == [1]
        cols = DataAccess(store).since_epoch(-1).read_all(projection=["quantity"])
        assert len(cols["quantity"]) == 16 * 100
        eng.close()

    def test_real_worker_kill_maps_to_epoch_replay(self, store):
        """SIGTERM a live worker process mid-stream: pipe EOF is the death
        sentinel, the node joins the existing fault path, the epoch replays
        on survivors, and no items are lost."""
        eng = StreamingRuntimeEngine(store, epoch_items=4, queue_capacity=8,
                                     backend="process")
        eng.prewarm_executors()
        killer = threading.Timer(0.3, lambda: eng.executor("n1").kill())
        killer.start()
        rep = eng.run_stream(shuffled_plan(store),
                             shard_source(16, rows=100, delay_s=0.05))
        killer.cancel()
        ids = rep.committed_epoch_ids()
        assert ids == list(range(len(ids))) and len(ids) == 4
        assert "n1" in rep.node_failures
        cols = DataAccess(store).since_epoch(-1).read_all(projection=["quantity"])
        assert len(cols["quantity"]) == 16 * 100
        eng.close()

    def test_injected_op_failures_are_retried(self, store):
        eng = StreamingRuntimeEngine(store, epoch_items=4, queue_capacity=8,
                                     backend="process", max_retries=5)
        faults = StreamFaultInjection(op_failures={("main", 0): 2})
        rep = eng.run_stream(columnar_plan(store), shard_source(8, rows=50),
                             faults=faults)
        total_failures = sum(e.run.op_failures.get("main[0]", 0)
                             for e in rep.epochs)
        assert total_failures >= 2
        assert not any(e.run.dummy_substitutions for e in rep.epochs)
        cols = DataAccess(store).since_epoch(-1).read_all(projection=["quantity"])
        assert len(cols["quantity"]) == 8 * 50   # retries, no loss
        eng.close()


# ---------------------------------------------------------------------------
class TestProcessBatch:
    def test_batch_run_equivalent(self, tmp_path):
        totals = {}
        for backend in ("thread", "process"):
            ds = DataStore(str(tmp_path / backend), nodes=["n0", "n1"])
            with RuntimeEngine(ds, backend=backend) as eng:
                rep = eng.run(columnar_plan(ds),
                              list(shard_source(8, rows=100)))
            assert rep.stage_items["main"] > 0
            cols = DataAccess(ds).read_all(projection=["quantity"])
            totals[backend] = (len(cols["quantity"]),
                               int(cols["quantity"].sum()))
        assert totals["thread"] == totals["process"]

    def test_batch_injected_death_reassigns_shards(self, store):
        """Death after the pre-upload stage: the dead worker's shards replay
        on the next live node's worker, exactly once end-to-end."""
        p = IngestPlan("batch2")
        s1 = p.add_statement([resolve_op("identity_parser"),
                              resolve_op("chunk", target_rows=256),
                              resolve_op("serialize", layout="columnar")],
                             kind="select")
        s2 = p.add_statement([resolve_op("upload", store=store)],
                             kind="store", inputs=[s1])
        create_stage(p, using=[s1], name="a")
        chain_stage(p, to=["a"], using=[s2], name="b")
        eng = RuntimeEngine(store, backend="process")
        faults = FaultInjection(node_death_after_stage={"n1": "a"})
        rep = eng.run(p, list(shard_source(8, rows=50)), faults=faults)
        assert "n1" in rep.node_failures
        assert rep.reassigned_shards > 0
        cols = DataAccess(store).read_all(projection=["quantity"])
        assert len(cols["quantity"]) == 8 * 50
        eng.close()

    def test_batch_replay_survives_target_worker_death(self, store):
        """The reassignment target's worker dies right before the replay job:
        the replay loop marks it dead and moves the shards to the next
        survivor instead of surfacing a raw WorkerDeath."""
        p = IngestPlan("batch3")
        s1 = p.add_statement([resolve_op("identity_parser"),
                              resolve_op("chunk", target_rows=256),
                              resolve_op("serialize", layout="columnar")],
                             kind="select")
        s2 = p.add_statement([resolve_op("upload", store=store)],
                             kind="store", inputs=[s1])
        create_stage(p, using=[s1], name="a")
        chain_stage(p, to=["a"], using=[s2], name="b")
        eng = RuntimeEngine(store, backend="process")
        eng.prewarm_executors()
        ex2 = eng.executor("n2")
        orig = ex2.run_stage
        calls = {"n": 0}

        def flaky(*a, **kw):
            calls["n"] += 1
            if calls["n"] == 2:      # call 1 = own stage "a"; call 2 = replay
                ex2.kill()
                time.sleep(0.4)      # let the EOF sentinel land
            return orig(*a, **kw)

        ex2.run_stage = flaky
        faults = FaultInjection(node_death_after_stage={"n1": "a"})
        rep = eng.run(p, list(shard_source(8, rows=50)), faults=faults)
        assert "n1" in rep.node_failures and "n2" in rep.node_failures
        cols = DataAccess(store).read_all(projection=["quantity"])
        assert len(cols["quantity"]) == 8 * 50
        eng.close()

    def test_worker_plan_state_persists_dummy_substitution(self, store):
        """An operator failing past max_retries is dummy-substituted inside
        the worker's resident plan (paper Sec. VI-C1), and the substitution
        is reported back to the coordinator."""
        eng = StreamingRuntimeEngine(store, epoch_items=8, queue_capacity=8,
                                     backend="process", max_retries=2)
        faults = StreamFaultInjection(op_failures={("main", 1): 4})
        rep = eng.run_stream(columnar_plan(store), shard_source(8, rows=50),
                             faults=faults)
        subs = [s for e in rep.epochs for s in e.run.dummy_substitutions]
        assert any("main[1]" in s for s in subs)
        eng.close()
