"""Kernel-backed batch operator tier (ISSUE 7).

The scalar iterator path is the correctness oracle: every test here pins the
vectorized ``process_batch`` implementations (stacked GF(256) erasure encode,
batch serialize/pack), the ``VectorizeRule`` block selection, and the
runtime integration on both node backends against it — plus the satellite
regressions (``num_threads`` across clone/pickle, deque pending buffer,
pool reuse across ``set_input`` calls).
"""
import copy
import pickle
import threading
from collections import deque

import numpy as np
import pytest

from repro.core import (BatchFallback, DataAccess, DataStore, FaultInjection,
                        IngestionOptimizer, IngestPlan, RuntimeEngine,
                        StreamingRuntimeEngine, VectorizeRule, chain_stage,
                        create_stage, resolve_op, run_ops_batched, select)
from repro.core.items import Granularity, IngestItem
from repro.core.operators import IngestOp, OpMode
from repro.core.ops_format import PackOp, SerializeOp
from repro.core.ops_store import ErasureOp
from repro.data.generators import as_file_items, gen_lineitem
from repro.erasure import ReedSolomon
from repro.erasure.gf256 import GF256


def _blocks(rng, n, lo=1, hi=5000):
    """Random BLOCK items with ragged (often odd) payload lengths."""
    return [IngestItem(rng.integers(0, 256, size=int(rng.integers(lo, hi)),
                                    dtype=np.uint8).tobytes(),
                       Granularity.BLOCK, (), {}) for _ in range(n)]


def _norm(item):
    """Stripe ids embed a per-instance nonce; strip it so two operator
    instances' outputs compare equal."""
    meta = dict(item.meta)
    if "stripe_id" in meta:
        meta["stripe_id"] = meta["stripe_id"].rsplit("-", 1)[-1]
    data = item.data
    if isinstance(data, np.ndarray):
        data = data.tobytes()
    return (bytes(data) if isinstance(data, (bytes, bytearray)) else data,
            item.labels, meta)


# ---------------------------------------------------------------------------
class TestGF256Tables:
    def test_row_table_matches_mul(self, rng):
        b = np.arange(256, dtype=np.uint8)
        for c in (0, 1, 2, 7, 128, 255):
            np.testing.assert_array_equal(GF256.row_table(c),
                                          GF256.mul(np.uint8(c), b))

    def test_pair_table_packs_two_products(self):
        t = GF256.pair_table(29)
        row = GF256.row_table(29)
        idx = np.arange(65536, dtype=np.uint32)
        np.testing.assert_array_equal(t & 0xFF, row[idx & 0xFF])
        np.testing.assert_array_equal(t >> 8, row[idx >> 8])

    @pytest.mark.parametrize("n", [0, 1, 2, 3, 64, 777])
    def test_xor_mul_into_matches_scalar(self, rng, n):
        for c in (0, 3, 91, 255):
            payload = rng.integers(0, 256, n, dtype=np.uint8)
            acc = rng.integers(0, 256, max(n, 1), dtype=np.uint8)
            expect = acc.copy()
            expect[:n] ^= GF256.mul(np.uint8(c), payload)
            GF256.xor_mul_into(acc, c, payload)
            np.testing.assert_array_equal(acc, expect)

    def test_xor_mul_into_unaligned_slice(self, rng):
        # odd-offset slice of a larger buffer: uint16 view would raise
        buf = rng.integers(0, 256, 1025, dtype=np.uint8)
        payload = buf[1:]
        acc = np.zeros(len(payload), dtype=np.uint8)
        GF256.xor_mul_into(acc, 7, payload)
        np.testing.assert_array_equal(acc, GF256.mul(np.uint8(7), payload))


class TestBatchEncode:
    @pytest.mark.parametrize("k,m", [(4, 2), (10, 3)])
    def test_matches_per_stripe_oracle(self, rng, k, m):
        rs = ReedSolomon(k, m)
        stripes = [[rng.integers(0, 256, int(rng.integers(1, 3000)),
                                 dtype=np.uint8) for _ in range(k)]
                   for _ in range(5)]
        batched = rs.encode_payload_batch(stripes)
        for payloads, (parity, pad) in zip(stripes, batched):
            exp_parity, exp_pad = rs.encode_payloads(
                [p.tobytes() for p in payloads])
            assert pad == exp_pad
            np.testing.assert_array_equal(parity, exp_parity)

    def test_interpret_mode_kernel_on_stacked_matrix(self, rng):
        """The pallas path's stacked ``(m x k) @ (k x S*L)`` contraction vs
        the kernels/ref.py table oracle (interpret mode off-TPU)."""
        import jax.numpy as jnp

        from repro.kernels import ref
        from repro.kernels.ops import gf256_matmul
        k, m, S, L = 5, 3, 4, 256
        rs = ReedSolomon(k, m)
        data = rng.integers(0, 256, (k, S * L), dtype=np.uint8)
        out = np.asarray(gf256_matmul(jnp.asarray(rs.C), jnp.asarray(data),
                                      block_n=512))
        np.testing.assert_array_equal(out, ref.gf256_matmul_ref(rs.C, data))

    def test_use_pallas_batch_matches_numpy_batch(self, rng):
        k, m = 4, 2
        stripes = [[rng.integers(0, 256, 300, dtype=np.uint8)
                    for _ in range(k)] for _ in range(3)]
        plain = ReedSolomon(k, m).encode_payload_batch(copy.deepcopy(stripes))
        pallas = ReedSolomon(k, m, use_pallas=True).encode_payload_batch(
            copy.deepcopy(stripes))
        for (pa, la), (pb, lb) in zip(plain, pallas):
            assert la == lb
            np.testing.assert_array_equal(pa, pb)


# ---------------------------------------------------------------------------
class TestErasureOpBatch:
    @pytest.mark.parametrize("n", [1, 4, 11, 23])
    def test_byte_identical_to_scalar_oracle(self, rng, n):
        items = _blocks(rng, n)
        scalar = ErasureOp(k=4, m=2).run([copy.deepcopy(i) for i in items])
        batch = ErasureOp(k=4, m=2).run_batch(
            [copy.deepcopy(i) for i in items])
        assert [_norm(x) for x in scalar] == [_norm(x) for x in batch]

    def test_trailing_partial_stripe_drained(self, rng):
        op = ErasureOp(k=4, m=2)
        out = op.run_batch(_blocks(rng, 6))   # 1 full + 1 partial stripe
        assert len(out) == 6 + 2 * 2
        assert not op._stripe                 # nothing left buffered
        metas = [it.meta for it in out]
        assert {m["stripe_id"] for m in metas} == {
            metas[0]["stripe_id"], metas[-1]["stripe_id"]}

    def test_use_pallas_op_matches_scalar(self, rng):
        items = _blocks(rng, 9)
        scalar = ErasureOp(k=4, m=2).run([copy.deepcopy(i) for i in items])
        batch = ErasureOp(k=4, m=2, use_pallas=True).run_batch(
            [copy.deepcopy(i) for i in items])
        assert [_norm(x) for x in scalar] == [_norm(x) for x in batch]
        assert ErasureOp(k=4, m=2, use_pallas=True).rs._pallas_matmul

    def test_unsupported_payload_raises_fallback(self):
        op = ErasureOp(k=2, m=1)
        items = [IngestItem({"x": np.arange(4)}, Granularity.BLOCK, (), {}),
                 IngestItem(b"ok", Granularity.BLOCK, (), {})]
        with pytest.raises(BatchFallback):
            op.process_batch(items)


class TestFormatOpsBatch:
    def _chunks(self, n, rows=64):
        return [IngestItem({"a": np.arange(rows, dtype=np.int64) + i,
                            "b": np.full(rows, float(i))})
                for i in range(n)]

    @pytest.mark.parametrize("layouts", [None, ("columnar", "row")])
    def test_serialize_batch_matches_serial_oracle(self, layouts):
        kw = {"layouts": layouts} if layouts else {}
        oracle = SerializeOp(**kw)
        oracle.mode = OpMode.SERIAL     # the deterministic reference order
        expect = oracle.run(self._chunks(5))
        got = SerializeOp(**kw).run_batch(self._chunks(5))
        assert len(expect) == len(got)
        for e, g in zip(expect, got):
            assert e.labels == g.labels
            assert e.data.tobytes() == g.data.tobytes()

    def test_pack_batch_matches_serial_oracle(self, rng):
        def chunks():
            return [IngestItem({"tokens": np.array(
                [rng.integers(1, 100, int(rng.integers(3, 40)))
                 for _ in range(20)], dtype=object)}) for rng in
                [np.random.default_rng(s) for s in range(4)]]
        oracle = PackOp(seq_len=64, rows_per_block=4)
        oracle.mode = OpMode.SERIAL
        expect = oracle.run(chunks())
        got = PackOp(seq_len=64, rows_per_block=4).run_batch(chunks())
        assert len(expect) == len(got)
        for e, g in zip(expect, got):
            assert e.labels == g.labels
            for key in ("tokens", "loss_mask", "positions", "segment_ids"):
                np.testing.assert_array_equal(e.data[key], g.data[key])


# ---------------------------------------------------------------------------
class TestVectorizeRule:
    def _plan(self, ds):
        p = IngestPlan("v")
        s1 = select(p)
        s2 = p.add_statement([resolve_op("chunk", target_rows=256),
                              resolve_op("serialize", layout="columnar"),
                              resolve_op("erasure", k=4, m=2)],
                             kind="format", inputs=[s1])
        s3 = p.add_statement([resolve_op("upload", store=ds)],
                             kind="store", inputs=[s2])
        create_stage(p, using=[s1], name="a")
        chain_stage(p, to=["a"], using=[s2], name="b")
        chain_stage(p, to=["b"], using=[s3], name="c")
        return p

    def test_selects_all_capable_blocks_only(self, store):
        plans = IngestionOptimizer().optimize(self._plan(store).compile())
        fmt = next(sp for sp in plans if sp.name == "b")
        # [chunk, serialize] shares a block and vectorizes (chunk gained the
        # default-loop batch path with the columnar plane, ISSUE 10);
        # [erasure] stands alone and vectorizes
        assert fmt.batch_blocks == [True, True]
        for sp in plans:
            for blk, on in zip(sp.pipeline_blocks, sp.batch_blocks):
                if on:
                    assert all(sp.ops[i].batch_capable for i in blk)

    def test_disabled_rule_keeps_everything_scalar(self, store):
        opt = IngestionOptimizer(vectorize=VectorizeRule(enabled=False))
        plans = opt.optimize(self._plan(store).compile())
        assert not any(any(sp.batch_blocks) for sp in plans)

    def test_unoptimized_plans_untouched(self, store):
        assert all(sp.batch_blocks == []
                   for sp in self._plan(store).compile())

    def test_batch_blocks_survive_clone_and_pickle(self, store):
        plans = IngestionOptimizer().optimize(self._plan(store).compile())
        fmt = next(sp for sp in plans if sp.name == "b")
        assert fmt.clone().batch_blocks == fmt.batch_blocks
        # upload holds a live store; pickle the format stage only
        assert pickle.loads(pickle.dumps(fmt)).batch_blocks == fmt.batch_blocks


class _FallbackOp(IngestOp):
    name = "fb"
    batch_capable = True

    def process(self, item):
        yield item.with_label(self.name, "scalar")

    def process_batch(self, items):
        raise BatchFallback("no vectorized path for these payloads")


class TestRunOpsBatched:
    def test_fallback_counted_and_output_is_scalar(self, rng):
        out, stats = run_ops_batched([_FallbackOp()], _blocks(rng, 3))
        assert stats["batch_fallbacks"] == 1
        assert [it.label_value("fb") for it in out] == ["scalar"] * 3
        assert stats["vectorized_rows"] == 3

    def test_kernel_time_attributed(self, rng):
        """Kernel launches are charged to the block that made them: none on
        the numpy encoder, one per batch on the kernel."""
        op = ErasureOp(k=4, m=2)
        _, stats = run_ops_batched([op], _blocks(rng, 8))
        assert stats["batch_fallbacks"] == 0
        assert stats["kernel_calls"] == op.kernel_calls == 0
        op = ErasureOp(k=4, m=2, use_pallas=True)
        _, stats = run_ops_batched([op], _blocks(rng, 8))
        assert stats["batch_fallbacks"] == 0
        assert stats["kernel_calls"] == op.kernel_calls == 1


# ---------------------------------------------------------------------------
def erasure_plan(ds):
    p = IngestPlan("bt")
    s1 = select(p)
    s2 = p.add_statement([resolve_op("chunk", target_rows=256),
                          resolve_op("serialize", layout="columnar"),
                          resolve_op("erasure", k=4, m=2)],
                         kind="format", inputs=[s1])
    s3 = p.add_statement([resolve_op("upload", store=ds)],
                         kind="store", inputs=[s2])
    create_stage(p, using=[s1], name="a")
    chain_stage(p, to=["a"], using=[s2], name="b")
    chain_stage(p, to=["b"], using=[s3], name="c")
    return p


def stream_plan(ds):
    p = IngestPlan("sbt")
    s1 = p.add_statement([
        resolve_op("identity_parser"),
        resolve_op("partition", scheme="hash", key="orderkey",
                   num_partitions=4),
        resolve_op("map", fn="repro.core.ops_select:identity_columns",
                   shuffle_by="partition"),
    ], kind="select")
    s2 = p.add_statement([resolve_op("chunk", target_rows=256),
                          resolve_op("serialize", layout="columnar"),
                          resolve_op("erasure", k=4, m=2)],
                         kind="format", inputs=[s1])
    s3 = p.add_statement([resolve_op("upload", store=ds)],
                         kind="store", inputs=[s2])
    create_stage(p, using=[s1], name="a")
    chain_stage(p, to=["a"], using=[s2], name="b")
    chain_stage(p, to=["b"], using=[s3], name="c")
    return p


class TestEngineIntegration:
    def test_thread_backend_vectorizes_and_matches_scalar(self, tmp_path):
        rows = {}
        for tag, rule in (("vec", VectorizeRule()),
                          ("scalar", VectorizeRule(enabled=False))):
            ds = DataStore(str(tmp_path / tag), nodes=["n0", "n1"])
            eng = RuntimeEngine(
                ds, optimizer=IngestionOptimizer(vectorize=rule))
            rep = eng.run(erasure_plan(ds),
                          as_file_items(gen_lineitem(2000), shards=4))
            if tag == "vec":
                assert rep.vectorized_rows > 0
                assert rep.batch_fallbacks == 0
            else:
                assert rep.vectorized_rows == 0
            cols = DataAccess(ds).read_all(projection=["quantity"])
            rows[tag] = np.sort(cols["quantity"])
        np.testing.assert_array_equal(rows["vec"], rows["scalar"])

    def test_injected_failure_in_batched_block_retries(self, store):
        eng = RuntimeEngine(store, max_retries=3)
        items = as_file_items(gen_lineitem(1000), shards=4)
        # op index 2 = erasure, the batched block in stage "b"
        faults = FaultInjection(op_failures={("b", 2): 2})
        rep = eng.run(erasure_plan(store), items, faults=faults)
        assert rep.op_failures and not rep.dummy_substitutions
        assert rep.vectorized_rows > 0
        assert store.blocks()

    def test_repeated_failure_installs_dummy_in_batched_block(self, store):
        eng = RuntimeEngine(store, max_retries=3)
        items = as_file_items(gen_lineitem(1000), shards=4)
        faults = FaultInjection(op_failures={("b", 2): 99})
        rep = eng.run(erasure_plan(store), items, faults=faults)
        assert rep.dummy_substitutions
        assert store.blocks()   # dummy pass-through keeps the stage alive

    def test_process_backend_vectorizes_with_zero_coordinator_bytes(
            self, tmp_path):
        rows = {}
        for backend in ("thread", "process"):
            ds = DataStore(str(tmp_path / backend),
                           nodes=["n0", "n1", "n2", "n3"])
            eng = StreamingRuntimeEngine(ds, epoch_items=4, queue_capacity=8,
                                         backend=backend)
            rep = eng.run_stream(
                stream_plan(ds),
                (IngestItem(gen_lineitem(100, seed=i)) for i in range(8)))
            assert rep.vectorized_rows() > 0
            assert rep.batch_fallbacks() == 0
            if backend == "process":
                # batch execution must not re-route item bytes through the
                # coordinator: the resident dataflow invariant holds
                assert sum(e.run.stage_coordinator_bytes
                           for e in rep.epochs) == 0
            cols = DataAccess(ds).since_epoch(-1).read_all(
                projection=["quantity"])
            rows[backend] = np.sort(cols["quantity"])
            eng.close()
        np.testing.assert_array_equal(rows["thread"], rows["process"])


# ---------------------------------------------------------------------------
class TestSatelliteRegressions:
    def test_num_threads_survives_clone_and_pickle(self):
        op = SerializeOp(num_threads=7)
        assert op.num_threads == 7
        assert op.clone().num_threads == 7
        assert pickle.loads(pickle.dumps(op)).num_threads == 7

    def test_pending_buffer_is_deque(self):
        op = SerializeOp()
        assert isinstance(op._pending, deque)
        op.run(self_chunks())
        assert isinstance(op._pending, deque)

    def test_pool_reused_across_set_input_and_joined_on_finalize(self):
        op = SerializeOp(num_threads=2)   # cpu_heavy -> PARALLEL mode
        op.initialize()
        op.set_input(self_chunks())
        while op.has_next():
            op.next()
        pool1 = op._pool
        assert pool1 is not None
        op.set_input(self_chunks())
        while op.has_next():
            op.next()
        assert op._pool is pool1          # no per-batch pool churn
        op.finalize()
        assert op._pool is None
        assert pool1._shutdown


def self_chunks(n=4, rows=32):
    return [IngestItem({"a": np.arange(rows, dtype=np.int64) + i})
            for i in range(n)]
