"""Program spans (``repro.tracing``): recorded only while a profiler session
runs, linked to their parents across the node executor's lanes, and written
onto the profiler's host plane under the same names."""
import glob

import jax
import numpy as np
import pytest

from repro import tracing

SEQ, ROWS, K, M = 128, 4, 4, 2

#: every span name the program opens on the ingest and feed paths
NAMES = ("ib.epoch", "ib.op.PackOp", "ib.op.SerializeOp", "ib.op.ErasureOp",
         "ib.op.UploadOp", "ib.kernel.pack_tokens", "ib.kernel.gf256_matmul",
         "ib.store.commit", "ib.feeder.batch", "ib.train.make_batch",
         "ib.train.put_batch")


def _corpus(n_docs=40, seed=0):
    rng = np.random.default_rng(seed)
    docs = np.empty(n_docs, object)
    for i in range(n_docs):
        docs[i] = rng.integers(0, 500, int(rng.integers(10, 300))).astype(np.int32)
    return {"tokens": docs,
            "length": np.array([len(d) for d in docs], np.int32),
            "doc_id": np.arange(n_docs, dtype=np.int64)}


@pytest.fixture(scope="module")
def traced(tmp_path_factory):
    """A small streaming ingest through both kernels (interpreted), then two
    fed batches made and placed, all under one profiler session."""
    from repro.core import DataStore
    from repro.core.streaming import StreamingRuntimeEngine
    from repro.data.feeder import BlockFeeder, build_lm_plan
    from repro.data.generators import as_file_items
    from repro.launch.train import BATCH_FIELDS, Trainer, make_batch

    tmp = tmp_path_factory.mktemp("tracing")
    store = DataStore(str(tmp / "store"), nodes=["n0"])
    plan = build_lm_plan(store, seq_len=SEQ, rows_per_block=ROWS,
                         use_pallas=True,
                         erasure={"k": K, "m": M, "use_pallas": True})
    items = as_file_items(_corpus(), 4)
    engine = StreamingRuntimeEngine(store, epoch_items=2, backend="thread")
    dev = jax.sharding.SingleDeviceSharding(jax.devices()[0])
    trainer = Trainer(None, None, None, None, None, None,
                      {k: dev for k in BATCH_FIELDS})
    tracing.clear()
    try:
        with jax.profiler.trace(str(tmp / "trace")):
            report = engine.run_stream(plan, iter(items))
            for raw in BlockFeeder(store, batch_rows=ROWS).batches(2):
                jax.block_until_ready(trainer.put_batch(make_batch(raw, SEQ)))
    finally:
        engine.close()
    recs = tracing.records()
    tracing.clear()
    xplane = glob.glob(str(tmp / "trace" / "**" / "*.xplane.pb"),
                       recursive=True)
    return report, recs, xplane[0]


def test_nothing_is_recorded_without_a_profiler_session():
    tracing.clear()
    assert not tracing.recording()
    with tracing.span("ib.test", rows=3) as s:
        tracing.annotate(bytes=5)
    assert s is None
    assert tracing.span("ib.test") is tracing.NOOP
    assert tracing.records() == [] and tracing.dropped() == 0


def test_epoch_op_kernel_linked_across_the_executor_lane(traced):
    report, recs, _ = traced
    by_id = {r.id: r for r in recs}
    epochs = [r for r in recs if r.name == "ib.epoch"]
    assert sorted(r.attrs["epoch"] for r in epochs) == \
        report.committed_epoch_ids()
    kernels = [r for r in recs if r.name == "ib.kernel.pack_tokens"]
    assert len(kernels) == len(epochs)
    for k in kernels:
        op = by_id[k.parent]
        ep = by_id[op.parent]
        assert (op.name, ep.name) == ("ib.op.PackOp", "ib.epoch")
        assert op.thread != ep.thread           # the store lane's thread
        assert ep.start_ns <= op.start_ns <= k.start_ns
        assert k.end_ns <= op.end_ns <= ep.end_ns
        assert op.attrs["rows"] > 0
    commits = [r for r in recs if r.name == "ib.store.commit"]
    assert {by_id[c.parent].name for c in commits} == {"ib.epoch"}
    uploads = [r for r in recs if r.name == "ib.op.UploadOp"]
    assert uploads and all(u.attrs["bytes"] > 0 for u in uploads)


def test_every_span_lies_on_the_host_plane_nested_as_recorded(traced):
    from jax.profiler import ProfileData
    _, recs, xplane = traced
    events = {}
    for plane in ProfileData.from_file(xplane).planes:
        if not plane.name.startswith("/host:"):
            continue
        for line in plane.lines:
            for e in line.events:
                if e.name.startswith("ib."):
                    events.setdefault(e.name, []).append(
                        (e.start_ns, e.start_ns + e.duration_ns))
    assert set(NAMES) <= set(events)
    for name in NAMES:
        assert len(events[name]) == sum(r.name == name for r in recs), name
    by_id = {r.id: r for r in recs}
    pairs = {(r.name, by_id[r.parent].name) for r in recs
             if r.parent in by_id}
    assert ("ib.kernel.gf256_matmul", "ib.op.ErasureOp") in pairs
    for child, parent in pairs:
        for s, e in events[child]:
            assert any(ps <= s and e <= pe for ps, pe in events[parent]), \
                (child, parent)


def test_feed_spans_carry_their_rows(traced):
    _, recs, _ = traced
    batches = [r for r in recs if r.name == "ib.feeder.batch"
               and "rows" in r.attrs]
    assert [r.attrs["rows"] for r in batches] == [ROWS, ROWS]
    assert sum(r.name == "ib.train.put_batch" for r in recs) == 2


def test_clear_resets_the_records(tmp_path, monkeypatch):
    tracing.clear()
    monkeypatch.setattr(tracing, "LIMIT", 2)
    with jax.profiler.trace(str(tmp_path)):
        assert tracing.recording()
        for _ in range(3):
            with tracing.span("ib.test"):
                pass
    assert [r.name for r in tracing.records()] == ["ib.test"] * 2
    assert tracing.dropped() == 1
    tracing.clear()
    assert tracing.records() == [] and tracing.dropped() == 0
