"""The metrics read from the program's own spans, on hand-built records:
per-epoch trees by parent links, self time without the children, only the
window's whole epochs, and no number where nothing was recorded."""
import itertools
import sys

import pytest

import harness

harness.prepare()
from repro import tracing  # noqa: E402

MS = 1_000_000          # ns


def reader(name):
    return harness.load_module(harness.bench_file(harness.ROOT, "metrics", name),
                               name).read


BULK = ("pack_op_ms.bulk", "serialize_op_ms.bulk", "erasure_op_ms.bulk",
        "kernel_call_ms.bulk", "store_write_ms.bulk", "store_write_mbps.bulk",
        "engine_self_ms.bulk")


class Records:
    def __init__(self):
        self.recs = []
        self.ids = itertools.count(1)

    def add(self, name, start_ms, end_ms, parent=None, **attrs):
        r = tracing.SpanRecord(next(self.ids), parent, name, "t",
                               int(start_ms * MS), int(end_ms * MS), attrs)
        self.recs.append(r)
        return r.id

    def epoch(self, eid, t0, k=1.0, whole=True):
        """An epoch of the bulk ingest at ``k`` times the base durations;
        not ``whole``, its own ``ib.epoch`` span is missing, as where
        recording began after the epoch did."""
        e = self.add("ib.epoch", t0, t0 + 100 * k, epoch=eid)
        if not whole:
            self.recs.pop()
        at = lambda x: t0 + x * k
        pack = self.add("ib.op.PackOp", at(10), at(40), e, rows=16)
        self.add("ib.kernel.pack_tokens", at(20), at(30), pack)
        self.add("ib.op.SerializeOp", at(40), at(50), e, rows=40)
        era = self.add("ib.op.ErasureOp", at(50), at(70), e, rows=40)
        self.add("ib.kernel.gf256_matmul", at(55), at(60), era)
        self.add("ib.op.LocateOp", at(70), at(71), e, rows=56)
        self.add("ib.op.UploadOp", at(71), at(91), e, rows=56, bytes=20e6)
        self.add("ib.store.commit", at(92), at(98), e)
        return e


#: base readings of one epoch (k = 1)
BASE = {"pack_op_ms.bulk": 20.0,            # 30 less the 10 ms kernel
        "serialize_op_ms.bulk": 10.0,
        "erasure_op_ms.bulk": 15.0,         # 20 less the 5 ms kernel
        "kernel_call_ms.bulk": 15.0,
        "store_write_ms.bulk": 26.0,
        "store_write_mbps.bulk": 1000.0,    # 20 MB in 20 ms
        "engine_self_ms.bulk": 13.0}        # 100 less 81 + 6 ms of children


@pytest.fixture
def recorded(monkeypatch):
    recs = Records()
    monkeypatch.setattr(tracing, "records", lambda: list(recs.recs))
    return recs


@pytest.mark.parametrize("name", BULK)
def test_median_of_the_window_epochs_with_self_time(recorded, name):
    recorded.epoch(4, 0.0, k=50.0)          # before the window
    recorded.epoch(5, 1000.0, k=1.0)
    recorded.epoch(6, 2000.0, k=2.0)
    recorded.epoch(7, 3000.0, k=3.0)
    recorded.epoch(8, 4000.0, k=90.0, whole=False)
    value = reader(name)({"window_epochs": [5, 6, 7, 8]})
    # the same bytes in twice the time: half the rate
    want = BASE[name] / 2 if name == "store_write_mbps.bulk" else 2 * BASE[name]
    assert value == pytest.approx(want)


def test_an_epoch_sums_its_nodes(recorded):
    e = recorded.epoch(5, 0.0)
    recorded.add("ib.op.PackOp", 0.0, 7.0, e, rows=3)        # a second node
    assert reader("pack_op_ms.bulk")({"window_epochs": [5]}) == pytest.approx(27.0)
    assert reader("engine_self_ms.bulk")({"window_epochs": [5]}) == \
        pytest.approx(13.0 - 7.0)


def test_the_feed_spans_inside_the_window(recorded):
    spans = harness.Spans()
    spans.records = [("window", 1.0, 2.0), ("feeder.next", 0.9, 1.1)]
    recorded.add("ib.feeder.batch", 900.0, 1100.0, rows=8)    # half inside
    recorded.add("ib.train.make_batch", 1200.0, 1250.0)
    recorded.add("ib.train.put_batch", 1300.0, 1350.0)
    recorded.add("ib.train.put_batch", 2500.0, 2600.0)      # after it
    recorded.add("ib.epoch", 1000.0, 1900.0, epoch=1)       # not feed
    share = reader("feed_host_share.train")({"spans": spans})
    assert share == pytest.approx(20.0)


@pytest.mark.parametrize("name", BULK + ("feed_host_share.train",))
def test_no_number_without_records(recorded, monkeypatch, name):
    spans = harness.Spans()
    spans.records = [("window", 1.0, 2.0)]
    rec = {"window_epochs": [5], "spans": spans}
    assert reader(name)(rec) is None                # an untraced run
    recorded.epoch(3, 0.0)
    recorded.add("ib.feeder.batch", 5000.0, 5100.0)
    assert reader(name)(rec) is None                # nothing of the window
    monkeypatch.setitem(sys.modules, "repro.tracing", None)
    recorded.epoch(5, 1000.0)
    recorded.add("ib.feeder.batch", 1100.0, 1200.0)
    assert reader(name)(rec) is None                # a program without spans
