"""Trace reduction: busy union, idle share, kernel time by name, idle gaps
named by host spans -- on hand-made events and on a recorded trace."""
import json
import os

import pytest

import devtrace
from devtrace import Event

DATA = os.path.join(os.path.dirname(os.path.abspath(__file__)), "data")


def test_union_merges_nested_and_overlapping_intervals():
    assert devtrace.union([(0, 2), (1, 3), (5, 6), (5.5, 5.7)]) == [(0, 3), (5, 6)]


def test_busy_and_gaps_inside_a_window():
    ops = [Event("a", 0.0, 2.0), Event("b", 1.0, 3.0),   # overlap: 3 s
           Event("c", 5.0, 6.0), Event("d", 5.5, 5.7),   # nested: 1 s
           Event("e", 9.0, 12.0)]                        # cut at 10: 1 s
    assert devtrace.busy_seconds(ops, 0.0, 10.0) == pytest.approx(5.0)
    assert devtrace.gaps(ops, 0.0, 10.0) == [(3.0, 5.0), (6.0, 9.0)]


def test_idle_gaps_named_by_the_innermost_open_span():
    ops = [Event("x", 0.0, 1.0), Event("x", 4.0, 5.0)]
    spans = [Event("window", 0.0, 5.0), Event("feeder.next", 1.5, 3.5)]
    assert devtrace.named_gaps(ops, spans, 0.0, 5.0) == [("feeder.next", 3.0)]


def test_module_calls_by_program_name():
    mods = [Event("jit_pack_tokens(123)", 1.0, 1.1),
            Event("jit_pack_tokens(456)", 2.0, 2.2),
            Event("jit_gf256_matmul(789)", 3.0, 3.5),
            Event("jit_pack_tokens(123)", 11.0, 11.1)]
    calls = devtrace.module_calls(mods, "jit_pack_tokens", 0.0, 10.0)
    assert [c.start for c in calls] == [1.0, 2.0]
    assert devtrace.module_name("jit_train_step(52886)") == "jit_train_step"
    assert devtrace.op_label("%fusion.661 = bf16[8,1024]{1,0} fusion(x)") == \
        "fusion.661 bf16[8,1024]{1,0}"


def _recorded():
    with open(os.path.join(DATA, "ingest_trace.json")) as f:
        raw = json.load(f)
    ev = lambda xs: [Event(n, s, e) for n, s, e in xs]
    return raw, ev(raw["ops"]), ev(raw["modules"]), ev(raw["spans"])


def test_recorded_trace_reduces_as_recorded():
    """A slice of a traced bulk-ingest window on one v5e: the busy time,
    the kernels' device time and the idle share it was recorded with."""
    raw, ops, mods, spans = _recorded()
    lo, hi = raw["window"]
    busy = devtrace.busy_seconds(ops, lo, hi)
    assert busy == pytest.approx(raw["busy_s"], rel=1e-9)
    assert 0.0 < busy < hi - lo
    for name, want in raw["module_seconds"].items():
        got = sum(c.end - c.start for c in devtrace.module_calls(mods, name, lo, hi))
        assert got == pytest.approx(want, rel=1e-9)
    idle = sum(e - s for s, e in devtrace.gaps(ops, lo, hi))
    assert idle + busy == pytest.approx(hi - lo, rel=1e-9)
    named = devtrace.named_gaps(ops, spans, lo, hi)
    assert len(named) == min(10, len(devtrace.gaps(ops, lo, hi)))
    assert named[0][1] == max(e - s for s, e in devtrace.gaps(ops, lo, hi))
