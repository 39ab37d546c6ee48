"""Shared reduction for the metrics read from the program's own spans.

The program records its spans (``repro.tracing``) while the profiler runs,
in this process; a reader takes them after the run.  A program without
that module, or a run that recorded nothing, reads as None.

In an ingest cell the spans form one tree per epoch: the ``ib.epoch`` span
(attr ``epoch``) and every span under it by parent links, across the
engine's threads.  An epoch counts only where its ``ib.epoch`` span was
recorded, which holds only if recording was on from its start to its end,
so every span of the epoch is in the tree.  Times are milliseconds on
``time.perf_counter``.
"""
from __future__ import annotations

import fnmatch
import importlib
import os
import statistics
import sys
from typing import Any, Callable, Dict, List, Optional, Sequence

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import devtrace  # noqa: E402


def records() -> Optional[List[Any]]:
    """The program's span records, or None where it recorded none."""
    try:
        tracing = importlib.import_module("repro.tracing")
    except ImportError:
        return None
    return tracing.records() or None


def _ms(ns: float) -> float:
    return ns * 1e-6


class Epoch:
    """The spans of one epoch's tree."""

    def __init__(self, root: Any, kids: Dict[int, List[Any]]) -> None:
        self.root, self.kids = root, kids
        self.spans: List[Any] = []
        todo = [root]
        while todo:
            r = todo.pop()
            self.spans.append(r)
            todo.extend(kids.get(r.id, ()))

    def named(self, *patterns: str) -> List[Any]:
        return [r for r in self.spans
                if any(fnmatch.fnmatchcase(r.name, p) for p in patterns)]

    def self_ns(self, r: Any) -> int:
        """A span's duration less the union of its children's intervals."""
        inner = devtrace.union(devtrace.clip(
            ((c.start_ns, c.end_ns) for c in self.kids.get(r.id, ())),
            r.start_ns, r.end_ns))
        return (r.end_ns - r.start_ns) - sum(e - s for s, e in inner)

    def total_ms(self, *patterns: str) -> Optional[float]:
        """Summed duration of the spans matching ``patterns``, or None
        where none is in this epoch."""
        spans = self.named(*patterns)
        return _ms(sum(r.end_ns - r.start_ns for r in spans)) if spans else None

    def self_ms(self, *patterns: str) -> Optional[float]:
        spans = self.named(*patterns)
        return _ms(sum(self.self_ns(r) for r in spans)) if spans else None

    def attr(self, pattern: str, key: str) -> float:
        return sum(r.attrs.get(key, 0) for r in self.named(pattern))


def window_epochs(rec: Dict[str, Any]) -> List[Epoch]:
    """The trees of the window's epochs that were recorded whole."""
    recs = records()
    if not recs:
        return []
    mine = set(rec.get("window_epochs", ()))
    kids: Dict[int, List[Any]] = {}
    roots: Dict[int, Any] = {}
    for r in recs:
        if r.parent is not None:
            kids.setdefault(r.parent, []).append(r)
        if r.name == "ib.epoch" and r.attrs.get("epoch") in mine:
            roots[r.attrs["epoch"]] = r     # the latest run's, if several
    return [Epoch(r, kids) for r in roots.values()]


def epoch_median(rec: Dict[str, Any],
                 value: Callable[[Epoch], Optional[float]]) -> Optional[float]:
    """Median over the window's whole epochs of ``value`` (summed over the
    epoch's nodes, as its tree holds them); epochs where it is None are
    left out, and None where every one is."""
    vals = [v for v in (value(e) for e in window_epochs(rec)) if v is not None]
    return statistics.median(vals) if vals else None


def window_share(rec: Dict[str, Any], names: Sequence[str]) -> Optional[float]:
    """Share (%) of the benchmark's last ``window`` span that the program's
    spans named ``names`` cover, clipped to the window (both clocks are
    ``time.perf_counter``)."""
    spans, recs = rec.get("spans"), records()
    if spans is None or not recs:
        return None
    bounds = [(s, e) for n, s, e in spans.records if n == "window"]
    if not bounds:
        return None
    lo, hi = bounds[-1]
    mine = [(r.start_ns * 1e-9, r.end_ns * 1e-9) for r in recs
            if r.name in names]
    inside = devtrace.clip(mine, lo, hi)
    if not inside:
        return None
    return 100.0 * sum(e - s for s, e in inside) / (hi - lo)
