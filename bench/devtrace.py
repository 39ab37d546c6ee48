"""Device profiler traces: capture, and reduction to busy time, kernel time
and idle gaps.

A trace is read with ``jax.profiler.ProfileData`` alone.  Device planes are
named ``/device:TPU:<n>``; on each, the ``XLA Modules`` line holds one event
per program execution (named ``jit_<function>(<hash>)``) and the
``XLA Ops`` line one per operation.  Busy time is the union of the
operation intervals (nested operations count once); idle time is the rest of
the traced window.  The benchmark's own host spans (``TraceAnnotation``)
sit on the host plane on the same clock, which is how a window's bounds and
an idle gap's cause are found.
"""
from __future__ import annotations

import glob
import os
import re
from dataclasses import dataclass, field
from typing import Dict, Iterable, List, Optional, Sequence, Tuple

Interval = Tuple[float, float]          # seconds on the trace clock


def start(log_dir: str) -> None:
    """Start the profiler with the Python tracer off (it records every
    Python call of every thread, which costs more than the work traced)."""
    import jax
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    opts.host_tracer_level = 2
    jax.profiler.start_trace(log_dir, profiler_options=opts)


def stop() -> None:
    import jax
    jax.profiler.stop_trace()


def find_xplane(log_dir: str) -> str:
    files = sorted(glob.glob(os.path.join(log_dir, "**", "*.xplane.pb"),
                             recursive=True))
    if not files:
        raise FileNotFoundError(f"no .xplane.pb under {log_dir}")
    return files[-1]


@dataclass
class Event:
    name: str
    start: float
    end: float


@dataclass
class Trace:
    """What the reductions need from one trace, times in seconds."""
    device_ops: Dict[str, List[Event]] = field(default_factory=dict)
    device_modules: Dict[str, List[Event]] = field(default_factory=dict)
    host_spans: List[Event] = field(default_factory=list)


def load(path: str, span_names: Iterable[str]) -> Trace:
    from jax.profiler import ProfileData
    wanted = set(span_names)
    out = Trace()
    data = ProfileData.from_file(path)
    for plane in data.planes:
        if plane.name.startswith("/device:TPU:"):
            ops: List[Event] = []
            mods: List[Event] = []
            for line in plane.lines:
                target = {"XLA Ops": ops, "XLA Modules": mods}.get(line.name)
                if target is None:
                    continue
                for e in line.events:
                    s = e.start_ns * 1e-9
                    target.append(Event(e.name, s, s + e.duration_ns * 1e-9))
            out.device_ops[plane.name] = ops
            out.device_modules[plane.name] = mods
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                for e in line.events:
                    if e.name in wanted:
                        s = e.start_ns * 1e-9
                        out.host_spans.append(
                            Event(e.name, s, s + e.duration_ns * 1e-9))
    return out


# ------------------------------------------------------------ reductions
def union(intervals: Iterable[Interval]) -> List[Interval]:
    merged: List[Interval] = []
    for s, e in sorted(intervals):
        if merged and s <= merged[-1][1]:
            if e > merged[-1][1]:
                merged[-1] = (merged[-1][0], e)
        else:
            merged.append((s, e))
    return merged


def clip(intervals: Iterable[Interval], lo: float, hi: float) -> List[Interval]:
    return [(max(s, lo), min(e, hi)) for s, e in intervals
            if e > lo and s < hi]


def busy_seconds(events: Sequence[Event], lo: float, hi: float) -> float:
    """Length of the union of the events' intervals inside [lo, hi]."""
    return sum(e - s for s, e in union(clip(((x.start, x.end) for x in events),
                                            lo, hi)))


def gaps(events: Sequence[Event], lo: float, hi: float) -> List[Interval]:
    """Idle intervals of [lo, hi]: where no event runs."""
    out: List[Interval] = []
    t = lo
    for s, e in union(clip(((x.start, x.end) for x in events), lo, hi)):
        if s > t:
            out.append((t, s))
        t = max(t, e)
    if hi > t:
        out.append((t, hi))
    return out


_MODULE = re.compile(r"^(.*)\(\d+\)$")


def module_name(event_name: str) -> str:
    """``jit_pack_tokens(1165...)`` -> ``jit_pack_tokens``."""
    m = _MODULE.match(event_name)
    return m.group(1) if m else event_name


def module_calls(mods: Sequence[Event], name: str, lo: float,
                 hi: float) -> List[Event]:
    """Executions of program ``name`` (e.g. ``jit_pack_tokens``) that start
    inside [lo, hi], in time order."""
    return sorted((e for e in mods if module_name(e.name) == name
                   and lo <= e.start < hi), key=lambda e: e.start)


def op_label(event_name: str) -> str:
    """Short name of an HLO op event: ``%fusion.661 = bf16[8,1024,...]...``
    -> ``fusion.661 bf16[8,1024,...]`` (cut to 80 characters)."""
    head, _, rest = event_name.partition(" = ")
    return (head.lstrip("%") + " " + rest.split(" ")[0])[:80].strip()


def top_ops(ops: Sequence[Event], lo: float, hi: float,
            n: int = 10) -> List[Tuple[str, float]]:
    total: Dict[str, float] = {}
    for s, e, name in ((max(x.start, lo), min(x.end, hi), x.name) for x in ops):
        if e > s:
            key = op_label(name)
            total[key] = total.get(key, 0.0) + (e - s)
    return sorted(total.items(), key=lambda kv: -kv[1])[:n]


def named_gaps(ops: Sequence[Event], spans: Sequence[Event], lo: float,
               hi: float, n: int = 10) -> List[Tuple[str, float]]:
    """The ``n`` longest idle gaps, each named by the innermost host span
    open at its midpoint (``other`` where none is)."""
    out = []
    for s, e in gaps(ops, lo, hi):
        mid = 0.5 * (s + e)
        open_ = [x for x in spans if x.start <= mid < x.end]
        name = min(open_, key=lambda x: x.end - x.start).name if open_ else "other"
        out.append((name, e - s))
    return sorted(out, key=lambda kv: -kv[1])[:n]


def span_bounds(spans: Sequence[Event], name: str) -> Optional[Interval]:
    hits = [x for x in spans if x.name == name]
    if not hits:
        return None
    return (min(x.start for x in hits), max(x.end for x in hits))
