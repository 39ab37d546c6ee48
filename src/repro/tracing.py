"""Program spans: where an operator's host time goes, per epoch and per batch.

A span names one piece of work at batch or epoch granularity::

    from repro import tracing
    with tracing.span("ib.op.PackOp", rows=n):
        ...
        tracing.annotate(bytes=written)    # counts on the innermost span

Recording is on exactly while a JAX profiler session collects host events
(``jax.profiler.trace`` / ``start_trace``, or a client of
``jax.profiler.start_server``).  Off, a span costs one check and returns a
shared no-op context; a process that has not imported JAX records nothing.
On, each span

* opens a ``jax.profiler.TraceAnnotation`` of the same name, so it sits on
  the profiler's host plane, on the device trace's clock, beside the device
  ops it launched, and
* on exit appends one :class:`SpanRecord` (clock: ``time.perf_counter_ns``)
  to a bounded in-memory list, read with :func:`records` and reset with
  :func:`clear`.  A span is kept only if recording was on at both its ends,
  so a recorded span holds every span nested in it.

Parents follow ``contextvars``: a span opened inside another is its child,
on the same thread or on a worker thread that runs a job in the
submitter's context (the node executor's lanes do, while recording is on).
Every span name starts with ``ib.``.
"""
from __future__ import annotations

import contextlib
import contextvars
import itertools
import sys
import threading
import time
from dataclasses import dataclass, field
from typing import Any, Callable, Dict, List, Optional

#: records kept; spans past the bound are counted in :func:`dropped`
LIMIT = 1 << 16


@dataclass(frozen=True)
class SpanRecord:
    id: int
    parent: Optional[int]
    name: str
    thread: str
    start_ns: int
    end_ns: int
    attrs: Dict[str, Any] = field(default_factory=dict)


_records: List[SpanRecord] = []
_dropped = 0
_lock = threading.Lock()
_ids = itertools.count(1)
_current: "contextvars.ContextVar[Optional[_Span]]" = contextvars.ContextVar(
    "repro_tracing_span", default=None)
_enabled: Optional[Callable[[], bool]] = None


def recording() -> bool:
    """Whether a span opened now is recorded: a JAX profiler session is
    collecting host events in this process."""
    global _enabled
    if _enabled is None:
        prof = sys.modules.get("jax.profiler")
        if prof is None:
            return False
        _enabled = prof.TraceAnnotation.is_enabled
    return _enabled()


#: what a span is while recording is off
NOOP = contextlib.nullcontext()


class _Span:
    __slots__ = ("id", "parent", "name", "attrs", "start_ns", "_ann",
                 "_token")

    def __init__(self, name: str, attrs: Dict[str, Any]) -> None:
        self.name, self.attrs = name, attrs

    def __enter__(self) -> "_Span":
        from jax.profiler import TraceAnnotation
        self._ann = TraceAnnotation(self.name)
        self._ann.__enter__()
        up = _current.get()
        self.parent = up.id if up is not None else None
        self.id = next(_ids)
        self._token = _current.set(self)
        self.start_ns = time.perf_counter_ns()
        return self

    def __exit__(self, *exc: Any) -> bool:
        end = time.perf_counter_ns()
        _current.reset(self._token)
        self._ann.__exit__(*exc)
        if recording():
            _append(SpanRecord(self.id, self.parent, self.name,
                               threading.current_thread().name,
                               self.start_ns, end, self.attrs))
        return False


def _append(rec: SpanRecord) -> None:
    global _dropped
    with _lock:
        if len(_records) < LIMIT:
            _records.append(rec)
        else:
            _dropped += 1


def span(name: str, **attrs: Any):
    """A context manager timing ``name``, recorded while recording is on."""
    if not recording():
        return NOOP
    return _Span(name, attrs)


def annotate(**counts: Any) -> None:
    """Add ``counts`` to the innermost open span (nothing when none is)."""
    s = _current.get()
    if s is None:
        return
    for k, v in counts.items():
        s.attrs[k] = s.attrs.get(k, 0) + v


def records() -> List[SpanRecord]:
    """The recorded spans, in the order they closed."""
    with _lock:
        return list(_records)


def dropped() -> int:
    """Spans not kept because the list was full."""
    return _dropped


def clear() -> None:
    """Forget every record and the dropped count."""
    global _dropped
    with _lock:
        _records.clear()
        _dropped = 0
