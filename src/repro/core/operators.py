"""Ingestion operator base: the paper's iterator model (Sec. III).

    IngestOp: LID -> LID'   with API
      initialize / setInput / hasNext / next / finalize

Operators are *vectorized* internally (DESIGN.md §2) — ``next()`` yields whole
labelled items (usually CHUNK/BLOCK granularity) — but the control-plane
contract is exactly the paper's iterator API so the runtime, optimizer, and
fault-tolerance machinery reason about operators uniformly.

Each operator also carries:
  * ``name``        — the label key it writes (``l_<name>`` in the language),
  * ``mode``        — SERIAL or PARALLEL (paper Sec. VI-A intra-node parallelism),
  * ``granularity_in/out`` — used by the pipelining rule (materialize only at
    granularity changes, paper Sec. V) and by plan validation (Sec. IV-A:
    consecutive operators must match in granularity/schema),
  * ``expansion``   — data-volume factor estimate used by the reordering rule
    (push-down reducers / push-up expanders, paper Sec. V).
"""
from __future__ import annotations

import enum
import threading
from collections import deque
from concurrent.futures import ThreadPoolExecutor
from typing import (Any, Deque, Dict, Iterable, Iterator, List, Optional,
                    Sequence, Tuple)

from .. import tracing
from .items import Granularity, IngestItem


class OpMode(enum.Enum):
    SERIAL = "serial"
    PARALLEL = "parallel"


class OperatorFailure(RuntimeError):
    """Raised by an operator when processing fails (drives in-flight FT)."""


class BatchFallback(RuntimeError):
    """Raised by ``process_batch`` when a batch cannot run vectorized (e.g. a
    payload type the kernel path does not cover).  The caller falls back to
    the scalar iterator path for that operator — the batch tier degrades, it
    never fails (ISSUE 7)."""


class IngestOp:
    """Base ingestion operator implementing the paper's iterator API."""

    #: label key; subclasses override (e.g. "filter", "serialize")
    name: str = "op"
    #: granularity contract; None = any / unchanged
    granularity_in: Optional[Granularity] = None
    granularity_out: Optional[Granularity] = None
    #: estimated output/input volume ratio (<1 reducer, >1 expander)
    expansion: float = 1.0
    #: CPU-heavy operators default to parallel mode (paper Sec. VI-A)
    cpu_heavy: bool = False
    #: operators that publish into the DataStore; stages containing one form
    #: the commit-side segment the epoch pipeliner may overlap (DESIGN.md §4)
    commit_side: bool = False
    #: operators with a vectorized ``process_batch`` the VectorizeRule may
    #: select into a batch-mode pipeline block (ISSUE 7); the scalar iterator
    #: path stays as the fallback and correctness oracle
    batch_capable: bool = False

    def __init__(self, **params: Any) -> None:
        self.params: Dict[str, Any] = params
        self.mode: OpMode = OpMode.PARALLEL if self.cpu_heavy else OpMode.SERIAL
        # num_threads stays IN params: clone() and the process-backend
        # __reduce__ rebuild from params, so popping it here silently reset
        # cloned/shipped operators to the default pool width
        self.num_threads: int = int(params.get("num_threads", 4))
        self._inputs: List[IngestItem] = []
        self._outputs: Iterator[IngestItem] = iter(())
        self._pending: Deque[IngestItem] = deque()
        self._pool: Optional[ThreadPoolExecutor] = None
        self._initialized = False
        self._finalized_ok = False  # runtime FT tracks finalize success (Sec. VI-C)
        # test hook: fail the next N process() calls (fault injection)
        self._fail_next: int = 0
        # device kernel launches (batch tier); the runtime diffs this around
        # a batch block to charge RunReport.kernel_calls
        self.kernel_calls: int = 0

    # ------------------------------------------------------------ iterator API
    def initialize(self) -> None:
        """Initialize the operator for the first time."""
        self._initialized = True
        self._finalized_ok = False

    def set_input(self, items: Sequence[IngestItem]) -> None:
        """Assign the set of input ingest data items."""
        if not self._initialized:
            self.initialize()
        self._inputs = list(items)
        self._outputs = self._make_output_iter()

    # paper naming
    setInput = set_input

    def has_next(self) -> bool:
        if self._pending:
            return True
        try:
            self._pending.append(next(self._outputs))
            return True
        except StopIteration:
            return False

    hasNext = has_next

    def next(self) -> IngestItem:
        if not self.has_next():
            raise StopIteration
        return self._pending.popleft()

    def finalize(self) -> None:
        """Cleanup; parallel-mode threads are joined here (paper Sec. VI-A)."""
        self._inputs = []
        self._pending = deque()
        self._outputs = iter(())
        if self._pool is not None:
            self._pool.shutdown(wait=True)
            self._pool = None
        self._finalized_ok = True

    # --------------------------------------------------------------- execution
    def _make_output_iter(self) -> Iterator[IngestItem]:
        if self.mode is OpMode.PARALLEL and len(self._inputs) > 1:
            return self._parallel_iter()
        return self._serial_iter()

    def _serial_iter(self) -> Iterator[IngestItem]:
        for item in self._inputs:
            yield from self._process_guarded(item)

    def _ensure_pool(self) -> ThreadPoolExecutor:
        """Lazily-created worker pool, reused across ``set_input`` calls and
        joined in ``finalize()`` — one pool per run instead of one per batch
        (pool churn on every epoch x stage x node)."""
        if self._pool is None:
            self._pool = ThreadPoolExecutor(max_workers=self.num_threads)
        return self._pool

    def _parallel_iter(self) -> Iterator[IngestItem]:
        """Thread-pool processing of independent items; order preserved."""
        pool = self._ensure_pool()
        futures = [pool.submit(lambda it=item: list(self._process_guarded(it)))
                   for item in self._inputs]
        for fut in futures:
            yield from fut.result()

    def _process_guarded(self, item: IngestItem) -> Iterable[IngestItem]:
        if self._fail_next > 0:
            self._fail_next -= 1
            raise OperatorFailure(f"{self.name}: injected failure")
        return self.process(item)

    # ----------------------------------------------------------- to implement
    def process(self, item: IngestItem) -> Iterable[IngestItem]:
        """Transform one labelled ingest data item into zero or more outputs."""
        raise NotImplementedError

    # ------------------------------------------------------- batch tier (ISSUE 7)
    def process_batch(self, items: Sequence[IngestItem]) -> List[IngestItem]:
        """Transform a whole batch at once.  ``batch_capable`` operators
        override this with a vectorized implementation (and may raise
        ``BatchFallback`` for inputs the vectorized path does not cover);
        the default is the scalar loop, so a dummy substituted into a
        batch-mode block still runs correctly."""
        out: List[IngestItem] = []
        for item in items:
            out.extend(self.process(item))
        return out

    def run_batch(self, items: Sequence[IngestItem]) -> List[IngestItem]:
        """Batch-mode twin of ``run``: one ``process_batch`` call instead of
        the per-item iterator drain.  Same lifecycle (initialize/finalize,
        ``_fail_next`` fault hook) so the runtime's retry-from-checkpoint and
        dummy-substitution machinery treat both paths identically."""
        self.initialize()
        if self._fail_next > 0:
            self._fail_next -= 1
            raise OperatorFailure(f"{self.name}: injected failure")
        out = list(self.process_batch(list(items)))
        self.finalize()
        return out

    # ------------------------------------------------------------------- misc
    def run(self, items: Sequence[IngestItem]) -> List[IngestItem]:
        """Convenience: drive the full iterator protocol over ``items``."""
        self.initialize()
        self.set_input(items)
        out: List[IngestItem] = []
        while self.has_next():
            out.append(self.next())
        self.finalize()
        return out

    def clone(self) -> "IngestOp":
        """Fresh instance with the same parameters (operators are re-instantiable
        from their params — the catalog stores params, not instances; Sec. VII)."""
        op = type(self)(**dict(self.params))
        op.mode = self.mode
        return op

    def __reduce__(self):
        """Operators pickle as (type, params, mode) — exactly the catalog
        contract — so shipping a plan to a worker process re-instantiates
        fresh operator state there (the process backend's launch_remote).
        Closure-valued params (a lambda predicate) fail here by design:
        ``assert_picklable_plan`` turns that into an actionable error."""
        return (_rebuild_op, (type(self), dict(self.params), self.mode))

    def signature(self) -> Dict[str, Any]:
        return {"type": type(self).__name__, "name": self.name,
                "params": {k: repr(v) for k, v in self.params.items()},
                "mode": self.mode.value}

    def __repr__(self) -> str:
        ps = ", ".join(f"{k}={v!r}" for k, v in self.params.items())
        return f"{type(self).__name__}({ps})"


def _rebuild_op(cls: type, params: Dict[str, Any], mode: OpMode) -> "IngestOp":
    op = cls(**params)
    op.mode = mode
    return op


def resolve_callable(spec: Any) -> Any:
    """Resolve a picklable callable spec.

    Accepts a callable (returned unchanged — fine for thread backends, only
    picklable if it is a module-level function) or an import spec string
    ``"package.module:attr"`` resolved at call time.  Spec strings are what
    make FilterOp / MapOp / ParserOp params cross process boundaries.
    """
    if isinstance(spec, str):
        mod, _, attr = spec.partition(":")
        if not attr:
            raise ValueError(
                f"callable spec {spec!r} must look like 'pkg.module:attr'")
        import importlib
        obj = importlib.import_module(mod)
        for part in attr.split("."):
            obj = getattr(obj, part)
        if not callable(obj):
            raise TypeError(f"callable spec {spec!r} resolved to non-callable {obj!r}")
        return obj
    return spec


class PassThroughOp(IngestOp):
    """The paper's *dummy pass-through operator* (Sec. VI-C): substituted for an
    operator that failed repeatedly; labels every item with -1 to mark the failure."""

    name = "dummy"

    def __init__(self, replaces: str = "op", **kw: Any) -> None:
        super().__init__(replaces=replaces, **kw)
        self.replaces = replaces

    def process(self, item: IngestItem) -> Iterable[IngestItem]:
        yield item.with_label(self.replaces, -1)


class MaterializeOp(IngestOp):
    """Materialization barrier inserted between operators (paper Sec. V).

    By default every operator boundary materializes; the pipelining rule removes
    barriers between same-granularity operators.  Each surviving barrier is also
    an in-flight checkpoint (Sec. VI-C1): the runtime snapshots items here.
    """

    name = "materialize"

    def __init__(self, **kw: Any) -> None:
        super().__init__(**kw)
        self.buffer: List[IngestItem] = []

    def process(self, item: IngestItem) -> Iterable[IngestItem]:
        self.buffer.append(item)
        yield item


def op_span(op: IngestOp, items: Sequence[IngestItem]):
    """The ``ib.op.<class>`` span around one operator call, with the rows
    it takes in (counted only while spans are recorded)."""
    if not tracing.recording():
        return tracing.NOOP
    return tracing.span("ib.op." + type(op).__name__,
                        rows=sum(it.nrows() for it in items))


def run_ops_batched(ops: Sequence[IngestOp], items: Sequence[IngestItem]
                    ) -> Tuple[List[IngestItem], Dict[str, Any]]:
    """Execute one batch-mode pipeline block (ISSUE 7).

    Shared by the thread backend (``RuntimeEngine._run_stage``) and the
    process backend's worker (``procexec._run_stage_ops``).  Each op runs
    ``run_batch``; a ``BatchFallback`` drops that op back to the scalar
    iterator path (counted — the block as a whole still succeeds).
    ``OperatorFailure`` propagates so both backends' retry-from-checkpoint
    machinery applies unchanged.

    Returns ``(out, stats)`` with ``vectorized_rows`` (rows entering the
    block), ``batch_fallbacks`` and ``kernel_calls`` (device kernel
    launches the block's ops made).
    """
    rows = sum(it.nrows() for it in items)
    calls_before = sum(op.kernel_calls for op in ops)
    fallbacks = 0
    out: List[IngestItem] = list(items)
    for op in ops:
        with op_span(op, out):
            try:
                out = op.run_batch(out)
            except BatchFallback:
                fallbacks += 1
                out = op.run(out)
    return out, {"vectorized_rows": rows, "batch_fallbacks": fallbacks,
                 "kernel_calls": sum(op.kernel_calls for op in ops)
                 - calls_before}


# ----------------------------------------------------------------------------
# Operator registry: the language front-end resolves names (e.g. SERIALIZE AS
# "columnar") through this registry; users register custom operators the same
# way (paper Sec. IV-A: parser/filter/projection/replicator may be custom ops).
# ----------------------------------------------------------------------------
_REGISTRY: Dict[str, type] = {}


def register_op(key: str):
    def deco(cls: type) -> type:
        _REGISTRY[key] = cls
        return cls
    return deco


def resolve_op(__op_key: str, **params: Any) -> IngestOp:
    if __op_key not in _REGISTRY:
        raise KeyError(f"unknown ingestion operator {__op_key!r}; registered: {sorted(_REGISTRY)}")
    return _REGISTRY[__op_key](**params)


def registered_ops() -> List[str]:
    return sorted(_REGISTRY)
