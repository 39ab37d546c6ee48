"""The INGESTBASE runtime engine (paper Sec. VI).

* **Inter-node parallelism** — the client ships the *optimized* plan to every
  node in the slaves list and runs it over node-local shards ("ship the plan
  to the data").  Nodes here are persistent ``NodeExecutor`` workers over
  per-node directories; the remote-shell seam is ``launch_remote``
  (DESIGN.md §2), invoked once per compiled plan, not once per stage barrier.
  ``backend="process"`` realizes the seam with one long-lived worker
  *process* per node (``core/procexec.py``, DESIGN.md §6) — real CPU
  parallelism for GIL-bound operators; ``backend="thread"`` is the default.
* **Intra-node parallelism** — parallel-mode operators fan out over a thread
  pool (see operators.IngestOp._parallel_iter).
* **Work stealing** — when sources are given as a shared list, nodes pull
  shards from a global queue, so stragglers simply take fewer shards.
* **Distributed I/O** — shuffle via the ``ShuffleCoordinator`` control plane
  (DESIGN.md §4): node workers partition their own output by the plan's
  routing key and exchange partitions peer-to-peer (shared-memory segments /
  in-memory deposits / DFS spill files past the per-edge share); the
  coordinator relays only manifests — zero item bytes cross its pipes on
  the shuffle path.  ``synchronous=True`` (and cross-segment boundaries)
  fall back to the legacy coordinator barrier.  Placement via location IDs,
  replication decoupled from placement.
* **In-flight fault tolerance** — pipeline blocks are checkpoints: a failing
  operator retries its block from the previous materialization; after
  ``max_retries`` failures it is replaced by a dummy pass-through operator
  labelling items with -1.  Node failures reassign shards + location IDs to
  the next node in the slaves order.
"""
from __future__ import annotations

import contextvars
import itertools
import os
import pickle
import queue
import shutil
import threading
import time
from collections import defaultdict
from concurrent.futures import Future
from dataclasses import dataclass, field
from typing import Any, Callable, Dict, Iterable, List, Optional, Sequence, Set, Tuple, Union

from .. import tracing
from .exchange import (PartitionExchange, build_manifest, columnar_file_name,
                       exchange_file_name, partition_items, resident_file_name,
                       unlink_segment, write_columnar_file,
                       write_partition_file)
from .items import ColumnarBatch, IngestItem, items_nbytes
from .operators import (IngestOp, OperatorFailure, PassThroughOp, op_span,
                        run_ops_batched)
from .optimizer import IngestionOptimizer
from .plan import (IngestPlan, StagePlan, failed_op_index, route_items,
                   stage_consumers)
from .procexec import ProcessNodeExecutor, WorkerDeath
from .sources import ShardDescriptor, SourceAdapter, build_source
from .store import DataStore


class NodeFailure(RuntimeError):
    """Simulated machine failure during ingestion.

    ``stage_index`` records which stage the death surfaced at (None when it
    happened outside stage execution, e.g. at plan install): the streaming
    engine's lineage-cone recovery needs to know whether the survivors had
    already completed the ingest segment when the node died (ISSUE 8)."""

    stage_index: Optional[int] = None


class _CohortReplay(RuntimeError):
    """Batch-mode recovery escalation (ROADMAP "batch shuffle cohort
    replay"): a node died at or after a shuffle-consuming stage, so its
    processed groups mixed other nodes' lineages and cannot be recovered
    from its own source shards.  The only exact recovery is replaying the
    whole run as one epoch on the survivors — ``RuntimeEngine.run`` catches
    this, aborts the run's staged epoch, invalidates its exchange rounds,
    and re-executes on the live set."""


#: legacy static shuffle spill threshold (used when no memory budget is set)
DEFAULT_SPILL_BYTES = 32 << 20
#: floor under budget-derived spill thresholds — a tiny budget must not turn
#: every shuffle round into a blocking DFS round-trip
MIN_SPILL_BYTES = 1 << 20


def derive_spill_bytes(memory_budget_bytes: int, reserved_bytes: int = 0) -> int:
    """Shuffle spill threshold from a shared memory budget: whatever the
    ingest queues are expected to hold (``reserved_bytes``) is carved out
    first, the remainder bounds in-memory shuffle rounds (ROADMAP
    "spill-aware shuffle sizing")."""
    return max(MIN_SPILL_BYTES, int(memory_budget_bytes) - int(reserved_bytes))


@dataclass
class RunReport:
    """What the engine observed while executing a plan."""

    stage_items: Dict[str, int] = field(default_factory=dict)
    op_failures: Dict[str, int] = field(default_factory=dict)
    dummy_substitutions: List[str] = field(default_factory=list)
    node_failures: List[str] = field(default_factory=list)
    reassigned_shards: int = 0
    shuffled_items: int = 0
    shuffle_spills: int = 0        # rounds that materialized DFS spill files
    shuffle_async_rounds: int = 0  # rounds handled fully off the DFS
    shuffle_exchange_rounds: int = 0   # peer-to-peer exchange rounds
    # item bytes the *coordinator's* shuffle path moved (legacy barrier only
    # — a peer-exchange round keeps this at zero: the coordinator relays
    # manifests, never item bytes)
    shuffle_coordinator_bytes: int = 0
    # partition bytes handed worker-to-worker (shm segments, spill files,
    # and the thread backend's direct in-memory deposits)
    shuffle_peer_bytes: int = 0
    # --- node-resident dataflow (ISSUE 5): narrow stage edges -------------
    # item bytes that crossed a coordinator pipe at a *stage boundary*
    # (stage outputs returned to / re-shipped from the coordinator).  With
    # the resident exchange plane this stays zero end-to-end: only the
    # final store-stage registration metadata reaches the coordinator.
    stage_coordinator_bytes: int = 0
    stage_exchange_rounds: int = 0     # narrow (identity-routed) rounds
    stage_resident_bytes: int = 0      # bytes kept node-resident across edges
    resident_spills: int = 0           # resident buckets spilled to the DFS
    cohort_replays: int = 0            # batch whole-run replays (post-shuffle death)
    # --- lineage-cone recovery + liveness (ISSUE 8) -------------------------
    cone_replays: int = 0              # deaths repaired by a cone patch alone
    replayed_rows: int = 0             # rows re-executed by recovery (cone or epoch)
    spawn_retries: int = 0             # worker spawn attempts beyond the first
    # --- worker-pull sources (ISSUE 6): the source hop ---------------------
    # item bytes the coordinator routed on the source hop.  Descriptor-backed
    # sources keep this at zero on both backends — the coordinator hands out
    # shard descriptors, workers read the data; only the legacy pushed-
    # iterator path (feed joints, raw iterators) still counts bytes here.
    source_coordinator_bytes: int = 0
    source_descriptors: int = 0        # shard descriptors issued to workers
    source_reissues: int = 0           # descriptors re-issued after a reader death
    source_items: int = 0              # items workers materialized from descriptors
    # --- batch operator tier (ISSUE 7): optimizer-selected vectorization ----
    vectorized_rows: int = 0           # rows that entered batch-mode blocks
    batch_fallbacks: int = 0           # ops that dropped back to the scalar path
    kernel_calls: int = 0              # kernel launches in batch blocks
    # --- columnar data plane (ISSUE 10): column buffers across stage edges --
    columnar_rounds: int = 0           # exchange rounds with >=1 columnar part
    columnar_bytes: int = 0            # partition bytes that crossed columnar
    columnar_fallbacks: int = 0        # producers that fell back to items
    # --- socket fabric + degraded exchange (ISSUE 9) ------------------------
    degraded_exchange_rounds: int = 0  # rounds with >=1 streamed (cross-host) part
    degraded_peer_bytes: int = 0       # partition bytes that crossed host-to-host
    sweep_skipped_remote: int = 0      # shm sweeps skipped: worker not local
    wall_time_s: float = 0.0
    per_node_shards: Dict[str, int] = field(default_factory=dict)


@dataclass
class FaultInjection:
    """Test hooks: deterministic failures."""

    # (stage_name, op_index) -> number of consecutive failures to inject
    op_failures: Dict[Tuple[str, int], int] = field(default_factory=dict)
    # node -> stage name after which the node dies
    node_death_after_stage: Dict[str, str] = field(default_factory=dict)


# --------------------------------------------------------------------------
# Persistent node executors (DESIGN.md §4)
# --------------------------------------------------------------------------
class _ExecutorLane:
    """One FIFO worker thread: jobs run in submission order."""

    def __init__(self, name: str) -> None:
        self.jobs: "queue.Queue[Optional[Tuple[Callable, tuple, Future]]]" = queue.Queue()
        self.thread = threading.Thread(target=self._loop,
                                       name=f"nodeexec-{name}", daemon=True)
        self.thread.start()

    def submit(self, fn: Callable, *args: Any) -> Future:
        fut: Future = Future()
        if tracing.recording():
            # the job runs in the submitter's context: spans it opens nest
            # under the span open where it was submitted
            fn, args = contextvars.copy_context().run, (fn,) + args
        self.jobs.put((fn, args, fut))
        return fut

    def _loop(self) -> None:
        while True:
            job = self.jobs.get()
            if job is None:
                return
            fn, args, fut = job
            if not fut.set_running_or_notify_cancel():
                continue
            try:
                fut.set_result(fn(*args))
            except BaseException as e:  # delivered via Future.result()
                fut.set_exception(e)

    def stop(self) -> None:
        self.jobs.put(None)


class NodeExecutor:
    """One long-lived worker per node, owning the node's plan clone.

    The plan-clone cache is bounded (``PLAN_CACHE``): a long-lived engine
    running many different plans re-clones an evicted one instead of pinning
    every plan it ever saw.

    The engine used to create a fresh ``ThreadPoolExecutor`` at every stage
    barrier and re-clone ("re-ship") the whole plan per ``_execute`` call.  A
    NodeExecutor instead persists for the engine's lifetime and owns

    * the node's **plan clone** — installed once per compiled plan, so
      streaming epochs stop re-shipping plans (operator state, including
      dummy substitutions after repeated failures, survives across epochs
      exactly as it would in a long-running per-node JVM), and
    * one or more **lanes** — named FIFO worker threads.  Batch stages run on
      the default ``"main"`` lane; the pipelined streaming engine runs epoch
      N+1's ingest segment on the ``"ingest"`` lane while epoch N's store
      segment occupies the ``"store"`` lane, overlapping transform compute
      with commit I/O on every node (DESIGN.md §4).
    """

    PLAN_CACHE = 4

    def __init__(self, node: str) -> None:
        self.node = node
        self._lock = threading.Lock()
        self._lanes: Dict[str, _ExecutorLane] = {}
        # id(original) -> (original, clone); the original is pinned so its id
        # cannot be recycled while the cache entry lives
        self._plans: Dict[int, Tuple[List[StagePlan], List[StagePlan]]] = {}

    def install_plan(self, stage_plans: List[StagePlan],
                     cloner: Callable[[str, List[StagePlan]], List[StagePlan]]
                     ) -> List[StagePlan]:
        """This node's clone of ``stage_plans`` — cloned on first sight only
        ("ship the plan to the data" happens once, not per barrier)."""
        key = id(stage_plans)
        with self._lock:
            cached = self._plans.get(key)
            if cached is not None and cached[0] is stage_plans:
                return cached[1]
            clone = cloner(self.node, stage_plans)
            while len(self._plans) >= self.PLAN_CACHE:   # bounded: evict oldest
                self._plans.pop(next(iter(self._plans)))
            self._plans[key] = (stage_plans, clone)
            return clone

    def submit(self, fn: Callable, *args: Any, lane: str = "main") -> Future:
        with self._lock:
            ln = self._lanes.get(lane)
            if ln is None:
                ln = self._lanes[lane] = _ExecutorLane(f"{self.node}:{lane}")
        return ln.submit(fn, *args)

    def shutdown(self) -> None:
        with self._lock:
            lanes, self._lanes = list(self._lanes.values()), {}
            self._plans.clear()
        for ln in lanes:
            ln.stop()


# --------------------------------------------------------------------------
# Shuffle: control-plane coordinator + worker-side data plane (DESIGN.md §4)
# --------------------------------------------------------------------------
@dataclass
class ExchangeRound:
    """Control-plane record of one peer-to-peer exchange round.

    Since ISSUE 5 a round covers *any* stage edge, not just shuffles:
    ``key=None`` is a **narrow** round (identity routing — every producer's
    output stays resident on its own node), a non-None key partitions across
    the peers.  ``pinned=True`` marks a round whose consuming stage lies
    outside the slice that produced it (the ingest/store segment boundary):
    it survives the ``_execute`` call in the coordinator's pinned registry
    and the next slice adopts it.

    Everything here is metadata: stage/epoch identity, the pinned target
    set, per-producer manifests (counts, sizes, segment/file refs), and the
    consumer-delivery cursor.  Item bytes never enter this structure."""

    xid: int
    stage: str
    key: Optional[str]                # routing key; None = narrow (identity)
    epoch: int                        # -1 = batch run
    targets: List[str]                # pinned executing-node set = partition targets
    consumers: List[str]              # ALL consuming stage names (DAG order)
    spill_share: int                  # per-edge spill threshold, bytes
    pinned: bool = False              # consumed (partly) by a later slice
    manifests: Dict[str, Dict[str, Any]] = field(default_factory=dict)
    total_count: int = 0              # items partitioned (all producers)
    total_bytes: int = 0              # peer-bound partition bytes
    resident_bytes: int = 0           # bytes that stayed on their own node
    served: Dict[str, int] = field(default_factory=dict)   # node -> stages served
    # nodes that were ever handed refs — unlike `served` (reset when a
    # consumer fails, so finish_round reclaims best-effort), this is never
    # cleared: refs once delivered may already be consumed and must not be
    # re-served to a redirect target
    delivered: Set[str] = field(default_factory=set)
    consumers_done: int = 0
    spilled: bool = False
    degraded_parts: int = 0           # cross-host (streamed) partitions
    degraded_bytes: int = 0           # their bytes (subset of total_bytes)
    # columnar data plane (ISSUE 10): the optimizer proved every consuming
    # block pair batch-capable, so producers may cross this edge as a
    # ColumnarBatch (column buffers, no per-item pickling).  A producer whose
    # output doesn't pack (mixed payload kinds, exotic labels) falls back to
    # the scalar path per-manifest — counted, never wrong.
    columnar: bool = False
    columnar_parts: int = 0           # partitions that crossed as column buffers
    columnar_bytes: int = 0           # their bytes (subset of total+resident)
    columnar_fallbacks: int = 0       # producers that fell back to item lists

    def worker_ctx(self, spill_dir: str,
                   hosts: Optional[Dict[str, str]] = None) -> Dict[str, Any]:
        """The shuffle instruction shipped to a producing worker.  ``hosts``
        (node -> host label, ISSUE 9) tells the worker which targets are NOT
        shm-reachable: partitions for another host go degraded (spill file +
        stream endpoint) instead of a shared-memory segment."""
        ctx = {"xid": self.xid, "key": self.key,
               "targets": list(self.targets), "epoch": self.epoch,
               "spill_share": self.spill_share, "spill_dir": spill_dir}
        if hosts:
            ctx["hosts"] = dict(hosts)
        if self.columnar:
            ctx["columnar"] = True
        return ctx


def _desc_paths(desc: Dict[str, Any]) -> List[str]:
    """Every spill path a partition descriptor references: the primary
    ``path``/``spilled`` plus the ``extra_paths`` a manifest merge stacked
    (ISSUE 8 cone patches deal into an already-recorded round)."""
    paths: List[str] = []
    p = desc.get("path") or desc.get("spilled")
    if p:
        paths.append(p)
    paths.extend(desc.get("extra_paths", ()))
    return paths


def _merge_manifest(prev: Dict[str, Any], fresh: Dict[str, Any]) -> None:
    """Fold a producer's second manifest for the same round into its first
    (the node-side buckets extended on deposit, so the union is what the
    consumers will actually collect)."""
    prev["total_count"] = (int(prev.get("total_count", 0))
                           + int(fresh.get("total_count", 0)))
    parts = prev.setdefault("parts", {})
    for dst, desc in fresh.get("parts", {}).items():
        have = parts.get(dst)
        if have is None:
            parts[dst] = desc
            continue
        have["count"] = int(have.get("count", 0)) + int(desc.get("count", 0))
        have["nbytes"] = (int(have.get("nbytes", 0))
                          + int(desc.get("nbytes", 0)))
        known = set(_desc_paths(have))
        for p in _desc_paths(desc):
            if p not in known:
                have.setdefault("extra_paths", []).append(p)


class ShuffleCoordinator:
    """The shuffle's *control plane* (DESIGN.md §4).

    Since ISSUE 4 the default data path is a **decentralized peer exchange**:
    after a shuffle-boundary stage, each node worker partitions its own
    output by the plan's routing key (``StagePlan.shuffle_key``) and hands
    partitions directly to peer workers — per-edge shared-memory segments
    (process backend, ``exchange.encode_partition``) or direct in-memory
    deposits (thread backend), with oversized partitions crossing as
    peer-readable spill files under the DFS dir.  This coordinator only

    * opens a round per boundary (``plan_round``) and pins its target set,
    * collects per-producer **manifests** — stage, epoch, counts, sizes,
      segment names / file paths — never item bytes,
    * hands each consumer its incoming refs (``refs_for`` / ``serve``), and
    * reclaims a round's segments/files when it finishes or its epoch is
      invalidated (node death -> epoch replay).

    The **legacy barrier** (groups collected and redistributed through the
    coordinator) remains for two cases: ``synchronous=True`` (the paper-
    verbatim in-barrier DFS round-trip, kept for debugging and as the
    benchmark baseline) and boundaries whose consuming stage lies outside
    the executing stage slice (cross-segment shuffles), where the items
    must outlive the worker call anyway.  Only this legacy path moves item
    bytes through the coordinator — counted in
    ``RunReport.shuffle_coordinator_bytes``, which a peer-exchange round
    keeps at zero.
    """

    def __init__(self, store: DataStore, spill_bytes: int = 32 << 20,
                 synchronous: bool = False, columnar: bool = True) -> None:
        self.store = store
        self.spill_bytes = spill_bytes
        self.synchronous = synchronous
        #: columnar data plane master switch (ISSUE 10): when False every
        #: round stays item-at-a-time — the byte-identical oracle path
        self.columnar = columnar
        self._lock = threading.Lock()
        self._stage_locks: Dict[str, threading.Lock] = {}
        self._pending: Dict[str, Future] = {}
        self._writer: Optional[_ExecutorLane] = None
        self._spilled_stages: set = set()   # stages with DFS group files
        self._xids = itertools.count()
        self._rounds: Dict[int, ExchangeRound] = {}
        self._epoch_rounds: Dict[int, Set[int]] = {}
        # rounds pinned across _execute slices, keyed (epoch, producing
        # stage): the ingest segment leaves them here, the store segment
        # adopts them (ISSUE 5 cross-segment exchange)
        self._pinned: Dict[Tuple[int, str], ExchangeRound] = {}
        #: test hook: called as (round, producer_node) when a manifest lands
        #: — lets fault tests kill a worker exactly mid-exchange
        self.test_on_manifest: Optional[Callable[[ExchangeRound, str], None]] = None

    # ------------------------------------------------------------------ util
    def _stage_lock(self, stage: str) -> threading.Lock:
        with self._lock:
            lk = self._stage_locks.get(stage)
            if lk is None:
                lk = self._stage_locks[stage] = threading.Lock()
            return lk

    def _writer_lane(self) -> _ExecutorLane:
        with self._lock:
            if self._writer is None:
                self._writer = _ExecutorLane("shuffle-journal")
            return self._writer

    def _dfs_dir(self, stage: str) -> str:
        return os.path.join(self.store.dfs_dir, f"shuffle_{stage}")

    @staticmethod
    def _shuffle_key(sp: StagePlan) -> Optional[str]:
        return sp.shuffle_key or sp.compute_shuffle_key()

    # ------------------------------------------- peer-exchange control plane
    def plan_round(self, stage_plans: List[StagePlan], si: int, stop: int,
                   live: List[str],
                   epoch: Optional[int]) -> Optional[ExchangeRound]:
        """Open a peer-exchange round for stage ``si``'s outgoing edges.

        Since ISSUE 5 every edge gets a round: shuffle edges partition by
        the routing key (``shuffle_key``), narrow edges keep the output
        resident on the producing node (``key=None``), and an edge whose
        consumer lies outside the executing slice [si+1, stop) pins the
        round across slices instead of falling back to the coordinator
        barrier.  Returns None only for terminal stages (no consumer in the
        DAG) and in ``synchronous`` legacy mode."""
        sp = stage_plans[si]
        if self.synchronous or not sp.ops or not live:
            return None
        consumers = stage_consumers(stage_plans, si)
        if not consumers:
            return None
        in_slice = {stage_plans[j].name for j in range(si + 1, stop)}
        pinned = any(c not in in_slice for c in consumers)
        e = -1 if epoch is None else epoch
        if pinned:
            with self._lock:
                existing = self._pinned.get((e, sp.name))
            if existing is not None:
                # a lineage-cone replay (ISSUE 8) re-runs the ingest segment
                # for a patch of shards: the survivors' partitions already
                # live in this pinned round, so the patch producers merge
                # into it (deposits extend node-side buckets, manifests
                # merge in record_manifest) instead of opening a second
                # round the store slice would never adopt.  Whole-epoch
                # replay never reuses: it invalidates the epoch (clearing
                # the pinned registry) before re-executing.
                for n in live:
                    if n not in existing.targets:
                        existing.targets.append(n)
                return existing
        rnd = ExchangeRound(
            xid=next(self._xids), stage=sp.name, key=self._shuffle_key(sp),
            epoch=e, targets=list(live),
            consumers=consumers,
            spill_share=max(1, self.spill_bytes // max(1, len(live))),
            pinned=pinned,
            # the edge goes columnar only when the optimizer proved EVERY
            # consuming stage's first block batch-capable (ISSUE 10) — a
            # single scalar consumer keeps the whole round item-at-a-time
            columnar=bool(self.columnar and consumers and
                          all(sp.columnar_edges.get(c, False)
                              for c in consumers)))
        with self._lock:
            self._rounds[rnd.xid] = rnd
            self._epoch_rounds.setdefault(rnd.epoch, set()).add(rnd.xid)
            if rnd.pinned:
                self._pinned[(rnd.epoch, rnd.stage)] = rnd
        return rnd

    def adopt_pinned(self, epoch: Optional[int],
                     slice_stages: Sequence[str]) -> List[ExchangeRound]:
        """Hand a starting ``_execute`` slice the rounds an earlier slice of
        the same epoch pinned for it (producing stage outside the slice, at
        least one consuming stage inside).  Adoption removes the pinned
        registration — the consuming slice owns the round's lifecycle from
        here (``finish_round`` on drain, epoch invalidation on failure)."""
        e = -1 if epoch is None else epoch
        names = set(slice_stages)
        with self._lock:
            keys = [k for k, r in self._pinned.items()
                    if k[0] == e and (set(r.consumers) & names)]
            return [self._pinned.pop(k) for k in keys]

    def record_manifest(self, rnd: ExchangeRound, node: str,
                        manifest: Dict[str, Any]) -> None:
        """A producer's partition manifest arrived: lease its spill files,
        account sizes — metadata only, the partitions themselves went (or
        stayed) worker-side."""
        for dst, desc in manifest.get("parts", {}).items():
            path = desc.get("path") or desc.get("spilled")
            if path:
                rnd.spilled = True
                self.store.lease_exchange_path(path)
            if desc.get("kind") == "stream":
                # degraded mode (ISSUE 9): this partition crosses hosts as
                # a streamed spill file, not a shared-memory segment
                rnd.degraded_parts += 1
                rnd.degraded_bytes += int(desc.get("nbytes", 0))
            if desc.get("columnar"):
                # ISSUE 10: this partition crossed as a column buffer —
                # no per-item pickling on either side of the edge
                rnd.columnar_parts += 1
                rnd.columnar_bytes += int(desc.get("nbytes", 0))
            if dst != node:
                rnd.total_bytes += int(desc.get("nbytes", 0))
            else:
                # the node's own slice: stayed resident (narrow edges keep
                # the entire output here — zero-coordinator dataflow)
                rnd.resident_bytes += int(desc.get("nbytes", 0))
        if manifest.get("columnar_fallback"):
            rnd.columnar_fallbacks += 1
        prev = rnd.manifests.get(node)
        if prev is not None:
            # a cone replay's patch producer (ISSUE 8) dealt a second time
            # into the same pinned round: node-side deposits extend the
            # bucket, so the manifests merge — counts and sizes sum, and a
            # second spill path stacks under "extra_paths" so every cleanup
            # walk still reaches it
            _merge_manifest(prev, manifest)
        else:
            rnd.manifests[node] = manifest
        rnd.total_count += int(manifest.get("total_count", 0))
        if self.test_on_manifest is not None:
            self.test_on_manifest(rnd, node)

    def serve(self, rnd: ExchangeRound, node: str) -> bool:
        """Advance the consumer-stage cursor for ``node``; True when this is
        the round's final consuming stage (the node-side collect pops)."""
        served = rnd.served.get(node, 0)
        rnd.served[node] = served + 1
        rnd.delivered.add(node)
        return served + 1 >= len(rnd.consumers)

    def refs_for(self, rnd: ExchangeRound, node: str) -> List[Dict[str, Any]]:
        """Fetch descriptors for the consumer job on ``node`` (process
        backend).  The first consuming stage receives the real refs —
        segments, spill files, the node's resident marker; later consuming
        stages replay the worker's cached bucket.  ``keep`` tells the worker
        another consuming stage follows."""
        served = rnd.served.get(node, 0)
        last = self.serve(rnd, node)
        if served:
            return [{"kind": "cached", "xid": rnd.xid, "keep": not last}]
        refs: List[Dict[str, Any]] = []
        for src, m in rnd.manifests.items():
            desc = m.get("parts", {}).get(node)
            if not desc:
                continue
            kind = desc["kind"]
            if kind == "mem":        # thread backend: bucket handoff, no ref
                continue
            if kind == "resident":
                if src == node:
                    refs.append({"kind": "resident", "xid": rnd.xid,
                                 "keep": not last})
                continue
            refs.append({**desc, "xid": rnd.xid, "src": src, "keep": not last})
        return refs

    def finish_round(self, rnd: ExchangeRound) -> bool:
        """A round's final consuming stage drained: drop the bookkeeping,
        release file leases, and reclaim refs addressed to nodes that never
        fetched (a consumer died mid-round).  Returns True when node-side
        buckets may still hold data — the engine then drops the round from
        the exchanges."""
        with self._lock:
            self._rounds.pop(rnd.xid, None)
            self._pinned.pop((rnd.epoch, rnd.stage), None)
            er = self._epoch_rounds.get(rnd.epoch)
            if er is not None:
                er.discard(rnd.xid)
                if not er:
                    self._epoch_rounds.pop(rnd.epoch, None)
        leftovers = False
        for src, m in rnd.manifests.items():
            for dst, desc in m.get("parts", {}).items():
                kind = desc["kind"]
                fetched = rnd.served.get(dst, 0) > 0
                for path in _desc_paths(desc):
                    if not fetched and kind in ("file", "resident", "stream"):
                        # an unfetched resident spill's owning worker may be
                        # dead (its bucket died with it) — reclaim the file
                        # here; a live holder's later drop no-ops on it
                        try:
                            os.remove(path)
                        except OSError:
                            pass
                    self.store.release_exchange_path(path)
                if kind == "shm" and not fetched:
                    unlink_segment(desc["shm"])
                if kind in ("mem", "resident") and not fetched:
                    leftovers = True
        return leftovers

    def invalidate_epoch(self, epoch: Optional[int]) -> List[int]:
        """Epoch abort/replay: destroy every live round of the epoch —
        unlink unconsumed segments, delete spill files, release leases.
        Returns the dead round ids so the engine can clear node-side
        buckets (``PartitionExchange.drop`` / worker drop messages)."""
        e = -1 if epoch is None else epoch
        with self._lock:
            xids = sorted(self._epoch_rounds.pop(e, ()))
            rounds = [self._rounds.pop(x) for x in xids if x in self._rounds]
            for k in [k for k in self._pinned if k[0] == e]:
                del self._pinned[k]
        for rnd in rounds:
            for src, m in rnd.manifests.items():
                for dst, desc in m.get("parts", {}).items():
                    if desc["kind"] == "shm":
                        unlink_segment(desc["shm"])
                    for path in _desc_paths(desc):
                        try:
                            os.remove(path)
                        except OSError:
                            pass
                        self.store.release_exchange_path(path)
        return xids

    def invalidate_producer(self, epoch: Optional[int], node: str) -> List[int]:
        """Lineage-cone recovery (ISSUE 8): strip ONE dead producer's
        contribution from the epoch's live rounds, leaving every survivor's
        partitions intact.  Sound only when the epoch's rounds are
        identity-routed (``key=None``) — then a producer's output lives
        solely in its own bucket and separates cleanly; a shuffle round
        commingles producers per target, which is why callers gate on
        ``plan.cone_replay_capable``.  The dead node's unconsumed segments
        and spill files are reclaimed, its manifests and delivery cursors
        forgotten, and it leaves the rounds' target sets (a later cone
        patch re-deals over the survivors).  Returns the touched round ids
        so the engine can drop the matching node-side buckets."""
        e = -1 if epoch is None else epoch
        with self._lock:
            xids = sorted(self._epoch_rounds.get(e, ()))
            rounds = [self._rounds[x] for x in xids if x in self._rounds]
        touched: List[int] = []
        for rnd in rounds:
            if node in rnd.targets:
                rnd.targets.remove(node)
            rnd.served.pop(node, None)
            rnd.delivered.discard(node)
            m = rnd.manifests.pop(node, None)
            if m is None:
                continue
            touched.append(rnd.xid)
            rnd.total_count -= int(m.get("total_count", 0))
            for dst, desc in m.get("parts", {}).items():
                if desc["kind"] == "shm":
                    unlink_segment(desc["shm"])
                nb = int(desc.get("nbytes", 0))
                if dst != node:
                    rnd.total_bytes -= nb
                else:
                    rnd.resident_bytes -= nb
                for path in _desc_paths(desc):
                    try:
                        os.remove(path)
                    except OSError:
                        pass
                    self.store.release_exchange_path(path)
        return touched

    # --------------------------------------------------------------- barrier
    def barrier(self, sp: StagePlan,
                outputs: Dict[str, Dict[str, List[IngestItem]]],
                live: List[str], report: RunReport) -> None:
        """Legacy coordinator-side redistribution (synchronous mode and
        cross-slice boundaries).  ``live`` is the caller's pinned
        executing-node set — groups are collected from and reassigned over
        exactly these nodes.  This is the only path that moves item bytes
        through the coordinator (``shuffle_coordinator_bytes``)."""
        if not sp.ops:
            return
        shuffle_by = self._shuffle_key(sp)
        if shuffle_by is None:
            return
        with self._stage_lock(sp.name):
            with self._lock:
                prev = self._pending.pop(sp.name, None)
            if prev is not None:
                prev.result()  # double buffer: last round's journal must land

            groups: Dict[Any, List[IngestItem]] = {}
            nbytes = 0
            for n in live:
                for it in outputs[n][sp.name]:
                    g = it.label_value(shuffle_by, 0)
                    groups.setdefault(g, []).append(it)
                    nbytes += it.nbytes()
                    report.shuffled_items += 1
                outputs[n][sp.name] = []
            if not groups:
                return
            report.shuffle_coordinator_bytes += nbytes
            order = sorted(groups, key=str)
            if self.synchronous:
                # legacy path: DFS round-trip inside the barrier
                report.shuffle_spills += 1
                dfs = self._write_groups(sp.name, order, groups)
                groups.clear()
                for gi, fn in enumerate(sorted(os.listdir(dfs))):
                    target = live[gi % len(live)]
                    with open(os.path.join(dfs, fn), "rb") as f:
                        outputs[target][sp.name].extend(pickle.load(f))
                # consume-on-read: the next round must not merge these files
                shutil.rmtree(dfs, ignore_errors=True)
                self.store.release_exchange_path(dfs)
                return
            for gi, g in enumerate(order):
                outputs[live[gi % len(live)]][sp.name].extend(groups[g])
            if nbytes > self.spill_bytes:
                # oversized round: materialize the group files on the DFS in
                # the background — overlapped with the next epoch's ingest
                report.shuffle_spills += 1
                fut = self._writer_lane().submit(
                    self._write_groups, sp.name, order, groups)
                with self._lock:
                    self._pending[sp.name] = fut
                    self._spilled_stages.add(sp.name)
            else:
                report.shuffle_async_rounds += 1

    # ----------------------------------------------------------------- paths
    def _write_groups(self, stage: str, order: List[Any],
                      groups: Dict[Any, List[IngestItem]]) -> str:
        """Local groups -> one DFS file per group (consume-on-write: a fresh
        round never merges an earlier round's leftovers).  The dir is leased
        so ``gc_orphans`` spares it while this service lives."""
        dfs = self._dfs_dir(stage)
        shutil.rmtree(dfs, ignore_errors=True)
        self.store.lease_exchange_path(dfs)
        os.makedirs(dfs, exist_ok=True)
        for g in order:
            with open(os.path.join(dfs, f"group{g}.pkl"), "wb") as f:
                pickle.dump(groups[g], f, protocol=pickle.HIGHEST_PROTOCOL)
        return dfs

    # ------------------------------------------------------------- lifecycle
    def drain(self) -> None:
        """Wait for every outstanding journal write (end-of-stream barrier)."""
        with self._lock:
            pending, self._pending = list(self._pending.values()), {}
        for fut in pending:
            fut.result()

    def close(self) -> None:
        self.drain()
        with self._lock:
            writer, self._writer = self._writer, None
            spilled, self._spilled_stages = set(self._spilled_stages), set()
            epochs = list(self._epoch_rounds)
        for e in epochs:           # leftover exchange rounds die with us
            self.invalidate_epoch(e)
        if writer is not None:
            writer.stop()
        for stage in spilled:   # spilled group files die with the service
            dfs = self._dfs_dir(stage)
            shutil.rmtree(dfs, ignore_errors=True)
            self.store.release_exchange_path(dfs)


#: pre-ISSUE-4 name, kept for callers that predate the control/data split
ShuffleService = ShuffleCoordinator


class RuntimeEngine:
    def __init__(self, store: DataStore, optimizer: Optional[IngestionOptimizer] = None,
                 max_retries: int = 3, shuffle_spill_bytes: Optional[int] = None,
                 shuffle_synchronous: bool = False,
                 backend: str = "thread",
                 memory_budget_bytes: Optional[int] = None,
                 transport: str = "pipe",
                 node_hosts: Optional[Dict[str, str]] = None,
                 network_chaos: bool = False,
                 columnar: bool = True) -> None:
        """``backend`` selects the node substrate: ``"thread"`` (default —
        in-process ``NodeExecutor`` lanes) or ``"process"`` (one long-lived
        worker process per node, real CPU parallelism; DESIGN.md §6).

        ``memory_budget_bytes`` is the engine's shared memory budget: when
        set and no explicit ``shuffle_spill_bytes`` is given, the shuffle
        spill threshold is derived from it (minus the ingest queues' share,
        for the streaming engine) instead of the static default.

        ``transport`` (process backend, ISSUE 9) selects the control/store
        medium: ``"pipe"`` (default — ``multiprocessing.Pipe``, the
        byte-identical oracle) or ``"socket"`` (the framed TCP fabric,
        DESIGN.md §7).  ``node_hosts`` maps node -> host label; nodes on
        different hosts are treated as not shm-reachable — their shuffle
        partitions cross in degraded mode (streamed spill files) and the
        liveness monitor applies the per-host partition quorum.
        ``network_chaos`` inserts the ChaosProxy shim on each socket pair
        so the chaos harness can render partition/drop/delay events.

        ``columnar`` (ISSUE 10) enables the columnar data plane: stage
        edges whose producing AND consuming blocks the optimizer proved
        batch-capable cross as ColumnarBatch column buffers instead of
        item lists.  ``columnar=False`` pins every edge to the
        item-at-a-time path — the byte-identical correctness oracle."""
        if backend not in ("thread", "process"):
            raise ValueError(f"unknown backend {backend!r} (thread|process)")
        if transport not in ("pipe", "socket"):
            raise ValueError(f"unknown transport {transport!r} (pipe|socket)")
        self.store = store
        self.nodes = list(store.nodes)
        self.optimizer = optimizer or IngestionOptimizer()
        self.max_retries = max_retries
        self.backend = backend
        self.transport = transport
        self.node_hosts = dict(node_hosts) if node_hosts else {}
        self.network_chaos = network_chaos
        self.memory_budget_bytes = memory_budget_bytes
        self.columnar = columnar
        self._explicit_spill = shuffle_spill_bytes is not None
        if shuffle_spill_bytes is None:
            shuffle_spill_bytes = (derive_spill_bytes(memory_budget_bytes)
                                   if memory_budget_bytes is not None
                                   else DEFAULT_SPILL_BYTES)
        self.shuffle = ShuffleCoordinator(store, spill_bytes=shuffle_spill_bytes,
                                          synchronous=shuffle_synchronous,
                                          columnar=columnar)
        # thread-backend data plane: node lanes deposit/collect partitions
        # here directly — the coordinator thread never touches the items
        self._exchange = PartitionExchange()
        self._executors: Dict[str, Any] = {}
        self._exec_lock = threading.Lock()

    # ------------------------------------------------------------------ remote
    def launch_remote(self, node: str, stage_plans: List[StagePlan]) -> List[StagePlan]:
        """The remote-shell seam: in a real deployment this SSHes the optimized
        plan to ``node`` (paper Sec. VI-A).  The thread backend clones operator
        instances so every node runs its own state, exactly as separate JVMs
        would; the process backend ships the same plan by pickle to the node's
        worker process (``ProcessNodeExecutor.install_plan``)."""
        return [sp.clone() for sp in stage_plans]

    def executor(self, node: str) -> Any:
        """The node's persistent executor (created on first use, kept for the
        engine's lifetime — stage barriers stop re-creating thread pools).
        Thread backend: ``NodeExecutor``; process backend:
        ``ProcessNodeExecutor`` (a live worker process)."""
        with self._exec_lock:
            ex = self._executors.get(node)
            if ex is None:
                if self.backend == "process":
                    # the fork is always local in this repo — ``host`` is
                    # the *placement label* driving quorum grouping and
                    # degraded exchange, so local_worker stays True and
                    # shm sweeps keep running (no leaked segments in the
                    # simulated-multi-host soaks)
                    ex = ProcessNodeExecutor(
                        node, self.store, transport=self.transport,
                        host=self.node_hosts.get(node),
                        chaos_shim=self.network_chaos,
                        bulk_registration=self.columnar)
                else:
                    ex = NodeExecutor(node)
                self._executors[node] = ex
            return ex

    def prewarm_executors(self) -> None:
        """Spawn every node's executor up front.  The process backend forks
        here — before feeder/committer threads exist — so worker processes
        never inherit mid-operation thread state."""
        for n in self.nodes:
            self.executor(n)

    def close(self) -> None:
        """Shut down persistent node executors and the shuffle planes."""
        self.shuffle.close()
        self._exchange.close()
        with self._exec_lock:
            execs, self._executors = list(self._executors.values()), {}
        for ex in execs:
            ex.shutdown()

    def invalidate_exchange(self, epoch: Optional[int]) -> None:
        """Tear down a dead epoch's in-flight exchange state everywhere:
        the coordinator unlinks unconsumed segments and deletes spill files
        (metadata bookkeeping), then every node-side exchange drops its
        buckets — a replay of the epoch starts from clean rounds."""
        self._drop_rounds(self.shuffle.invalidate_epoch(epoch))

    def invalidate_producer(self, epoch: Optional[int], node: str) -> None:
        """Per-producer exchange invalidation (ISSUE 8 cone recovery): the
        coordinator strips the dead node's manifests from the epoch's live
        rounds, then the engine-side exchange forgets only that node's
        buckets.  Survivors' partitions stay live for the store segment.  A
        process worker's resident buckets died with the worker itself, and
        identity-routed rounds never placed the producer's data on a peer —
        so no worker drop message is needed."""
        xids = self.shuffle.invalidate_producer(epoch, node)
        if xids:
            self._exchange.drop_node(xids, node)

    def _drop_rounds(self, xids: Sequence[int]) -> None:
        """Clear node-side exchange buckets for dead rounds — the engine's
        own exchange (thread backend) and every live worker process (their
        resident buckets hold refcounted segment leases)."""
        if not xids:
            return
        self._exchange.drop(xids)
        if self.backend == "process":
            with self._exec_lock:
                execs = list(self._executors.values())
            for ex in execs:
                drop = getattr(ex, "drop_exchange", None)
                if drop is not None:
                    drop(xids)

    def _deposit_partitions(self, rnd: ExchangeRound, node: str,
                            out: List[IngestItem]) -> Dict[str, Any]:
        """Thread-backend data plane: partition this node's stage output by
        the routing key and hand each partition straight to its target's
        bucket (the in-memory queue handoff) — for a narrow round
        (``rnd.key is None``) the whole output deposits into the node's own
        bucket, staying resident.  A partition past the per-edge spill share
        crosses as a DFS file instead (``resident_*`` naming for the node's
        own slice).  Runs on the node's executor lane — only the returned
        manifest (counts, sizes, paths) ever reaches the coordinator.

        On a columnar round (ISSUE 10) the output packs into a
        ColumnarBatch first: partitioning is one vectorized hash pass and
        each partition deposits (or spills) as a column buffer.  A batch
        that doesn't pack falls back to the scalar path and flags the
        manifest (``columnar_fallback``) so the coordinator counts it."""
        def part_fn(dst: str, its: Any, nb: int) -> Dict[str, Any]:
            if isinstance(its, ColumnarBatch):
                if nb > rnd.spill_share:
                    path = os.path.join(
                        self.store.dfs_dir,
                        columnar_file_name(rnd.epoch, rnd.xid, node, dst))
                    write_columnar_file(path, its)
                    self._exchange.deposit(rnd.xid, dst, None, nb, path=path)
                    return {"kind": "mem", "count": len(its), "nbytes": nb,
                            "spilled": path, "columnar": True}
                self._exchange.deposit_batch(rnd.xid, dst, its)
                return {"kind": "mem", "count": len(its), "nbytes": nb,
                        "columnar": True}
            if nb > rnd.spill_share:
                path = os.path.join(
                    self.store.dfs_dir,
                    resident_file_name(rnd.epoch, rnd.xid, node)
                    if dst == node else
                    exchange_file_name(rnd.epoch, rnd.xid, node, dst))
                write_partition_file(path, its)
                self._exchange.deposit(rnd.xid, dst, None, nb, path=path)
                return {"kind": "mem", "count": len(its), "nbytes": nb,
                        "spilled": path}
            self._exchange.deposit(rnd.xid, dst, its, nb)
            return {"kind": "mem", "count": len(its), "nbytes": nb}

        payload: Any = out
        fallback = False
        if rnd.columnar and out:
            batch = ColumnarBatch.from_items(out)
            if batch is None:
                fallback = True
            else:
                payload = batch
        manifest = build_manifest(payload, rnd.key, rnd.targets, part_fn,
                                  self_node=node)
        if fallback:
            manifest["columnar_fallback"] = True
        return {"kind": "xmanifest", "manifest": manifest}

    def __enter__(self) -> "RuntimeEngine":
        return self

    def __exit__(self, *exc: Any) -> None:
        self.close()

    # --------------------------------------------------------------------- run
    def run(self, plan: IngestPlan,
            sources: Union[Dict[str, List[IngestItem]], List[IngestItem],
                           "SourceAdapter", None] = None,
            faults: Optional[FaultInjection] = None,
            optimize: bool = True) -> RunReport:
        t0 = time.time()
        faults = faults or FaultInjection()
        report = RunReport()
        if self.backend == "process":
            self.prewarm_executors()   # fork before any run-scoped threads

        stage_plans = plan.compile()
        if optimize:
            stage_plans = self.optimizer.optimize(stage_plans)

        # worker-pull source (ISSUE 6): the coordinator distributes shard
        # descriptors; workers read them.  Everything downstream treats the
        # descriptors as opaque shards — reassignment/cohort replay move
        # them between nodes exactly like items, but no item bytes ever
        # exist coordinator-side.
        adapter = sources if isinstance(sources, SourceAdapter) else None
        if adapter is None and getattr(plan, "source_spec", None) and sources is None:
            adapter = build_source(plan.source_spec)
        if adapter is not None:
            sources = adapter.describe()
            report.source_descriptors = len(sources)
        elif not isinstance(sources, dict):
            sources = list(sources)   # cohort replay re-distributes them

        alive = {n: True for n in self.nodes}
        # a fresh batch run starts from full liveness — clear placement marks
        # a previous run's (injected) deaths left on the shared store
        for n in self.nodes:
            self.store.mark_node_live(n)

        # ---- cohort-replay guard (ROADMAP "batch shuffle cohort replay"):
        # a DAG that consumes a shuffle stages its blocks under an epoch, so
        # a node death at/after a shuffle-consuming stage — whose groups
        # mixed other nodes' lineages and cannot be replayed from the dead
        # node's own shards — can abort the staged blocks and replay the
        # *whole run* on the survivors, exactly-once (the streaming engine's
        # epoch-granular recovery applied to batch).
        wrap = self._has_shuffle_consumer(stage_plans)
        eid: Optional[int] = None
        try:
            while True:
                live = [n for n in self.nodes if alive[n]]
                if not live:
                    raise RuntimeError("all nodes failed")
                node_sources = self._distribute_sources(sources, live)
                report.per_node_shards = {n: len(v)
                                          for n, v in node_sources.items()}
                if adapter is None:
                    # legacy pushed path: the coordinator held and routed
                    # every source item — count the hop it paid
                    report.source_coordinator_bytes = sum(
                        items_nbytes(v) for v in node_sources.values())
                if wrap:
                    eid = self.store.next_epoch_id()
                    self.store.begin_epoch(eid)
                try:
                    self._execute(stage_plans, node_sources, faults, report,
                                  alive, epoch=eid, source=adapter)
                    break
                except _CohortReplay:
                    self.store.abort_epoch(eid)
                    self.invalidate_exchange(eid)
                    report.cohort_replays += 1
                    eid = None   # rolled back; the retry stages afresh
            self.shuffle.drain()
            if eid is not None:
                self.store.commit_epoch(
                    eid, n_items=sum(report.per_node_shards.values()))
        except BaseException:
            # don't strand a staging epoch: a stuck staging id would block
            # every later commit on this store (the commit sequencer waits
            # on smaller staging ids forever)
            if eid is not None and not self.store.epoch_committed(eid):
                self.store.abort_epoch(eid)
                self.invalidate_exchange(eid)
            raise

        report.wall_time_s = time.time() - t0
        report.spawn_retries = self._spawn_retry_total()
        report.sweep_skipped_remote = self._sweep_skip_total()
        self.store.flush_manifest()
        return report

    def _spawn_retry_total(self) -> int:
        """Process-worker spawn attempts beyond the first, over every
        executor this engine created (ISSUE 8 bounded spawn retry)."""
        with self._exec_lock:
            execs = list(self._executors.values())
        return sum(getattr(ex, "spawn_retries", 0) for ex in execs)

    def _sweep_skip_total(self) -> int:
        """Shm sweep passes skipped because a worker was remote (ISSUE 9
        satellite): reported instead of silently pretending the remote
        host's segments were reclaimed."""
        with self._exec_lock:
            execs = list(self._executors.values())
        return sum(getattr(ex, "sweep_skips", 0) for ex in execs)

    def _redistribute(self, batch: Dict[str, List[IngestItem]],
                      live: List[str]) -> Dict[str, List[IngestItem]]:
        """Node affinity where the node is in the live set; round-robin onto
        survivors otherwise — the one rebalancing policy shared by batch
        cohort replay and the streaming engine's epoch replay."""
        node_sources: Dict[str, List[IngestItem]] = {n: [] for n in self.nodes}
        spill: List[IngestItem] = []
        for n, its in batch.items():
            (node_sources[n] if n in live else spill).extend(its)
        for i, it in enumerate(spill):
            node_sources[live[i % len(live)]].append(it)
        return node_sources

    def _distribute_sources(self, sources: Union[Dict[str, List[IngestItem]],
                                                 List[IngestItem]],
                            live: List[str]) -> Dict[str, List[IngestItem]]:
        """Distribute source shards over the live nodes: node-local dict
        (a dead node's shards move round-robin onto survivors), or a shared
        queue (work stealing / straggler mitigation: slow nodes take fewer
        shards)."""
        if isinstance(sources, dict):
            return self._redistribute(sources, live)
        node_sources: Dict[str, List[IngestItem]] = {n: [] for n in self.nodes}
        shared: "queue.Queue[IngestItem]" = queue.Queue()
        for it in sources:
            shared.put(it)
        while True:
            grabbed = False
            for n in live:
                try:
                    node_sources[n].append(shared.get_nowait())
                    grabbed = True
                except queue.Empty:
                    break
            if not grabbed:
                break
        return node_sources

    @staticmethod
    def _has_shuffle_consumer(stage_plans: List[StagePlan],
                              upto: Optional[int] = None) -> bool:
        """True when some stage at index <= ``upto`` (whole DAG when None)
        consumes a shuffle boundary — the condition under which a dead
        node's state cannot be rebuilt from its own source shards.  Reads
        the compiled per-edge metadata (``edge_kinds`` consumer map +
        ``shuffle_key``), falling back to an upstream scan for hand-built
        plans that never went through ``annotate_edges``."""
        in_range = {sp.name for sp in (stage_plans if upto is None
                                       else stage_plans[:upto + 1])}
        for si, sp in enumerate(stage_plans):
            if not (sp.shuffle_key or sp.compute_shuffle_key()):
                continue
            consumers = stage_consumers(stage_plans, si,
                                        downstream_only=False)
            if any(c in in_range for c in consumers):
                return True
        return False

    # ----------------------------------------------------------- stage dataflow
    def _mark_dead(self, node: str, alive: Dict[str, bool], report: RunReport) -> None:
        alive[node] = False
        report.node_failures.append(node)
        # location IDs of the dead node flow to the survivors (Sec. VI-C1):
        # the upload operator maps location ids over live nodes only
        self.store.mark_node_dead(node)

    def _execute(self, stage_plans: List[StagePlan],
                 node_sources: Dict[str, List[IngestItem]],
                 faults: FaultInjection, report: RunReport,
                 alive: Dict[str, bool],
                 on_node_death: str = "reassign",
                 lane: str = "main",
                 epoch: Optional[int] = None,
                 outputs: Optional[Dict[str, Dict[str, List[IngestItem]]]] = None,
                 start_stage: int = 0,
                 end_stage: Optional[int] = None,
                 node_set: Optional[List[str]] = None,
                 source: Optional["SourceAdapter"] = None
                 ) -> Dict[str, Dict[str, List[IngestItem]]]:
        """Run (a slice of) the stage DAG over per-node shards — the body
        shared by the batch engine and the streaming engine's per-epoch
        execution.  Stage jobs run on the persistent per-node executors.

        ``on_node_death`` selects the recovery policy:
          * ``"reassign"`` (batch): the dead node's shards move to the next
            live node, which replays stages 0..si for them (Sec. VI-C1).
          * ``"raise"`` (streaming): mark the node dead and raise NodeFailure —
            the caller aborts the staged epoch and replays it on the
            surviving nodes (epoch-granular recovery).

        ``lane`` picks the NodeExecutor lane (pipelined streaming keeps epoch
        N+1's ingest and epoch N's store on separate lanes); ``epoch`` binds
        ``DataStore.put_block`` attribution for concurrent staging epochs;
        ``outputs``/``start_stage``/``end_stage`` execute a slice of the DAG
        over pre-seeded upstream outputs (the ingest/store segment split).

        ``node_set`` pins the executing nodes for the whole call: with two
        epochs in flight, ``alive`` can flip concurrently from the *other*
        epoch's thread, and a per-stage liveness read could silently skip a
        node whose inputs this epoch still holds.  Raise-mode callers pass
        their consistent snapshot; batch recomputes per stage (it owns
        ``alive`` exclusively and needs reassignment to see deaths).

        ``source`` flips the source hop to worker-pull (ISSUE 6): the
        source-stage entries of ``node_sources`` are :class:`ShardDescriptor`
        lists, and each node opens/reads/parses its shards on its own lane
        (thread backend) or inside its worker process (process backend,
        ``ctx["source"]``) — no item bytes ever transit the coordinator.
        Predicates of the source stage apply to the *read* items.
        """
        if on_node_death == "reassign" and (start_stage != 0 or end_stage is not None):
            raise ValueError("shard reassignment requires the full stage DAG")
        use_proc = self.backend == "process"
        # ---- plan is resident on every node executor (installed once);
        # thread backend: in-process clone; process backend: pickled ship to
        # the worker.  A worker already dead at install time takes the same
        # fault path as one dying mid-stage.
        node_plans: Dict[str, List[StagePlan]] = {}
        plan_keys: Dict[str, str] = {}
        install_failed: List[str] = []
        exec_nodes = (list(node_set) if node_set is not None
                      else [n for n in self.nodes if alive.get(n)])
        for n in exec_nodes:
            try:
                if use_proc:
                    plan_keys[n] = self.executor(n).install_plan(stage_plans)
                else:
                    node_plans[n] = self.executor(n).install_plan(
                        stage_plans, self.launch_remote)
            except WorkerDeath:
                install_failed.append(n)
        for n in install_failed:
            self._mark_dead(n, alive, report)
        if install_failed and on_node_death == "raise":
            raise NodeFailure(install_failed[0])
        if outputs is None:
            outputs = {n: defaultdict(list) for n in self.nodes}
        stop = len(stage_plans) if end_stage is None else end_stage
        failure_counts: Dict[Tuple[str, str, int], int] = defaultdict(int)

        # dedicated lock for report mutation from worker threads
        rlock = threading.Lock()

        def read_descs(descs: List[Any]) -> List[IngestItem]:
            """Worker-pull: materialize a node's shard descriptors (runs on
            the node's own lane — the thread backend's equivalent of the
            process worker's in-worker read)."""
            pulled: List[IngestItem] = []
            for d in descs:
                pulled.extend(source.read(d))
            with rlock:
                report.source_items += len(pulled)
            return pulled

        # peer-exchange rounds still awaiting consuming stage(s), keyed by
        # producing stage name.  A slice starting mid-DAG (the store segment)
        # first adopts the rounds an earlier slice pinned for it — node-
        # resident buckets crossing the ingest/store boundary (ISSUE 5)
        active_rounds: Dict[str, ExchangeRound] = {}
        if start_stage:
            active_rounds = {
                r.stage: r for r in self.shuffle.adopt_pinned(
                    epoch, [sp.name for sp in stage_plans[start_stage:stop]])}

        for si in range(start_stage, stop):
            sp = stage_plans[si]

            live_nodes = (list(node_set) if node_set is not None
                          else [n for n in self.nodes if alive[n]])
            # exchange plumbing for this stage: rounds it consumes, and the
            # round it produces (None -> legacy barrier handles the boundary)
            incoming = [r for r in active_rounds.values()
                        if sp.name in r.consumers]
            produce = self.shuffle.plan_round(stage_plans, si, stop,
                                              live_nodes, epoch)
            if produce is not None:
                active_rounds[sp.name] = produce
            # a terminal stage (no consumer anywhere in the DAG) is a sink:
            # process workers reply a count instead of shipping the output
            # items back over the coordinator pipe (zero-coordinator bytes
            # end-to-end; the thread backend's outputs dict is in-process)
            has_consumers = bool(sp.edge_kinds) or any(
                sp.name in sq.upstream for sq in stage_plans[si + 1:])
            sink = (use_proc and produce is None and not has_consumers
                    and not self.shuffle.synchronous and bool(sp.ops))
            sink_counts: Dict[str, int] = {}
            # worker-pull: this stage's inputs are shard descriptors, read
            # node-side (source stages only — stages with upstream consume
            # prior outputs as usual)
            src_mode = source is not None and not sp.upstream

            # -------------------------------------------------- stage barrier
            def run_stage_on(node: str, nsp: StagePlan,
                             input_items: List[Any],
                             fetches: List[Tuple[int, bool]],
                             prnd: Optional[ExchangeRound]) -> Any:
                with self.store.epoch_context(epoch):
                    if src_mode:
                        items = route_items(read_descs(input_items),
                                            nsp.predicates)
                    else:
                        items = input_items
                    for xid, last, owner in fetches:
                        # thread backend: partitions hand off in memory —
                        # collect on the node's own lane, route, and merge.
                        # `owner` is normally this node; a redirected fetch
                        # drains a dead consumer's bucket instead.
                        got, _ = self._exchange.collect(xid, owner, last=last)
                        items = items + route_items(got, nsp.predicates)
                    out = self._run_stage(node, nsp, items, faults,
                                          failure_counts, report, rlock)
                    if prnd is None:
                        return out
                    return self._deposit_partitions(prnd, node, out)

            def stage_inputs(node: str, nsp: StagePlan) -> List[IngestItem]:
                if not nsp.upstream:
                    base = node_sources[node]
                    if src_mode:
                        # descriptors are routed post-read, node-side
                        return list(base)
                else:
                    base = []
                    for up in nsp.upstream:  # CHAIN = union all (Sec. IV-B)
                        base = base + outputs[node][up]
                return route_items(base, nsp.predicates)

            # ---- batch-mode redirection: a target that died between the
            # producing and consuming stage never fetches its bucket.  Its
            # *peer-held* partitions (segments / files / thread buckets) are
            # location-independent, so they deliver to the next live node —
            # the same node its replayed shards land on — instead of being
            # reclaimed as leftovers.  (Raise mode never gets here: a death
            # aborts the epoch before the consumer stage is submitted.)
            redirects: Dict[str, List[Any]] = {}
            if on_node_death == "reassign":
                final_consuming_stage = {
                    rnd.xid: rnd.consumers_done == len(rnd.consumers) - 1
                    for rnd in incoming}
                for rnd in incoming:
                    for t in rnd.targets:
                        if t in live_nodes:
                            continue
                        tgt = self._next_live(t, alive)
                        if tgt is None:
                            continue
                        if use_proc:
                            # redirect once, and never to a node that was
                            # already handed refs (they may be consumed —
                            # segments unlinked, files deleted); the target
                            # worker caches the decoded batch (keep flag),
                            # so its later "cached" collects include it.  A
                            # node that consumed before dying took its cache
                            # with it — unrecoverable (pre-existing corner).
                            if t in rnd.delivered:
                                continue
                            refs = [r for r in self.shuffle.refs_for(rnd, t)
                                    if r["kind"] in ("shm", "file", "stream")]
                            redirects.setdefault(tgt, []).extend(refs)
                        else:
                            # thread buckets outlive the node (peek keeps
                            # them): redirect at EVERY consuming stage, and
                            # pop exactly at the round's final one — the
                            # dead node's own cursor may have been reset by
                            # the failure bookkeeping
                            redirects.setdefault(tgt, []).append(
                                (rnd.xid, final_consuming_stage[rnd.xid], t))

            futs = {}
            if use_proc:
                # injected op failures are assigned to the first live node
                # (the thread backend's shared-dict race picks an arbitrary
                # winner; the process backend makes it deterministic)
                injections: Dict[int, int] = {}
                for (sname, oi), cnt in list(faults.op_failures.items()):
                    if sname == sp.name and cnt > 0:
                        injections[oi] = cnt
                        faults.op_failures[(sname, oi)] = 0
                for ni, n in enumerate(live_nodes):
                    fetch: List[Dict[str, Any]] = []
                    for rnd in incoming:
                        fetch.extend(self.shuffle.refs_for(rnd, n))
                    fetch.extend(redirects.get(n, []))
                    futs[n] = self.executor(n).run_stage(
                        plan_keys[n], si,
                        [] if src_mode else stage_inputs(n, sp), lane=lane,
                        epoch=epoch, live_nodes=live_nodes,
                        injections=injections if ni == 0 else None,
                        max_retries=self.max_retries,
                        shuffle_ctx=(produce.worker_ctx(self.store.dfs_dir,
                                                        self.node_hosts)
                                     if produce is not None else None),
                        fetch_refs=fetch or None, sink=sink,
                        source_ctx=({"adapter": source,
                                     "descs": node_sources[n]}
                                    if src_mode else None))
            else:
                for n in live_nodes:
                    nsp = node_plans[n][si]
                    fetches = [(rnd.xid, self.shuffle.serve(rnd, n), n)
                               for rnd in incoming]
                    fetches.extend(redirects.get(n, []))
                    futs[n] = self.executor(n).submit(
                        run_stage_on, n, nsp, stage_inputs(n, nsp),
                        fetches, produce, lane=lane)
            failed: List[str] = []
            for n, fut in futs.items():  # drain ALL jobs before acting on death
                try:
                    res = fut.result()
                except (NodeFailure, WorkerDeath):
                    failed.append(n)
                    continue
                except Exception:
                    # a SIGTERM'd worker can emit one garbled/partial reply
                    # before the pipe EOF lands — if the worker is gone, the
                    # failure IS the death, not a stage error.  (Exception,
                    # not BaseException: a KeyboardInterrupt landing in this
                    # wait must abort the run, not mark the node dead.)
                    if use_proc and not getattr(self.executor(n), "alive", True):
                        failed.append(n)
                        continue
                    raise
                if use_proc:
                    payload, stats = res
                    with rlock:
                        for k, v in stats["op_failures"].items():
                            report.op_failures[k] = max(
                                report.op_failures.get(k, 0), v)
                        report.dummy_substitutions.extend(stats["dummy"])
                        report.source_items += stats.get("source_items", 0)
                        report.vectorized_rows += stats.get(
                            "vectorized_rows", 0)
                        report.batch_fallbacks += stats.get(
                            "batch_fallbacks", 0)
                        report.kernel_calls += stats.get("kernel_calls", 0)
                else:
                    payload = res
                if (produce is not None and isinstance(payload, dict)
                        and payload.get("kind") == "xmanifest"):
                    # partitions went peer-to-peer (or stayed resident);
                    # only metadata came back
                    outputs[n][sp.name] = []
                    self.shuffle.record_manifest(produce, n,
                                                 payload["manifest"])
                elif isinstance(payload, dict) and payload.get("kind") == "sink":
                    # terminal stage: the worker dropped its outputs locally
                    # — only the count crossed the coordinator pipe
                    outputs[n][sp.name] = []
                    sink_counts[n] = int(payload.get("count", 0))
                else:
                    outputs[n][sp.name] = payload
                    if has_consumers:
                        # legacy boundary: the stage output round-tripped
                        # through the coordinator as item bytes
                        report.stage_coordinator_bytes += items_nbytes(payload)
            if produce is not None:
                report.stage_resident_bytes += produce.resident_bytes
                if produce.degraded_parts:
                    report.degraded_exchange_rounds += 1
                    report.degraded_peer_bytes += produce.degraded_bytes
                if produce.columnar_parts:
                    report.columnar_rounds += 1
                    report.columnar_bytes += produce.columnar_bytes
                report.columnar_fallbacks += produce.columnar_fallbacks
                if produce.key is None:        # narrow (identity) round
                    report.stage_exchange_rounds += 1
                    if produce.spilled:
                        report.resident_spills += 1
                else:
                    report.shuffled_items += produce.total_count
                    report.shuffle_peer_bytes += produce.total_bytes
                    report.shuffle_exchange_rounds += 1
                    if produce.spilled:
                        report.shuffle_spills += 1
                    else:
                        report.shuffle_async_rounds += 1
            for n in failed:
                self._mark_dead(n, alive, report)
                for rnd in incoming:
                    # the consumer died mid-fetch: count it as never served
                    # so finish_round reclaims its unconsumed refs (a
                    # double-unlink of a ref it did consume is a no-op)
                    rnd.served.pop(n, None)
            if failed and on_node_death == "raise":
                err = NodeFailure(failed[0])
                err.stage_index = si
                raise err

            # ---- legacy shuffle barrier (Sec. VI-B) for boundaries the
            # exchange does not cover: synchronous mode, or the consuming
            # stage lies outside this slice.  With a pinned node_set (raise
            # mode) a stage failure raised above, so the whole set
            # redistributes — re-reading `alive` here would race with the
            # other epoch's thread and silently skip a node's outputs.
            # Batch mode re-reads it so a node that just failed this stage
            # takes no groups.
            if produce is None:
                barrier_live = (live_nodes if node_set is not None
                                else [n for n in live_nodes if alive[n]])
                self.shuffle.barrier(sp, outputs, barrier_live, report)

            # ---- exchange rounds whose final consuming stage just drained:
            # release control-plane bookkeeping; drop node-side leftovers of
            # consumers that never fetched (died mid-round)
            for rnd in incoming:
                rnd.consumers_done += 1
                if rnd.consumers_done >= len(rnd.consumers):
                    if self.shuffle.finish_round(rnd):
                        self._drop_rounds([rnd.xid])
                    active_rounds.pop(rnd.stage, None)

            # ---- injected node deaths after this stage
            died_here = list(failed)
            for n, after in faults.node_death_after_stage.items():
                if after == sp.name and alive.get(n):
                    self._mark_dead(n, alive, report)
                    died_here.append(n)
                    if on_node_death == "raise":
                        err = NodeFailure(n)
                        err.stage_index = si
                        raise err

            # ---- cohort-replay escalation (ROADMAP "batch shuffle cohort
            # replay"): once a shuffle-consuming stage has run, a dead
            # node's state mixed other nodes' lineages — replaying its own
            # source shards would double-count or lose groups.  Escalate to
            # whole-run replay (run() aborts the staged epoch and restarts
            # on the survivors) instead of shard reassignment.
            if (died_here and on_node_death == "reassign"
                    and self._has_shuffle_consumer(stage_plans, upto=si)):
                raise _CohortReplay(died_here[0])

            # ---- node-failure recovery: reassign dead nodes' shards to the
            # next live node in the slaves order and re-run stages 0..si for
            # them (their in-flight state is lost with the node).  Only the
            # batch policy reassigns here — under "raise" the epoch replays
            # wholesale, and a death observed from a *concurrent* epoch's
            # thread must not trigger a partial replay inside this one.
            # Recomputed until quiescent: a *target* worker dying mid-replay
            # (process backend) is marked dead, its shards — including the
            # ones just moved onto it — reassign to the next survivor.
            while on_node_death == "reassign":
                dead = [n for n in self.nodes if not alive[n] and node_sources[n]]
                if not dead:
                    break
                n = dead[0]
                target = self._next_live(n, alive)
                if target is None:
                    raise RuntimeError("all nodes failed")
                shards = node_sources.pop(n)
                node_sources[n] = []
                node_sources[target].extend(shards)
                report.reassigned_shards += len(shards)
                if source is not None:
                    # the moved shards are descriptors: the reader died, the
                    # survivor re-reads them (descriptor-granular re-issue)
                    report.source_reissues += len(shards)
                # re-run all stages so far for the moved shards on the target
                replay_out: Dict[str, List[IngestItem]] = defaultdict(list)
                target_died = False

                def lost_slices_only(stage_name: str, dead_node: str,
                                     out: List[IngestItem]) -> List[IngestItem]:
                    """Replay of a shuffle-producer stage whose round is
                    still in flight must contribute only the slices whose
                    exchange copies actually died — everything the dead
                    node managed to deal (its manifest: peer segments,
                    spill files, engine-held thread buckets) is delivered
                    or redirected, and replaying it would double-count.
                    Only a process worker's *resident* slice dies with it;
                    a node that never dealt (died mid-stage) replays in
                    full."""
                    rnd = active_rounds.get(stage_name)
                    if rnd is None:
                        return out
                    m = rnd.manifests.get(dead_node)
                    if m is None:
                        return out
                    lost = {dst for dst, desc in m.get("parts", {}).items()
                            if desc["kind"] == "resident"}
                    if not lost:
                        return []
                    if rnd.key is None:
                        # narrow round: the whole output was the node's own
                        # resident slice and died with it — recompute all of
                        # it from the shards (self-contained lineage)
                        return out
                    parts = partition_items(out, rnd.key, rnd.targets)
                    return [it for dst in lost for it in parts.get(dst, ())]

                for sj in range(si + 1):
                    rp = stage_plans[sj] if use_proc else node_plans[target][sj]
                    replay_src = source is not None and not rp.upstream
                    if not rp.upstream:
                        base = shards
                        if replay_src and not use_proc:
                            # descriptors: the survivor re-reads them here
                            base = read_descs(shards)
                    else:
                        base = []
                        for up in rp.upstream:
                            base = base + replay_out[up]
                    routed = route_items(base, rp.predicates)
                    if use_proc:
                        # replay runs on the target's worker (its resident
                        # plan state absorbs the moved shards)
                        try:
                            rout, rstats = self.executor(
                                target).run_stage(
                                    plan_keys[target], sj,
                                    [] if replay_src else routed, lane=lane,
                                    epoch=epoch, live_nodes=live_nodes,
                                    max_retries=self.max_retries,
                                    source_ctx=({"adapter": source,
                                                 "descs": shards}
                                                if replay_src else None)
                                    ).result()
                        except (NodeFailure, WorkerDeath):
                            # the shards sit in node_sources[target]; the
                            # next loop pass moves them to a survivor
                            self._mark_dead(target, alive, report)
                            target_died = True
                            break
                        replay_out[rp.name] = lost_slices_only(rp.name, n, rout)
                        with rlock:
                            report.dummy_substitutions.extend(rstats["dummy"])
                    else:
                        replay_out[rp.name] = lost_slices_only(
                            rp.name, n, self._run_stage(
                                target, self.launch_remote(target, [rp])[0],
                                routed, faults, failure_counts, report, rlock))
                if not target_died:
                    for k, v in replay_out.items():
                        outputs[target][k].extend(v)

            total = sum(len(outputs[n][sp.name]) for n in self.nodes if alive[n])
            if produce is not None:
                # exchange stages keep their outputs worker-side; the
                # manifests carry the count
                total = produce.total_count
            elif sink_counts:
                # sink stages dropped their outputs worker-side; the counts
                # came back as metadata.  Alive-filtered like the outputs
                # sum: a node that died after replying gets its shards
                # replayed (re-counted via the survivor's outputs)
                total += sum(c for n2, c in sink_counts.items()
                             if alive.get(n2))
            report.stage_items[sp.name] = total

        return outputs

    # ------------------------------------------------------------- stage exec
    def _run_stage(self, node: str, sp: StagePlan, items: List[IngestItem],
                   faults: FaultInjection,
                   failure_counts: Dict[Tuple[str, str, int], int],
                   report: RunReport, rlock: threading.Lock) -> List[IngestItem]:
        """Run one stage's pipeline blocks over a node's items.

        Each block boundary is a materialization = checkpoint: on operator
        failure the block is retried from its checkpointed input; after
        ``max_retries`` the failing operator is replaced by a dummy
        pass-through (paper Sec. VI-C1).
        """
        current = items
        for bi, block in enumerate(
                sp.pipeline_blocks or [[i] for i in range(len(sp.ops))]):
            batched = bool(sp.batch_blocks[bi]) if bi < len(sp.batch_blocks) \
                else False
            checkpoint = current  # materialized input of this block
            while True:
                try:
                    out = checkpoint
                    if batched:
                        # batch tier (ISSUE 7): the whole block runs through
                        # the ops' vectorized process_batch path; injected
                        # failures fire up front (the retry reruns the block
                        # from its checkpoint either way)
                        for oi in block:
                            key = (sp.name, oi)
                            if faults.op_failures.get(key, 0) > 0:
                                faults.op_failures[key] -= 1
                                raise OperatorFailure(
                                    f"injected @ {sp.name}[{oi}]")
                        out, bstats = run_ops_batched(
                            [sp.ops[oi] for oi in block], out)
                        with rlock:
                            report.vectorized_rows += bstats["vectorized_rows"]
                            report.batch_fallbacks += bstats["batch_fallbacks"]
                            report.kernel_calls += bstats["kernel_calls"]
                    else:
                        for oi in block:
                            op = sp.ops[oi]
                            # injected failures (tests)
                            key = (sp.name, oi)
                            if faults.op_failures.get(key, 0) > 0:
                                faults.op_failures[key] -= 1
                                raise OperatorFailure(
                                    f"injected @ {sp.name}[{oi}]")
                            with op_span(op, out):
                                out = op.run(out)
                    current = out
                    break
                except OperatorFailure as e:
                    oi = block[0] if len(block) == 1 else self._failed_op_index(sp, block, e)
                    fkey = (node, sp.name, oi)
                    failure_counts[fkey] += 1
                    with rlock:
                        report.op_failures[f"{sp.name}[{oi}]"] = failure_counts[fkey]
                    if failure_counts[fkey] >= self.max_retries:
                        failing = sp.ops[oi]
                        sp.ops[oi] = PassThroughOp(replaces=failing.name)
                        with rlock:
                            report.dummy_substitutions.append(
                                f"{sp.name}[{oi}]:{type(failing).__name__}")
                    # retry block from the checkpoint (resume from previous
                    # materialization, not from scratch)
                    continue
        return current

    # shared with the process backend's worker (plan.failed_op_index)
    _failed_op_index = staticmethod(failed_op_index)

    def _next_live(self, node: str, alive: Dict[str, bool]) -> Optional[str]:
        """Round-robin successor in the slaves file order (paper Sec. VI-C1)."""
        if node in self.nodes:
            start = self.nodes.index(node)
        else:
            start = 0
        for k in range(1, len(self.nodes) + 1):
            cand = self.nodes[(start + k) % len(self.nodes)]
            if alive.get(cand):
                return cand
        return None


def ingest(plan: IngestPlan, sources: Union[Dict[str, List[IngestItem]], List[IngestItem]],
           store: DataStore, optimize: bool = True,
           faults: Optional[FaultInjection] = None) -> RunReport:
    """One-call entry point: optimize + run an ingestion plan against a store."""
    with RuntimeEngine(store) as eng:
        return eng.run(plan, sources, faults=faults, optimize=optimize)
