"""Streaming micro-batch ingestion runtime.

The batch ``RuntimeEngine`` takes a finite source list and runs every stage
behind a full barrier; this module makes the same optimized stage DAG consume
an *unbounded* source, in the shape of AsterixDB-style long-running feeds
(arXiv:1405.1705) with enrichment pipelines layered on top (arXiv:1902.08271):

* **Bounded ingest queues + backpressure** — a feeder thread routes source
  items round-robin into per-node ``queue.Queue(maxsize=...)``; when a node's
  queue is full the producer *blocks*, so queue memory is bounded no matter
  how fast data arrives.  An item the feeder could not place (``stop()`` fired
  mid-put, or every node died) is never silently dropped: it is parked in
  ``IngestQueues.unrouted``.
* **Epochs (micro-batches)** — the stream is cut into epochs by item count
  and/or wall-clock tick; each epoch runs through the existing optimized
  ``StagePlan`` pipeline (operator chains, pipeline blocks, shuffle, retry /
  dummy-substitution fault machinery are all reused via
  ``RuntimeEngine._execute`` on the persistent per-node executors).
* **Pipelined epochs** (DESIGN.md §4) — the optimizer's segment split
  (``split_pipeline_segments``) divides the DAG into an *ingest segment*
  (parse / transform / shuffle) and a *store segment* (upload + commit).
  Epoch N+1's ingest segment runs on the node executors' ``"ingest"`` lane
  while epoch N's store segment occupies the ``"store"`` lane inside a
  background committer; the DataStore commit sequencer publishes commits
  strictly in epoch order, so ``since_epoch`` readers never observe a gap.
  ``pipelined=False`` restores strictly sequential epochs.
* **Epoch-granular fault tolerance** — a node death mid-epoch aborts the
  staged epoch (its partially-written blocks are rolled back) and replays the
  whole epoch on the surviving nodes.  Committed epochs are never redone:
  ``DataStore.begin_epoch`` refuses an already-committed epoch id.
* **Exactly-once commits** — ``DataStore.commit_epoch`` publishes an epoch's
  blocks atomically (manifest temp-write + rename); ``DataAccess.since_epoch``
  lets queries consume exactly the committed epochs while ingestion continues.
* **Feed fan-out** — ``FeedDistributor`` + ``stream_ingest_multi`` fan one
  source into several plans (the language's ``FEED ... INTO plan1, plan2``),
  AsterixDB-style feed joints: enrichment pipelines share a single ingest.
* **Worker-pull sources** (ISSUE 6) — a ``SourceAdapter`` turns the source
  into shard *descriptors* (byte ranges / endpoints / seeded specs); the
  coordinator cuts epochs over descriptors and workers open/read/parse their
  shards directly into their local lanes, so zero item bytes cross the
  coordinator (``RunReport.source_coordinator_bytes == 0``).  A reader death
  re-issues the dead node's unfinished descriptors to survivors
  (``source_reissues``) before the usual invalidate-then-replay.  The pushed
  feeder path above remains as fallback and oracle for sources that cannot
  be described (feed joints, raw iterators).
"""
from __future__ import annotations

import itertools
import queue
import threading
import time
from collections import defaultdict, deque
from dataclasses import dataclass, field
from typing import (Any, Dict, Iterable, Iterator, List, Optional, Sequence,
                    Tuple, Union)

from .. import tracing
from .items import IngestItem
from .liveness import LivenessMonitor
from .optimizer import IngestionOptimizer, split_pipeline_segments
from .plan import IngestPlan, StagePlan, coerce_bool, cone_replay_capable
from .runtime import (FaultInjection, NodeFailure, RunReport, RuntimeEngine,
                      derive_spill_bytes)
from .sources import ShardDescriptor, SourceAdapter, build_source
from .store import DataStore


def _unit_rows(vals: Iterable[Any]) -> int:
    """Rows carried by a list of replay units — items report their actual
    row count, shard descriptors their estimate (at least one row each).
    This is the unit of ``RunReport.replayed_rows``: the cone-vs-whole-epoch
    comparison the death-matrix tests assert on (ISSUE 8)."""
    total = 0
    for v in vals:
        nr = getattr(v, "nrows", None)
        if callable(nr):
            total += int(nr())
        else:
            total += max(1, int(getattr(v, "est_items", 1)))
    return total


@dataclass
class EpochPolicy:
    """When to cut an epoch, and how big the ingest queues are.

    An epoch closes at the *first* threshold hit: ``items`` source items,
    ``bytes`` of queued payload (the first slice of adaptive epoch sizing —
    a burst of fat items no longer inflates the staged epoch), or ``seconds``
    of wall clock since the epoch's first item.  ``capacity`` bounds each
    node's ingest queue (the backpressure seam).  The declarative surface is
    ``STREAM WITH EPOCHS(items=…, seconds=…, bytes=…, capacity=…,
    adaptive=…)``.

    **Adaptive sizing** (ROADMAP "adaptive epoch sizing, part 2"): with
    ``adaptive=True`` the engine feeds every committed epoch's commit
    latency into :meth:`observe_commit`, which keeps an EWMA of the latency
    and rescales the ``items``/``bytes`` thresholds toward
    ``target_commit_s`` — commits lagging the target narrow the cut,
    fast commits widen it.  Each step is clamped to ``grow_limit`` per
    observation and the cut is bounded by ``min_items``/``max_items``, so a
    single outlier epoch cannot whiplash the stream.
    """

    items: int = 64
    seconds: Optional[float] = None
    bytes: Optional[int] = None
    capacity: int = 64
    adaptive: bool = False
    target_commit_s: float = 0.25
    alpha: float = 0.3          # EWMA smoothing factor
    grow_limit: float = 2.0     # max per-observation rescale (and 1/x shrink)
    min_items: int = 1
    max_items: int = 1 << 16
    _ewma: Optional[float] = field(default=None, init=False, repr=False,
                                   compare=False)

    @classmethod
    def from_stream_config(cls, cfg: Optional[Dict[str, Any]],
                           default: "EpochPolicy") -> "EpochPolicy":
        cfg = cfg or {}
        return cls(items=int(cfg.get("items", default.items)),
                   seconds=cfg.get("seconds", default.seconds),
                   bytes=(int(cfg["bytes"]) if cfg.get("bytes") is not None
                          else default.bytes),
                   capacity=int(cfg.get("capacity", default.capacity)),
                   adaptive=coerce_bool(cfg.get("adaptive", default.adaptive)),
                   target_commit_s=float(cfg.get("target_commit_s",
                                                 default.target_commit_s)))

    def observe_commit(self, latency_s: float) -> None:
        """Feed one committed epoch's commit latency into the controller.

        No-op unless ``adaptive``; otherwise updates the EWMA and rescales
        the items/bytes thresholds by ``clamp(target / ewma)``."""
        if not self.adaptive or latency_s <= 0:
            return
        a = self.alpha
        self._ewma = (latency_s if self._ewma is None
                      else a * latency_s + (1.0 - a) * self._ewma)
        ratio = self.target_commit_s / self._ewma
        ratio = min(self.grow_limit, max(1.0 / self.grow_limit, ratio))
        before = self.items
        self.items = max(self.min_items,
                         min(self.max_items, int(round(self.items * ratio))))
        if self.bytes is not None and before > 0:
            # bytes moves in lockstep with the *realized* items step, so it
            # inherits the min/max clamp: a saturated items cut stops the
            # bytes backstop from drifting unboundedly too
            self.bytes = max(1, int(round(self.bytes * self.items / before)))


@dataclass
class StreamFaultInjection:
    """Deterministic streaming fault hooks (tests/benchmarks).

    ``op_failures`` uses the batch engine's (stage, op_index) -> count format
    and is shared across epochs; ``node_death_in_epoch`` kills a node while
    the given epoch index is mid-flight (after its first stage, before
    commit) — exercising abort + replay.  ``node_death_at`` places the death
    precisely: ``(node, epoch_index) -> stage name`` dies right after that
    stage completes on the node, which is how the chaos harness (ISSUE 8)
    keys kill events to epoch·stage·node — a death after the ingest
    segment's *last* stage exercises the lineage-cone replay path.
    """

    op_failures: Dict[Tuple[str, int], int] = field(default_factory=dict)
    node_death_in_epoch: Dict[str, int] = field(default_factory=dict)
    node_death_at: Dict[Tuple[str, int], str] = field(default_factory=dict)


@dataclass
class EpochReport:
    """What the engine observed for one committed epoch."""

    epoch: int
    items_in: int                 # source items consumed by the epoch
    n_blocks: int                 # blocks the commit published
    attempts: int                 # 1 = clean; >1 = replayed after node death
    commit_latency_s: float       # epoch cut -> manifest rename landed
    run: RunReport = field(default_factory=RunReport)


@dataclass
class StreamReport:
    """Aggregate of a ``run_stream`` call."""

    epochs: List[EpochReport] = field(default_factory=list)
    node_failures: List[str] = field(default_factory=list)
    replayed_epochs: List[int] = field(default_factory=list)
    total_items: int = 0
    wall_time_s: float = 0.0
    spawn_retries: int = 0        # process-worker spawn attempts beyond the first
    liveness_deaths: List[Tuple[str, float]] = field(default_factory=list)
    # ^ (node, seconds-to-detection) for deaths the heartbeat monitor declared
    host_partitions: List[Tuple[str, List[str], float]] = field(
        default_factory=list)
    # ^ (host, member nodes, age) for hosts the quorum declared as one unit
    sweep_skipped_remote: int = 0  # shm sweeps skipped: worker not local

    def committed_epoch_ids(self) -> List[int]:
        return [e.epoch for e in self.epochs]

    def commit_latencies(self) -> List[float]:
        return [e.commit_latency_s for e in self.epochs]

    def items_per_sec(self) -> float:
        return self.total_items / self.wall_time_s if self.wall_time_s else 0.0

    # --------------------------- worker-pull source aggregates (ISSUE 6) ---
    def source_coordinator_bytes(self) -> int:
        """Item bytes that crossed the coordinator on the source hop —
        zero for descriptor-backed (worker-pull) sources."""
        return sum(e.run.source_coordinator_bytes for e in self.epochs)

    def source_descriptors(self) -> int:
        """Shard descriptors issued to workers across all committed epochs."""
        return sum(e.run.source_descriptors for e in self.epochs)

    def vectorized_rows(self) -> int:
        """Rows that went through the batch operator tier (ISSUE 7)."""
        return sum(e.run.vectorized_rows for e in self.epochs)

    def batch_fallbacks(self) -> int:
        """Batched blocks that fell back to the scalar iterator path."""
        return sum(e.run.batch_fallbacks for e in self.epochs)

    def kernel_calls(self) -> int:
        """Kernel launches in batch blocks across committed epochs."""
        return sum(e.run.kernel_calls for e in self.epochs)

    def source_reissues(self) -> int:
        """Descriptors re-issued to survivors after a reader death."""
        return sum(e.run.source_reissues for e in self.epochs)

    # ------------------------------------- lineage-cone recovery (ISSUE 8) ---
    def cone_replays(self) -> int:
        """Deaths recovered by replaying only the dead node's lineage cone
        (zero when every recovery fell back to whole-epoch replay)."""
        return sum(e.run.cone_replays for e in self.epochs)

    def replayed_rows(self) -> int:
        """Rows recomputed by recovery — a cone replay contributes only the
        dead node's share, a whole-epoch replay the full epoch."""
        return sum(e.run.replayed_rows for e in self.epochs)

    # ----------------------------------------- degraded exchange (ISSUE 9) ---
    def degraded_exchange_rounds(self) -> int:
        """Exchange rounds that moved at least one partition cross-host in
        degraded mode (streamed spill files instead of shm segments)."""
        return sum(e.run.degraded_exchange_rounds for e in self.epochs)

    def degraded_peer_bytes(self) -> int:
        """Partition bytes that crossed host-to-host over the stream path."""
        return sum(e.run.degraded_peer_bytes for e in self.epochs)

    # ------------------------------------------- columnar plane (ISSUE 10) ---
    def columnar_rounds(self) -> int:
        """Exchange rounds that moved at least one partition as a
        ColumnarBatch column buffer (no per-item pickling on the edge)."""
        return sum(e.run.columnar_rounds for e in self.epochs)

    def columnar_bytes(self) -> int:
        """Partition bytes that crossed stage edges in columnar form."""
        return sum(e.run.columnar_bytes for e in self.epochs)

    def columnar_fallbacks(self) -> int:
        """Producers on columnar rounds whose output wouldn't pack and fell
        back to the scalar item path (counted, never wrong)."""
        return sum(e.run.columnar_fallbacks for e in self.epochs)


class IngestQueues:
    """Per-node bounded ingest queues fed from an unbounded source.

    The feeder thread pulls from the source iterator and round-robins items
    across node queues with *blocking* puts — the backpressure seam: a slow
    pipeline stalls the producer instead of growing memory.  ``mark_dead``
    removes a node from the routing set; items already queued on a dead node
    are still drained (and re-routed to live nodes by the epoch cutter).

    **Manual mode** (``IngestQueues.manual``, used by feed joints): no feeder
    thread is started — an external distributor pushes items with ``put`` and
    signals end-of-stream with ``close``.

    An item in the feeder's (or distributor's) hand when ``stop()`` fires, or
    when every node has died, is recorded in ``unrouted`` — never silently
    dropped: the stream's producer offset can be rewound by exactly
    ``len(unrouted)`` items on restart.
    """

    def __init__(self, source: Optional[Iterable[IngestItem]], nodes: Sequence[str],
                 capacity: int = 64) -> None:
        self.nodes = list(nodes)
        self.capacity = capacity
        self.queues: Dict[str, "queue.Queue[IngestItem]"] = {
            n: queue.Queue(maxsize=capacity) for n in self.nodes}
        self._live = {n: True for n in self.nodes}
        self._rr = itertools.cycle(self.nodes)
        self._stop = threading.Event()
        self.exhausted = threading.Event()
        self.produced = 0   # items pulled from the source / pushed by put()
        self.items_routed = 0       # successfully placed items …
        self.bytes_routed = 0       # … and their payload bytes (for the
        # spill-aware shuffle budget: avg_item_bytes() estimates how much
        # memory the queues themselves can pin at full capacity)
        self.unrouted: List[IngestItem] = []   # in-flight items never placed
        self._thread: Optional[threading.Thread] = None
        if source is not None:
            self._source = iter(source)
            self._thread = threading.Thread(target=self._feed, daemon=True)
            self._thread.start()

    @classmethod
    def manual(cls, nodes: Sequence[str], capacity: int = 64) -> "IngestQueues":
        """Queues without a feeder thread (fed by a FeedDistributor)."""
        return cls(None, nodes, capacity)

    # ------------------------------------------------------------------ feeder
    def _next_live(self) -> Optional[str]:
        """Next live node in round-robin order; None when none remain (or the
        queues were stopped) — never spins on an all-dead cycle."""
        for _ in range(len(self.nodes)):
            n = next(self._rr)
            if self._live.get(n):
                return n
        return None

    def _route(self, item: IngestItem) -> bool:
        """Blocking put with liveness re-checks.  False when the item could
        not be placed (stop() fired mid-put, or all nodes are dead)."""
        target = self._next_live()
        while target is not None and not self._stop.is_set():
            try:
                self.queues[target].put(item, timeout=0.05)
                self.items_routed += 1
                self.bytes_routed += item.nbytes()
                return True
            except queue.Full:
                # blocked: backpressure — re-check liveness so items never
                # pile onto a node that died while we waited
                if not self._live.get(target):
                    target = self._next_live()
        return False

    def _feed(self) -> None:
        for item in self._source:
            self.produced += 1
            if not self._route(item):
                # the in-flight item is parked, not lost (satellite of ISSUE 2)
                self.unrouted.append(item)
                break
        self.exhausted.set()

    # --------------------------------------------------------- manual producer
    def put(self, item: IngestItem) -> bool:
        """Feed-joint surface: route one item (blocking).  Returns False — and
        records the item in ``unrouted`` — when it could not be placed."""
        self.produced += 1
        if self._route(item):
            return True
        self.unrouted.append(item)
        return False

    def close(self) -> None:
        """Feed-joint end-of-stream (what source exhaustion is to the feeder)."""
        self.exhausted.set()

    # ------------------------------------------------------------------- drain
    def avg_item_bytes(self, default: int = 64 << 10) -> int:
        """Observed mean payload size of routed items (``default`` until the
        first item lands) — the ingest queues' share of a memory budget is
        ``capacity * len(nodes) * avg_item_bytes()``."""
        if not self.items_routed:
            return default
        return max(1, self.bytes_routed // self.items_routed)

    def cut_epoch(self, max_items: int, tick_s: Optional[float] = None,
                  max_bytes: Optional[int] = None
                  ) -> Dict[str, List[IngestItem]]:
        """Drain queues into one epoch: up to ``max_items`` total (and/or
        ``max_bytes`` of payload — the byte cut closes the epoch at the first
        item that reaches the threshold), or whatever arrived when ``tick_s``
        elapses.

        The tick deadline arms on **entry** (bugfix, ISSUE 6): it used to arm
        only after the first item landed, so an idle stream never honored the
        wall-clock cut and a slow trickle held the epoch open indefinitely.
        An idle tick now returns an *empty* batch at the deadline — callers
        distinguish it from end-of-stream via :meth:`at_eof`."""
        batch: Dict[str, List[IngestItem]] = {n: [] for n in self.nodes}
        count = 0
        nbytes = 0
        deadline = (time.monotonic() + tick_s) if tick_s is not None else None
        while count < max_items and (max_bytes is None or nbytes < max_bytes):
            got = False
            for n in self.nodes:
                if count >= max_items or (max_bytes is not None
                                          and nbytes >= max_bytes):
                    break
                try:
                    it = self.queues[n].get_nowait()
                    batch[n].append(it)
                    count += 1
                    nbytes += it.nbytes()
                    got = True
                except queue.Empty:
                    continue
            if got:
                continue
            if self.at_eof():
                break
            if deadline is not None and time.monotonic() >= deadline:
                break
            # bounded wait, never past the tick deadline, waking early on
            # end-of-stream (the old code slept a blind 1 ms per pass)
            wait = 0.001
            if deadline is not None:
                wait = max(0.0005, min(wait, deadline - time.monotonic()))
            self.exhausted.wait(wait)
        return batch

    def at_eof(self) -> bool:
        """End of stream: the producer is done and every queue is drained
        (how callers tell an empty wall-clock tick from stream end)."""
        return (self.exhausted.is_set()
                and all(q.empty() for q in self.queues.values()))

    def mark_dead(self, node: str) -> None:
        self._live[node] = False

    def qsizes(self) -> Dict[str, int]:
        return {n: q.qsize() for n, q in self.queues.items()}

    def stop(self) -> None:
        self._stop.set()


class FeedDistributor:
    """AsterixDB-style feed joint (arXiv:1405.1705): one pull from the source,
    fanned out to several plans' ingest queues.

    Every joint receives every item (enrichment pipelines share the ingest);
    a slow pipeline exerts backpressure on the shared feed through its
    blocking ``put``.  A stopped or fully-dead pipeline fails its puts fast —
    the item is recorded unrouted on *that joint only* and the feed keeps
    serving the healthy pipelines.
    """

    def __init__(self, source: Iterable[IngestItem],
                 joints: Sequence[IngestQueues]) -> None:
        self.joints = list(joints)
        self.fanned_out = 0   # items pulled from the shared source
        self._source = iter(source)
        self._thread = threading.Thread(target=self._pump, daemon=True)
        self._thread.start()

    def _pump(self) -> None:
        active = list(self.joints)
        try:
            for item in self._source:
                self.fanned_out += 1
                for j in list(active):
                    if not j.put(item):
                        # the joint stopped (its pipeline finished or died):
                        # detach it so a long stream doesn't pile the whole
                        # remainder into its unrouted list
                        active.remove(j)
                if not active:
                    break
        finally:
            for j in self.joints:
                j.close()

    def join(self, timeout: Optional[float] = None) -> None:
        self._thread.join(timeout)


# --------------------------------------------------------------------------
# Pipelined epoch committer
# --------------------------------------------------------------------------
@dataclass
class _EpochJob:
    """A cut epoch whose ingest segment has run, awaiting store + commit.

    ``node_set`` is the live set the ingest segment executed on: the
    segment's outputs live in *node-resident* exchange buckets pinned to
    those nodes (ISSUE 5), so the store segment may consume them in place
    only while every one of them is still alive — otherwise the committer
    replays the whole epoch from the retained ``batch``.

    With a worker-pull ``source`` (ISSUE 6), ``batch``/``node_sources`` hold
    :class:`~repro.core.sources.ShardDescriptor` assignments instead of
    items — the retained descriptors are the replay unit: re-reading them is
    deterministic, so a replayed epoch commits the same rows."""

    eid: int
    epoch_index: int
    batch: Dict[str, List[Any]]          # items, or shard descriptors
    node_sources: Dict[str, List[Any]]
    outputs: Dict[str, Dict[str, List[IngestItem]]]
    faults: FaultInjection           # this epoch's injection view
    ereport: RunReport
    attempts: int
    items_in: int
    t_cut: float
    node_set: List[str] = field(default_factory=list)
    source: Optional[SourceAdapter] = None   # set => descriptor-backed epoch


class _EpochCommitter:
    """Background store-segment worker for pipelined epochs.

    A single FIFO thread runs each staged epoch's commit-side stages on the
    node executors' ``"store"`` lane and publishes the commit; the bounded
    job queue is the pipeline depth (cut N+1 blocks while N+1-depth epochs
    are still staged).  Processing order + the DataStore commit sequencer
    guarantee commits land strictly in epoch order.
    """

    def __init__(self, engine: "StreamingRuntimeEngine",
                 stage_plans: List[StagePlan], split: int,
                 faults: StreamFaultInjection, sreport: StreamReport,
                 queues: Optional[IngestQueues], max_inflight: int = 2,
                 policy: Optional[EpochPolicy] = None) -> None:
        self.engine = engine
        self.stage_plans = stage_plans
        self.split = split
        self.faults = faults
        self.sreport = sreport
        self.queues = queues
        self.policy = policy
        self._jobs: "queue.Queue[Optional[_EpochJob]]" = queue.Queue(
            maxsize=max(1, max_inflight))
        self._error: Optional[BaseException] = None
        self._thread = threading.Thread(target=self._loop, daemon=True,
                                        name="epoch-committer")
        self._thread.start()

    # ----------------------------------------------------------------- public
    def submit(self, job: _EpochJob) -> None:
        self.raise_if_failed()
        self._jobs.put(job)   # blocks: bounds the number of in-flight epochs

    def close(self) -> None:
        self._jobs.put(None)
        self._thread.join()

    def raise_if_failed(self) -> None:
        if self._error is not None:
            err, self._error = self._error, None
            raise err

    # ------------------------------------------------------------------- loop
    def _loop(self) -> None:
        while True:
            job = self._jobs.get()
            if job is None:
                return
            if self._error is not None:
                continue   # drain remaining jobs so submit() never deadlocks
            try:
                with tracing.span("ib.epoch", epoch=job.eid):
                    self._commit_job(job)
            except BaseException as e:
                self._error = e

    def _commit_job(self, job: _EpochJob) -> None:
        """Run the epoch's store segment and commit.

        The ingest segment's outputs live in node-resident exchange buckets
        (pinned rounds, ISSUE 5): the first attempt adopts them and runs
        only the store segment, in place, on the same node set.  If any
        ingest contributor has died since — its resident buckets died with
        it — or a later attempt is needed, the epoch's exchange state is
        invalidated and the *whole epoch* replays from the retained raw
        ``batch`` on the survivors (nothing committed yet, so the replay is
        exactly-once).  The executing node set is pinned per attempt — a
        death flipping ``alive`` from the ingest thread mid-attempt cannot
        silently drop a node's inputs.

        Before falling back, an ingest-contributor death on a cone-capable
        plan (ISSUE 8) first tries the narrower repair: strip only the dead
        node's exchange contribution and re-run the ingest segment for just
        its retained shards — survivors' resident buckets stay live and the
        store segment proceeds in place."""
        eng, store = self.engine, self.engine.store
        first = True
        while True:
            if not first:
                job.attempts += 1
            # a SIGTERM'd worker whose death never surfaced as a stage
            # failure (it finished its segment work, then died) is caught
            # here by its pipe EOF, before the store slice is submitted to it
            for n in eng._probe_executors():
                eng._record_death(n, job.eid, self.sreport, self.queues)
            if not any(eng.alive.values()):
                raise RuntimeError("all nodes failed")
            live = [n for n in eng.nodes if eng.alive.get(n)]
            in_place = first and not (set(job.node_set) - set(live))
            if (not in_place and first and eng.cone_recovery
                    and self.split > 0
                    and not getattr(eng.shuffle, "synchronous", False)
                    and cone_replay_capable(self.stage_plans, self.split)):
                dead = [n for n in job.node_set if n not in live]
                patch = eng._cone_patch(job.eid, dead, job.batch,
                                        self.stage_plans, self.split,
                                        job.faults, job.ereport, job.source)
                if patch is not None:
                    for n in dead:
                        job.batch[n] = []
                    for n, extra in patch.items():
                        job.batch.setdefault(n, []).extend(extra)
                    job.node_sources = job.batch
                    job.node_set = live
                    if job.eid not in self.sreport.replayed_epochs:
                        self.sreport.replayed_epochs.append(job.eid)
                    in_place = True
                else:
                    # the patch itself lost a node; its partial merge was
                    # torn down with the epoch's exchange state — recompute
                    # the live set and take the whole-epoch road
                    live = [n for n in eng.nodes if eng.alive.get(n)]
                    if not live:
                        raise RuntimeError("all nodes failed")
            first = False
            if not in_place:
                # resident ingest outputs are stale or lost: drop the
                # epoch's exchange rounds everywhere and recompute from the
                # retained batch
                eng.invalidate_exchange(job.eid)
                if job.source is not None:
                    # descriptor replay bookkeeping: the dead node's
                    # unfinished shards are handed to survivors
                    job.ereport.source_reissues += eng._count_lost(
                        job.batch, live)
                job.node_sources = eng._redistribute(job.batch, live)
                job.batch = job.node_sources
                job.outputs = {n: defaultdict(list) for n in eng.nodes}
                job.ereport.replayed_rows += _unit_rows(
                    it for v in job.node_sources.values() for it in v)
            store.begin_epoch(job.eid)
            base_items = job.ereport.source_items
            try:
                if not in_place and self.split > 0:
                    # recompute the ingest segment on the *ingest* lanes —
                    # the lane discipline of the original run: a stage's
                    # resident operator state (its output generator) is only
                    # ever driven by one lane, never concurrently from here
                    # and a newer epoch's ingest.  Its rounds re-pin and the
                    # store slice below adopts them, exactly like a clean run.
                    eng._execute(self.stage_plans, job.node_sources,
                                 job.faults, job.ereport, eng.alive,
                                 on_node_death="raise", lane="ingest",
                                 epoch=job.eid, outputs=job.outputs,
                                 start_stage=0, end_stage=self.split,
                                 node_set=live, source=job.source)
                eng._execute(self.stage_plans, job.node_sources, job.faults,
                             job.ereport, eng.alive, on_node_death="raise",
                             lane="store", epoch=job.eid, outputs=job.outputs,
                             start_stage=self.split, node_set=live,
                             source=job.source)
                if job.source is not None and self.split == 0:
                    # single-segment DAG: the shards were read just now, on
                    # the store lane — items_in is the worker-reported count
                    job.items_in = job.ereport.source_items - base_items
                self._publish(job)
                return
            except NodeFailure as e:
                store.abort_epoch(job.eid)
                eng._note_death(str(e), job.eid, self.sreport, self.queues)

    def _publish(self, job: _EpochJob) -> None:
        entry = self.engine.store.commit_epoch(job.eid, n_items=job.items_in)
        latency = time.time() - job.t_cut
        self.sreport.epochs.append(EpochReport(
            epoch=job.eid, items_in=job.items_in, n_blocks=entry.n_blocks,
            attempts=job.attempts, commit_latency_s=latency,
            run=job.ereport))
        self.sreport.total_items += job.items_in
        if self.policy is not None:
            # adaptive epoch sizing: the cut loop reads the rescaled
            # thresholds at its next epoch cut
            self.policy.observe_commit(latency)
        with self.engine._progress:
            self.engine._progress.notify_all()   # wake idle cut loops


class StreamingRuntimeEngine(RuntimeEngine):
    """Micro-batch streaming over the batch engine's optimized stage DAG.

    Epoch-cut knobs (``epoch_items`` / ``epoch_seconds`` / ``queue_capacity``)
    default from ``plan.stream_config`` — the declarative
    ``STREAM WITH EPOCHS(...)`` surface — and can be overridden per engine.

    ``pipelined=True`` (default) overlaps epoch N+1's ingest segment with
    epoch N's store/commit segment (DESIGN.md §4); ``max_inflight_epochs``
    bounds how many staged epochs the committer may hold.  Committed epoch
    ids are gap-free and in-order in either mode.
    """

    def __init__(self, store: DataStore, optimizer: Optional[IngestionOptimizer] = None,
                 max_retries: int = 3, epoch_items: int = 64,
                 epoch_seconds: Optional[float] = None,
                 epoch_bytes: Optional[int] = None,
                 queue_capacity: int = 64,
                 pipelined: bool = True,
                 max_inflight_epochs: int = 2,
                 shuffle_spill_bytes: Optional[int] = None,
                 shuffle_synchronous: bool = False,
                 backend: str = "thread",
                 memory_budget_bytes: Optional[int] = None,
                 epoch_adaptive: bool = False,
                 epoch_target_commit_s: Optional[float] = None,
                 cone_recovery: bool = True,
                 heartbeat_interval_s: Optional[float] = None,
                 heartbeat_miss: int = 4,
                 transport: str = "pipe",
                 node_hosts: Optional[Dict[str, str]] = None,
                 network_chaos: bool = False,
                 columnar: bool = True) -> None:
        super().__init__(store, optimizer, max_retries,
                         shuffle_spill_bytes=shuffle_spill_bytes,
                         shuffle_synchronous=shuffle_synchronous,
                         backend=backend,
                         memory_budget_bytes=memory_budget_bytes,
                         transport=transport, node_hosts=node_hosts,
                         network_chaos=network_chaos, columnar=columnar)
        self.epoch_items = epoch_items
        self.epoch_seconds = epoch_seconds
        self.epoch_bytes = epoch_bytes
        self.epoch_adaptive = epoch_adaptive
        self.epoch_target_commit_s = epoch_target_commit_s
        self.queue_capacity = queue_capacity
        self.pipelined = pipelined
        self.max_inflight_epochs = max_inflight_epochs
        self.alive = {n: True for n in self.nodes}
        # ----------------------------------------------- robustness (ISSUE 8)
        # cone_recovery=False forces every node death down the whole-epoch
        # replay road — the correctness oracle the death-matrix tests compare
        # cone-replayed stores against byte-for-byte
        self.cone_recovery = cone_recovery
        # heartbeat_interval_s arms the liveness monitor (process backend):
        # a worker that stops answering pings for heartbeat_miss intervals is
        # declared dead even though its pipe never closed (SIGSTOP / wedge)
        self.heartbeat_interval_s = heartbeat_interval_s
        self.heartbeat_miss = heartbeat_miss
        self.liveness: Optional[LivenessMonitor] = None
        # progress pulse: committers notify on publish/death so idle waiters
        # (the descriptor cut loop) sleep on a condition instead of spinning
        self._progress = threading.Condition()

    # ----------------------------------------------------------------- config
    def _config(self, plan: IngestPlan) -> EpochPolicy:
        default = EpochPolicy(items=self.epoch_items,
                              seconds=self.epoch_seconds,
                              bytes=self.epoch_bytes,
                              capacity=self.queue_capacity,
                              adaptive=self.epoch_adaptive)
        if self.epoch_target_commit_s is not None:
            default.target_commit_s = self.epoch_target_commit_s
        return EpochPolicy.from_stream_config(
            getattr(plan, "stream_config", None), default)

    # ------------------------------------------------- liveness (ISSUE 8)
    def _start_liveness(self) -> None:
        """Arm the heartbeat monitor over the process workers' control
        pipes.  No-op for the thread backend (an in-process executor cannot
        wedge independently of the coordinator) or when no interval is
        configured — pipe-EOF detection then remains the only death signal."""
        if self.heartbeat_interval_s is None or self.backend != "process":
            return
        mon = LivenessMonitor(interval_s=self.heartbeat_interval_s,
                              miss_threshold=self.heartbeat_miss)
        for n in self.nodes:
            # the host label opts the node into the per-host partition
            # quorum (ISSUE 9): a host whose workers all go silent together
            # is declared partitioned as one unit
            mon.watch(n, self.executor(n), host=self.node_hosts.get(n))
        mon.start()
        self.liveness = mon

    def _stop_liveness(self, sreport: StreamReport) -> None:
        mon, self.liveness = self.liveness, None
        if mon is not None:
            mon.stop()
            sreport.liveness_deaths.extend(mon.deaths)
            sreport.host_partitions.extend(mon.partitions)

    def _update_spill_budget(self, queues: IngestQueues) -> None:
        """Spill-aware shuffle sizing: re-derive ``spill_bytes`` from the
        shared memory budget minus what the ingest queues can pin at full
        capacity (observed mean item size) — re-evaluated at every epoch cut
        so the split adapts as the stream's item sizes drift."""
        if self.memory_budget_bytes is None or self._explicit_spill:
            return
        reserved = queues.capacity * len(self.nodes) * queues.avg_item_bytes()
        self.shuffle.spill_bytes = derive_spill_bytes(
            self.memory_budget_bytes, reserved)

    # -------------------------------------------------------------------- run
    def run_stream(self, plan: IngestPlan,
                   source: Union[Iterable[IngestItem], SourceAdapter,
                                 None] = None,
                   faults: Optional[StreamFaultInjection] = None,
                   optimize: bool = True,
                   max_epochs: Optional[int] = None,
                   queues: Optional[IngestQueues] = None) -> StreamReport:
        """Consume ``source`` until it is exhausted or ``max_epochs`` epochs
        have committed.  ``source`` is either a plain item iterator (legacy
        pushed path: a feeder thread routes items through coordinator-side
        queues) or a :class:`~repro.core.sources.SourceAdapter` (worker-pull
        path, ISSUE 6: epochs are cut over shard descriptors and workers read
        their shards directly).  Alternatively pass pre-built ``queues`` (a
        feed joint) instead of a source; with neither, a plan-level
        ``SOURCE ...`` spec compiles to an adapter."""
        adapter: Optional[SourceAdapter] = None
        if isinstance(source, SourceAdapter):
            adapter, source = source, None
        elif (source is None and queues is None
              and getattr(plan, "source_spec", None)):
            adapter = build_source(plan.source_spec)
        if sum(x is not None for x in (source, queues, adapter)) != 1:
            raise ValueError("run_stream needs exactly one of source/queues "
                             "(or a plan-level SOURCE spec)")
        t0 = time.time()
        faults = faults or StreamFaultInjection()
        sreport = StreamReport()
        if self.backend == "process":
            # fork the node workers before the feeder/committer threads exist
            self.prewarm_executors()
        self._start_liveness()

        # compile + optimize ONCE; every epoch reuses the same stage plans —
        # and the node executors keep their clone for the whole stream
        stage_plans = plan.compile()
        if optimize:
            stage_plans = self.optimizer.optimize(stage_plans)
        split = split_pipeline_segments(stage_plans)

        # store placement marks must agree with this engine's liveness view —
        # a fresh engine on a store a previous stream left marks on starts
        # from its own (all-live) map
        for n in self.nodes:
            (self.store.mark_node_live if self.alive[n]
             else self.store.mark_node_dead)(n)

        policy = self._config(plan)
        eid = self.store.next_epoch_id()
        if adapter is not None:
            # worker-pull path: no feeder thread, no coordinator queues —
            # the coordinator only plans *where* data is read
            try:
                self._run_pulled(stage_plans, split, adapter, faults, sreport,
                                 policy, max_epochs, eid)
            finally:
                self._stop_liveness(sreport)
                self.shuffle.drain()
                self.store.flush_manifest()
            sreport.spawn_retries = self._spawn_retry_total()
            sreport.sweep_skipped_remote = self._sweep_skip_total()
            sreport.wall_time_s = time.time() - t0
            return sreport
        if queues is None:
            queues = IngestQueues(source, self.nodes, policy.capacity)
        try:
            if self.pipelined:
                self._run_pipelined(stage_plans, split, queues, faults, sreport,
                                    policy, max_epochs, eid)
            else:
                epoch_index = 0
                while max_epochs is None or epoch_index < max_epochs:
                    self._update_spill_budget(queues)
                    batch = queues.cut_epoch(policy.items, policy.seconds,
                                             policy.bytes)
                    if not any(len(v) for v in batch.values()):
                        if queues.at_eof():
                            break   # end of stream
                        continue    # empty wall-clock tick: nothing to stage
                    ereport = self._run_epoch(eid, epoch_index, batch,
                                              stage_plans, faults, sreport, queues)
                    sreport.epochs.append(ereport)
                    sreport.total_items += ereport.items_in
                    policy.observe_commit(ereport.commit_latency_s)
                    eid += 1
                    epoch_index += 1
        finally:
            self._stop_liveness(sreport)
            queues.stop()
            self.shuffle.drain()
            self.store.flush_manifest()   # compact the epoch journal
        sreport.spawn_retries = self._spawn_retry_total()
        sreport.sweep_skipped_remote = self._sweep_skip_total()
        sreport.wall_time_s = time.time() - t0
        return sreport

    # -------------------------------------------------------------- pipelined
    def _run_pipelined(self, stage_plans: List[StagePlan], split: int,
                       queues: IngestQueues, faults: StreamFaultInjection,
                       sreport: StreamReport, policy: EpochPolicy,
                       max_epochs: Optional[int], eid: int) -> None:
        """Overlapped epochs: this thread cuts epoch N+1 and runs its ingest
        segment (lane "ingest") while the committer thread runs epoch N's
        store segment + commit (lane "store")."""
        committer = _EpochCommitter(self, stage_plans, split, faults, sreport,
                                    queues, max_inflight=self.max_inflight_epochs,
                                    policy=policy)
        epoch_index = 0
        try:
            while max_epochs is None or epoch_index < max_epochs:
                committer.raise_if_failed()
                self._update_spill_budget(queues)
                batch = queues.cut_epoch(policy.items, policy.seconds,
                                         policy.bytes)
                if not any(len(v) for v in batch.values()):
                    if queues.at_eof():
                        break   # end of stream
                    continue    # empty wall-clock tick: nothing to stage
                t_cut = time.time()
                job = self._ingest_segment(eid, epoch_index, batch, stage_plans,
                                           split, faults, sreport, queues, t_cut)
                committer.submit(job)
                eid += 1
                epoch_index += 1
        finally:
            committer.close()
        committer.raise_if_failed()

    # ------------------------------------------------------------ worker-pull
    @staticmethod
    def _count_lost(batch: Dict[str, List[Any]], live: Sequence[str]) -> int:
        """Descriptors assigned to nodes no longer in ``live`` — the shards a
        replay re-issues to survivors (``source_reissues``)."""
        live_set = set(live)
        return sum(len(v) for n, v in batch.items() if v and n not in live_set)

    def _cut_descriptors(self, pending: "deque[ShardDescriptor]",
                         adapter: SourceAdapter,
                         policy: EpochPolicy) -> List[ShardDescriptor]:
        """Epoch cut over shard descriptors.

        The coordinator never sees item bytes, so the cut budgets on the
        adapter's *estimates* (``est_items``/``est_bytes``, each descriptor
        counting at least one item); the authoritative per-epoch item count
        is worker-reported after the reads (``RunReport.source_items``).
        The ``seconds`` deadline arms on entry — an idle tick cuts whatever
        descriptors are pending, exactly like the fixed ``cut_epoch``."""
        deadline = (time.monotonic() + policy.seconds
                    if policy.seconds is not None else None)
        batch: List[ShardDescriptor] = []
        est_items = 0
        est_bytes = 0
        idle_wait = 0.005

        def full() -> bool:
            return (est_items >= policy.items
                    or (policy.bytes is not None
                        and est_bytes >= policy.bytes))

        while True:
            while pending and not full():
                d = pending.popleft()
                batch.append(d)
                est_items += max(1, int(getattr(d, "est_items", 1)))
                est_bytes += int(getattr(d, "est_bytes", 0))
            if full():
                break
            more = adapter.poll()
            if more:
                pending.extend(more)
                idle_wait = 0.005
                continue
            if adapter.exhausted():
                break
            if deadline is not None and time.monotonic() >= deadline:
                break
            # idle wait on the engine's progress condition instead of the old
            # 5 ms busy sleep (satellite of ISSUE 8): commit/death events wake
            # us immediately, and pure adapter polling backs off to 50 ms so
            # an idle stream doesn't spin a core.  The tick deadline caps the
            # wait so an armed wall-clock cut still fires on time.
            wait = idle_wait
            if deadline is not None:
                wait = max(0.0005, min(wait, deadline - time.monotonic()))
            with self._progress:
                self._progress.wait(wait)
            idle_wait = min(idle_wait * 2, 0.05)
        return batch

    def _run_pulled(self, stage_plans: List[StagePlan], split: int,
                    adapter: SourceAdapter, faults: StreamFaultInjection,
                    sreport: StreamReport, policy: EpochPolicy,
                    max_epochs: Optional[int], eid: int) -> None:
        """Descriptor-driven epochs (ISSUE 6): the coordinator distributes
        shard descriptors round-robin over the live nodes and the workers
        read them on their own lanes — zero source bytes cross here.  Reuses
        the pipelined committer / sequential epoch machinery unchanged; the
        retained descriptor batch is the replay unit after a reader death."""
        pending: "deque[ShardDescriptor]" = deque(adapter.describe())
        committer: Optional[_EpochCommitter] = None
        if self.pipelined:
            committer = _EpochCommitter(self, stage_plans, split, faults,
                                        sreport, None,
                                        max_inflight=self.max_inflight_epochs,
                                        policy=policy)
        epoch_index = 0
        try:
            while max_epochs is None or epoch_index < max_epochs:
                if committer is not None:
                    committer.raise_if_failed()
                descs = self._cut_descriptors(pending, adapter, policy)
                if not descs:
                    if adapter.exhausted() and not pending:
                        break   # end of stream
                    continue    # empty tick: the adapter may yet poll more
                live = [n for n in self.nodes if self.alive[n]]
                if not live:
                    raise RuntimeError("all nodes failed")
                batch: Dict[str, List[Any]] = {n: [] for n in self.nodes}
                for i, d in enumerate(descs):
                    batch[live[i % len(live)]].append(d)
                t_cut = time.time()
                if committer is not None:
                    job = self._ingest_segment(eid, epoch_index, batch,
                                               stage_plans, split, faults,
                                               sreport, None, t_cut,
                                               source=adapter)
                    committer.submit(job)
                else:
                    ereport = self._run_epoch(eid, epoch_index, batch,
                                              stage_plans, faults, sreport,
                                              None, source=adapter)
                    sreport.epochs.append(ereport)
                    sreport.total_items += ereport.items_in
                    policy.observe_commit(ereport.commit_latency_s)
                eid += 1
                epoch_index += 1
        finally:
            if committer is not None:
                committer.close()
        if committer is not None:
            committer.raise_if_failed()

    def _ingest_segment(self, eid: int, epoch_index: int,
                        batch: Dict[str, List[Any]],
                        stage_plans: List[StagePlan], split: int,
                        faults: StreamFaultInjection, sreport: StreamReport,
                        queues: Optional[IngestQueues], t_cut: float,
                        source: Optional[SourceAdapter] = None) -> _EpochJob:
        """Run the epoch's ingest segment (stages [0, split)), replaying on
        node death — nothing is staged yet, so recovery is pure recompute.

        With a worker-pull ``source`` the batch holds shard descriptors:
        the workers read them inside the segment's first stage, the
        committed item count is worker-reported (``source_items``), and a
        replay attempt re-issues the dead node's descriptors to survivors."""
        attempts = 0
        ereport = RunReport()
        if source is not None:
            ereport.source_descriptors = sum(len(v) for v in batch.values())
            items_in = 0   # worker-reported after the reads
        else:
            items_in = sum(len(v) for v in batch.values())
            # the legacy pushed path: every one of these items crossed the
            # coordinator's ingest queues — the hop the descriptor path deletes
            ereport.source_coordinator_bytes = sum(
                it.nbytes() for v in batch.values() for it in v)
        while True:
            attempts += 1
            live = [n for n in self.nodes if self.alive[n]]
            if not live:
                raise RuntimeError("all nodes failed")
            if source is not None:
                ereport.source_reissues += self._count_lost(batch, live)
            node_sources = self._redistribute(batch, live)
            batch = node_sources   # keep replay bookkeeping per-assignment
            if attempts > 1:
                # whole-segment retry: every retained unit recomputes
                ereport.replayed_rows += _unit_rows(
                    it for v in node_sources.values() for it in v)
            ef = FaultInjection(op_failures=faults.op_failures)
            for n, at_epoch in faults.node_death_in_epoch.items():
                if at_epoch == epoch_index and self.alive.get(n):
                    # die after the epoch's first stage — in the ingest
                    # segment if one exists, else at the store segment's head
                    ef.node_death_after_stage[n] = stage_plans[0].name
            for (n, at_epoch), stname in faults.node_death_at.items():
                if at_epoch == epoch_index and self.alive.get(n):
                    # chaos-harness placement: die right after `stname`
                    ef.node_death_after_stage[n] = stname
            outputs = {n: defaultdict(list) for n in self.nodes}
            if split == 0:
                return _EpochJob(eid, epoch_index, batch, node_sources, outputs,
                                 ef, ereport, attempts, items_in, t_cut,
                                 node_set=live, source=source)
            base_items = ereport.source_items
            try:
                # epoch binds the segment's exchange rounds (no store writes
                # happen before `split`, so the staging protocol is untouched)
                self._execute(stage_plans, node_sources, ef, ereport, self.alive,
                              on_node_death="raise", lane="ingest",
                              outputs=outputs, start_stage=0, end_stage=split,
                              node_set=live, epoch=eid, source=source)
            except NodeFailure as e:
                # lineage-cone site (ISSUE 8): a death surfacing at the
                # segment's LAST stage means every survivor completed the
                # whole ingest segment and dealt into the pinned rounds —
                # the minimal repair is to strip the victim and re-run only
                # its retained shards, leaving the survivors' work standing
                if (self.cone_recovery and split > 0
                        and getattr(e, "stage_index", None) == split - 1
                        # source epochs need the read stage (0) strictly
                        # before the death stage, so the victim's item count
                        # is known to have been worker-reported already
                        and (source is None or split >= 2)
                        and not getattr(self.shuffle, "synchronous", False)
                        and cone_replay_capable(stage_plans, split)):
                    before_patch = ereport.source_items
                    dead = [n for n in live if not self.alive.get(n)]
                    patch = self._cone_patch(eid, dead, batch, stage_plans,
                                             split, ef, ereport, source)
                    if patch is not None:
                        for n in dead:
                            self._record_death(n, eid, sreport, queues)
                            batch[n] = []
                        for n, extra in patch.items():
                            batch.setdefault(n, []).extend(extra)
                        if source is not None:
                            # the victim fully read its shards before dying
                            # (its last-stage completion is what raised) and
                            # the patch re-read them identically — the
                            # pre-patch counter already equals the epoch total
                            items_in = before_patch - base_items
                        survivors = [n for n in self.nodes if self.alive[n]]
                        return _EpochJob(eid, epoch_index, batch, batch,
                                         outputs, ef, ereport, attempts,
                                         items_in, t_cut, node_set=survivors,
                                         source=source)
                self._note_death(str(e), eid, sreport, queues)
                continue
            if source is not None:
                items_in = ereport.source_items - base_items
            return _EpochJob(eid, epoch_index, batch, node_sources, outputs,
                             ef, ereport, attempts, items_in, t_cut,
                             node_set=live, source=source)

    # ------------------------------------------------------------------ epoch
    # epoch batches rebalance with the engine-wide policy: RuntimeEngine
    # ._redistribute (node affinity for live nodes, round-robin spill)

    def _record_death(self, dead: str, eid: int, sreport: StreamReport,
                      queues: Optional[IngestQueues]) -> None:
        """Death bookkeeping alone — routing, failure list, replay list.
        The cone path uses this directly: it must NOT invalidate the whole
        epoch's exchange state, only the producer it strips itself."""
        if queues is not None:   # the worker-pull path has no ingest queues
            queues.mark_dead(dead)
        sreport.node_failures.append(dead)
        if eid not in sreport.replayed_epochs:
            sreport.replayed_epochs.append(eid)
        with self._progress:
            self._progress.notify_all()

    def _note_death(self, dead: str, eid: int, sreport: StreamReport,
                    queues: Optional[IngestQueues]) -> None:
        self._record_death(dead, eid, sreport, queues)
        # the epoch replays wholesale: its in-flight exchange partitions
        # (peer segments, spill files, worker-resident buckets) are invalid
        # — reclaim them everywhere before the replay opens fresh rounds
        self.invalidate_exchange(eid)

    def _probe_executors(self) -> List[str]:
        """Flip ``alive`` for nodes whose process worker already died (pipe
        EOF seen by its receive thread) without any stage future surfacing
        the failure — e.g. a SIGTERM landing after the node finished its
        ingest-segment work.  Thread executors expose no liveness and are
        skipped (their deaths always surface as stage failures)."""
        with self._exec_lock:
            execs = dict(self._executors)
        dead: List[str] = []
        for n, ex in execs.items():
            if self.alive.get(n) and not getattr(ex, "alive", True):
                self.alive[n] = False
                self.store.mark_node_dead(n)
                dead.append(n)
        return dead

    def _cone_patch(self, eid: int, dead_nodes: Sequence[str],
                    batch: Dict[str, List[Any]],
                    stage_plans: List[StagePlan], split: int,
                    ef: FaultInjection, ereport: RunReport,
                    source: Optional[SourceAdapter]
                    ) -> Optional[Dict[str, List[Any]]]:
        """Lineage-cone recovery (ISSUE 8): replay ONLY the dead nodes' cone.

        On a cone-capable plan (no shuffle in the ingest segment: every
        node's resident partitions derive solely from its own retained
        shards) the dead nodes' exchange contribution is stripped
        (``invalidate_producer``) and their shards re-run through the ingest
        segment on survivor targets.  The patch producers merge into the
        epoch's still-pinned rounds — deposits extend node-side buckets,
        manifests merge — so the store segment later adopts a complete
        round, with the survivors' work untouched.

        Returns the patch assignment (shards added per target) on success,
        or None when the patch itself lost a node — the caller falls back
        to whole-epoch replay, whose ``invalidate_exchange`` also cleans up
        the half-merged patch."""
        live = [n for n in self.nodes if self.alive[n]]
        if not live:
            return None
        shards = {n: list(batch.get(n) or []) for n in dead_nodes}
        total_units = sum(len(v) for v in shards.values())
        for n in dead_nodes:
            self.invalidate_producer(eid, n)
        if total_units == 0:
            ereport.cone_replays += 1
            return {}   # the dead node held no inputs: stripping sufficed
        if source is not None:
            ereport.source_reissues += total_units
        patch = {n: v for n, v in self._redistribute(shards, live).items()
                 if v}
        outputs = {n: defaultdict(list) for n in self.nodes}
        try:
            self._execute(stage_plans, patch, ef, ereport, self.alive,
                          on_node_death="raise", lane="ingest",
                          outputs=outputs, start_stage=0, end_stage=split,
                          node_set=list(patch), epoch=eid, source=source)
        except NodeFailure:
            return None
        ereport.cone_replays += 1
        ereport.replayed_rows += _unit_rows(
            it for v in shards.values() for it in v)
        return patch

    def _run_epoch(self, eid: int, epoch_index: int,
                   batch: Dict[str, List[Any]],
                   stage_plans: List[StagePlan], faults: StreamFaultInjection,
                   sreport: StreamReport, queues: Optional[IngestQueues],
                   source: Optional[SourceAdapter] = None) -> EpochReport:
        """Sequential mode: run one micro-batch through the full stage DAG and
        commit it atomically.

        Node death mid-attempt -> abort the staged blocks, mark the node dead,
        replay the *entire epoch* on the survivors.  The commit is the only
        publish point, so a replayed epoch can neither lose items (the full
        input batch — items or shard descriptors — is retained until commit)
        nor double-commit (``begin_epoch`` refuses committed ids)."""
        with tracing.span("ib.epoch", epoch=eid):
            items_in = sum(len(v) for v in batch.values())
            n_descs = items_in if source is not None else 0
            pushed_bytes = (0 if source is not None else sum(
                it.nbytes() for v in batch.values() for it in v))
            t_cut = time.time()
            attempts = 0
            reissues = 0
            while True:
                attempts += 1
                live = [n for n in self.nodes if self.alive[n]]
                if not live:
                    raise RuntimeError("all nodes failed")
                if source is not None:
                    reissues += self._count_lost(batch, live)
                node_sources = self._redistribute(batch, live)
                batch = node_sources   # keep replay bookkeeping per-assignment

                # injected mid-epoch deaths for this epoch index -> die after
                # the first stage of the attempt (blocks already staged get
                # aborted)
                ef = FaultInjection(op_failures=faults.op_failures)
                for n, at_epoch in faults.node_death_in_epoch.items():
                    if at_epoch == epoch_index and self.alive.get(n):
                        ef.node_death_after_stage[n] = stage_plans[0].name
                for (n, at_epoch), stname in faults.node_death_at.items():
                    if at_epoch == epoch_index and self.alive.get(n):
                        ef.node_death_after_stage[n] = stname

                self.store.begin_epoch(eid)
                ereport = RunReport()
                if attempts > 1:
                    # sequential mode always replays wholesale: the full DAG
                    # ran under one _execute, so a death loses the epoch's
                    # exchange
                    ereport.replayed_rows = _unit_rows(
                        it for v in node_sources.values() for it in v)
                if source is not None:
                    ereport.source_descriptors = n_descs
                    ereport.source_reissues = reissues
                else:
                    ereport.source_coordinator_bytes = pushed_bytes
                try:
                    self._execute(stage_plans, node_sources, ef, ereport,
                                  self.alive, on_node_death="raise", epoch=eid,
                                  node_set=live, source=source)
                except NodeFailure as e:
                    self.store.abort_epoch(eid)
                    self._note_death(str(e), eid, sreport, queues)
                    continue
                if source is not None:
                    items_in = ereport.source_items
                entry = self.store.commit_epoch(eid, n_items=items_in)
                return EpochReport(epoch=eid, items_in=items_in,
                                   n_blocks=entry.n_blocks, attempts=attempts,
                                   commit_latency_s=time.time() - t_cut,
                                   run=ereport)


def stream_ingest(plan: IngestPlan,
                  source: Union[Iterable[IngestItem], SourceAdapter, None],
                  store: DataStore,
                  *, optimize: bool = True,
                  faults: Optional[StreamFaultInjection] = None,
                  max_epochs: Optional[int] = None,
                  **engine_kw: Any) -> StreamReport:
    """One-call entry point: stream a source through an ingestion plan."""
    eng = StreamingRuntimeEngine(store, **engine_kw)
    try:
        return eng.run_stream(plan, source, faults=faults, optimize=optimize,
                              max_epochs=max_epochs)
    finally:
        eng.close()   # one-shot engine: release node executors + shuffle writer


def stream_ingest_multi(plans: Union[Sequence[IngestPlan], Any],
                        source: Iterable[IngestItem],
                        stores: Union[DataStore, Sequence[DataStore]],
                        *, optimize: bool = True,
                        faults: Optional[Union[StreamFaultInjection,
                                               Dict[str, StreamFaultInjection]]] = None,
                        max_epochs: Optional[int] = None,
                        **engine_kw: Any) -> Dict[str, StreamReport]:
    """Fan one source into several plans (``FEED ... INTO plan1, plan2``).

    ``plans`` is a sequence of IngestPlans, or any object with a ``.plans``
    attribute (the language front-end's FeedSpec).  Each plan runs in its own
    StreamingRuntimeEngine over its own DataStore from ``stores`` — one store
    per plan: concurrent engines must not share an epoch-id space.  A single
    ``StreamFaultInjection`` applies to every pipeline; a dict maps plan name
    -> injection.  Returns plan name -> StreamReport.
    """
    plan_list: List[IngestPlan] = list(getattr(plans, "plans", plans))
    store_list = list(stores) if isinstance(stores, (list, tuple)) else [stores]
    if len(store_list) != len(plan_list):
        raise ValueError(f"{len(plan_list)} plans need {len(plan_list)} stores, "
                         f"got {len(store_list)}")
    roots = {s.root for s in store_list}
    if len(roots) != len(store_list):
        raise ValueError("each fanned-out plan needs its own DataStore "
                         "(engines must not share an epoch-id space)")

    names = [p.name for p in plan_list]
    if len(set(names)) != len(names):
        raise ValueError(f"duplicate plan names {names}: rename plans so "
                         f"faults/results can be addressed deterministically")

    engines: List[StreamingRuntimeEngine] = []
    joints: List[IngestQueues] = []
    for plan, st in zip(plan_list, store_list):
        eng = StreamingRuntimeEngine(st, **engine_kw)
        engines.append(eng)
        joints.append(IngestQueues.manual(eng.nodes, eng._config(plan).capacity))
    distributor = FeedDistributor(source, joints)

    results: Dict[str, StreamReport] = {}
    errors: List[Tuple[str, BaseException]] = []

    def run_one(name: str, eng: StreamingRuntimeEngine, plan: IngestPlan,
                joint: IngestQueues) -> None:
        f = faults.get(name) if isinstance(faults, dict) else faults
        try:
            results[name] = eng.run_stream(plan, queues=joint, faults=f,
                                           optimize=optimize, max_epochs=max_epochs)
        except BaseException as e:
            errors.append((name, e))
            joint.stop()   # unblock the distributor for this joint

    threads = [threading.Thread(target=run_one, args=(nm, e, p, j), daemon=True)
               for nm, e, p, j in zip(names, engines, plan_list, joints)]
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    distributor.join()
    for eng in engines:
        eng.close()
    if errors:
        raise errors[0][1]
    return results
