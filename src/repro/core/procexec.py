"""Process-based node backend: real CPU parallelism over ``launch_remote``.

The thread backend's ``NodeExecutor`` lanes share one Python process, so on a
GIL-bound host the pipelined core overlaps latency but cannot multiply
CPU-heavy operator throughput (DESIGN.md §6).  This module realizes the
``launch_remote`` seam with real OS processes:

* **One long-lived worker process per logical node** (``ProcessNodeExecutor``
  spawns it once per engine), hosting the node's plan clone and the same
  named-lane model as the thread backend — the pipelined streaming engine's
  ``"ingest"`` / ``"store"`` lanes run as threads *inside* the worker, so
  epoch overlap and core-parallelism compose.
* **Plans ship once, by pickle** — ``IngestOp.__reduce__`` reduces operators
  to (type, params), exactly the catalog contract, so the worker re-creates
  fresh operator state that then persists across epochs (dummy substitutions
  survive, like in a long-running per-node JVM).  Closure params fail fast
  with a named operator (``plan.serialize_plans``).
* **Shared-memory data plane** — item batches cross the process boundary via
  ``items.encode_items``: one ``multiprocessing.shared_memory`` segment per
  hop, zero-copy numpy views on the worker side, inline pickle for small
  batches (see items.py).
* **Commit routing** — upload operators run *in the worker*, which performs
  the serialization/compression and the disk write locally (a ``.tmp`` name
  the orphan GC ignores), then registers the block's metadata with the
  coordinator over a dedicated store-RPC pipe
  (``DataStore.register_block_file``).  The manifest, the epoch staging
  index, and the commit sequencer therefore live only in the coordinator:
  epoch begin/commit/abort work unchanged.
* **Death detection** — the coordinator's receiver thread treats pipe EOF
  (worker crash, ``kill()``) as the node dying: every in-flight and future
  stage job on that node fails with ``NodeFailure``, which is exactly what
  the existing fault path consumes (batch shard reassignment, streaming
  epoch-granular abort + replay).

* **Worker-to-worker shuffle** (ISSUE 4) — a shuffle-boundary stage's output
  never returns to the coordinator: the worker partitions it locally by the
  plan's routing key (``ctx["shuffle"]``), encodes each peer-bound partition
  into its own shared-memory segment (``exchange.encode_partition`` — pickle
  meta *inside* the segment, so the reply manifest carries only names and
  sizes), spills oversized partitions to peer-readable DFS files, and keeps
  its own slice resident in the in-worker ``PartitionExchange``.  The
  consuming stage's job receives fetch refs (``ctx["fetch"]``) and maps the
  segments zero-copy / reads the files / pops its resident bucket.  The
  coordinator's ``ShuffleCoordinator`` relays only the manifests — zero item
  bytes cross its pipes on the shuffle path.  A ``("drop", xids)`` control
  message invalidates rounds of an aborted epoch.
"""
from __future__ import annotations

import itertools
import os
import pickle
import queue
import threading
import time
import uuid
from collections import defaultdict
from concurrent.futures import Future
from dataclasses import asdict
from typing import Any, Callable, Dict, List, Optional, Sequence, Tuple

import multiprocessing as mp

from .exchange import (PartitionExchange, build_manifest, columnar_file_name,
                       decode_partition, encode_columnar_partition,
                       encode_partition, exchange_file_name,
                       fetch_stream_partition, read_partition_file,
                       resident_file_name, write_columnar_file,
                       write_partition_file)
from .items import (ColumnarBatch, IngestItem, ShmLease, decode_items,
                    encode_items, items_nbytes, sweep_pid_segments)
from .liveness import retry_call
from .transport import (ChaosProxy, FrameListener, PartitionStreamServer,
                        connect_framed)
from .operators import OperatorFailure, PassThroughOp, run_ops_batched
from .plan import StagePlan, failed_op_index, route_items, serialize_plans
from .store import BlockEntry, DataStore, prepare_block_payload


class WorkerDeath(RuntimeError):
    """Raised coordinator-side when a node's worker process is gone; the
    runtime maps it onto ``NodeFailure`` (the existing fault path)."""


#: the host label meaning "this machine" — executors without an explicit
#: host, and every pre-ISSUE-9 caller, run here
LOCAL_HOST = "local"


class _StoreToken:
    """Picklable placeholder swapped for a ``DataStore`` param while a plan
    crosses the process boundary; the worker swaps in its store client."""

    def __repr__(self) -> str:
        return "<store@coordinator>"


_TOKEN = _StoreToken()
_ship_lock = threading.Lock()   # serializes the param swap on shared plans


def _mp_context():
    """fork by default (fast spawn, inherited imports); override with
    REPRO_MP_START_METHOD=spawn|forkserver on platforms or runtimes where
    forking a threaded parent is unsafe.  Workers run only host-side
    ingestion operators: ``serialize_plans_for_worker`` refuses any op that
    holds a device kernel, because the chip belongs to one process (the
    coordinator, which may already hold it) and a forked child must not
    touch JAX."""
    methods = mp.get_all_start_methods()
    want = os.environ.get("REPRO_MP_START_METHOD",
                          "fork" if "fork" in methods else "spawn")
    if want not in methods:
        want = "spawn"
    return mp.get_context(want)


def serialize_plans_for_worker(stage_plans: Sequence[StagePlan],
                               store: DataStore) -> bytes:
    """Pickle a stage DAG with DataStore params tokenized for the worker.

    Refuses ops that hold a device kernel (``use_pallas``): kernel ops run
    in the process that owns the chip, i.e. on the thread backend."""
    for sp in stage_plans:
        for op in sp.ops:
            if op.params.get("use_pallas"):
                raise ValueError(
                    f"stage {sp.name!r}: op {op.name!r} holds a device kernel "
                    f"(use_pallas=True); kernel ops run in the process that "
                    f"owns the chip — use the thread backend")
    with _ship_lock:
        swapped = []
        for sp in stage_plans:
            for op in sp.ops:
                s = op.params.get("store")
                if isinstance(s, DataStore):
                    if s is not store:
                        raise ValueError(
                            f"stage {sp.name!r}: upload target is not the "
                            f"engine's store — the process backend routes "
                            f"commits through the coordinator's store only")
                    swapped.append(op)
                    op.params["store"] = _TOKEN
        try:
            return serialize_plans(stage_plans)
        finally:
            for op in swapped:
                op.params["store"] = store


# ---------------------------------------------------------------------------
# Worker-process side
# ---------------------------------------------------------------------------
class _WorkerStoreClient:
    """The worker's stand-in for ``DataStore``: local payload prep + disk
    write, metadata registration RPC'd to the coordinator (DESIGN.md §6)."""

    def __init__(self, node: str, conn: Any, spec: Dict[str, Any]) -> None:
        self.node = node
        self._conn = conn
        self._rpc_lock = threading.Lock()
        self.root = spec["root"]
        self.nodes = list(spec["nodes"])
        self.durable = spec["durable"]
        self.compress = spec["compress"]
        self.compress_level = spec["compress_level"]
        self.journal_commits = spec["journal_commits"]
        #: columnar data plane (ISSUE 10): UploadOp.process_batch funnels
        #: the batch through ONE put_batch RPC when this is on; off keeps
        #: the per-block protocol (the PR-9 item-at-a-time baseline)
        self.bulk_registration = bool(spec.get("bulk_registration", False))
        self._live: List[str] = list(self.nodes)
        self._epoch = threading.local()

    # ------------------------------------------------------------- job scope
    def bind_live(self, live: Optional[Sequence[str]]) -> None:
        if live is not None:
            self._live = list(live)

    def set_epoch(self, epoch: Optional[int]) -> Any:
        prev = getattr(self._epoch, "value", None)
        self._epoch.value = epoch
        return prev

    # ------------------------------------------------- DataStore duck-typing
    def live_nodes(self) -> List[str]:
        live = set(self._live)
        return [n for n in self.nodes if n in live]

    def _rpc(self, *msg: Any) -> Any:
        with self._rpc_lock:
            self._conn.send(msg)
            status, val = self._conn.recv()
        if status == "err":
            raise RuntimeError(f"store RPC {msg[0]!r} failed: {val}")
        return val

    def staging_epoch_ids(self) -> List[int]:
        return self._rpc("staging")

    def flush_manifest(self) -> None:
        self._rpc("flush")

    def _put_record(self, item: IngestItem, node: str, *,
                    logical_id: str = "", replica_index: int = 0,
                    stripe_id: str = "", stripe_pos: int = -1,
                    is_parity: bool = False) -> Dict[str, Any]:
        """The heavy, local half of a block put: physical payload write (to
        a name gc never scans) plus the registration record for the RPC."""
        payload, layout, raw_nbytes = prepare_block_payload(
            item.data, self.compress, self.compress_level)
        tmp = os.path.join(self.root, "nodes", node, f".{uuid.uuid4().hex}.tmp")
        os.makedirs(os.path.dirname(tmp), exist_ok=True)
        with open(tmp, "wb") as f:
            f.write(payload)
            if self.durable:
                f.flush()
                os.fsync(f.fileno())
        epoch = getattr(self._epoch, "value", None)
        return {
            "node": node, "tmp_path": tmp, "base": item.lineage_name(),
            "checksum": item.checksum(), "nbytes": len(payload),
            "raw_nbytes": raw_nbytes, "compressed": self.compress,
            "labels": [[l.op, l.value] for l in item.labels],
            "layout": layout,
            "logical_id": logical_id or DataStore._logical_id(item),
            "replica_index": replica_index, "stripe_id": stripe_id,
            "stripe_pos": stripe_pos, "is_parity": is_parity,
            "meta": dict(item.meta),
            "epoch": -1 if epoch is None else epoch,
        }

    def put_block(self, item: IngestItem, node: str, *, logical_id: str = "",
                  replica_index: int = 0, stripe_id: str = "",
                  stripe_pos: int = -1, is_parity: bool = False) -> BlockEntry:
        rec = self._rpc("put", self._put_record(
            item, node, logical_id=logical_id, replica_index=replica_index,
            stripe_id=stripe_id, stripe_pos=stripe_pos, is_parity=is_parity))
        return BlockEntry(**rec)

    def put_block_batch(self, reqs: Sequence[Dict[str, Any]]
                        ) -> List[BlockEntry]:
        """Columnar data plane (ISSUE 10): register a whole block batch in
        ONE coordinator round trip.  The physical writes happen here first
        (order-preserving, same tmp-name protocol as ``put_block``); only
        the registration records cross the pipe.  At the pre-ISSUE-10
        per-block protocol's ~ms-per-RPC, a 512-block run spends more wall
        on registration chatter than on the writes themselves."""
        if not reqs:
            return []
        recs = [self._put_record(r["item"], r["node"],
                                 **{k: v for k, v in r.items()
                                    if k not in ("item", "node")})
                for r in reqs]
        out: List[BlockEntry] = []
        # slim reply: the coordinator assigns only (block_id, path); the
        # rest of each entry is the record this client just authored
        for rec, (block_id, path) in zip(recs, self._rpc("put_batch", recs)):
            kw = dict(rec)
            kw.pop("tmp_path")
            base = kw.pop("base")
            kw["logical_id"] = kw["logical_id"] or base
            out.append(BlockEntry(block_id=block_id, path=path, **kw))
        return out


class _WorkerLane:
    """FIFO worker thread inside the node process (same model as the thread
    backend's lanes: "ingest" and "store" jobs overlap within the worker)."""

    def __init__(self, name: str) -> None:
        self.jobs: "queue.Queue[Optional[Callable[[], None]]]" = queue.Queue()
        self.thread = threading.Thread(target=self._loop, daemon=True,
                                       name=f"lane-{name}")
        self.thread.start()

    def _loop(self) -> None:
        while True:
            job = self.jobs.get()
            if job is None:
                return
            job()


def _run_stage_ops(sp: StagePlan, items: List[IngestItem],
                   injections: Dict[int, int], max_retries: int
                   ) -> Tuple[List[IngestItem], Dict[str, Any]]:
    """The worker-side twin of ``RuntimeEngine._run_stage``: pipeline blocks
    as checkpoints, retry from the previous materialization, dummy
    substitution after ``max_retries`` (paper Sec. VI-C1).  Substitutions
    mutate the worker's resident plan, so they persist across epochs exactly
    like the thread backend's node clones."""
    stats: Dict[str, Any] = {"op_failures": {}, "dummy": [],
                             "vectorized_rows": 0, "batch_fallbacks": 0,
                             "kernel_calls": 0}
    counts: Dict[int, int] = defaultdict(int)
    current = items
    blocks = sp.pipeline_blocks or [[i] for i in range(len(sp.ops))]
    for bi, block in enumerate(blocks):
        batched = (bool(sp.batch_blocks[bi])
                   if bi < len(sp.batch_blocks) else False)
        checkpoint = current
        while True:
            try:
                out = checkpoint
                if batched:
                    # batch tier (ISSUE 7): same vectorized block execution
                    # as the thread backend; counters ride back to the
                    # coordinator in the stage stats payload
                    for oi in block:
                        if injections.get(oi, 0) > 0:
                            injections[oi] -= 1
                            raise OperatorFailure(
                                f"injected @ {sp.name}[{oi}]")
                    out, bstats = run_ops_batched(
                        [sp.ops[oi] for oi in block], out)
                    stats["vectorized_rows"] += bstats["vectorized_rows"]
                    stats["batch_fallbacks"] += bstats["batch_fallbacks"]
                    stats["kernel_calls"] += bstats["kernel_calls"]
                else:
                    for oi in block:
                        if injections.get(oi, 0) > 0:
                            injections[oi] -= 1
                            raise OperatorFailure(
                                f"injected @ {sp.name}[{oi}]")
                        out = sp.ops[oi].run(out)
                current = out
                break
            except OperatorFailure as e:
                oi = block[0] if len(block) == 1 else failed_op_index(sp, block, e)
                counts[oi] += 1
                stats["op_failures"][f"{sp.name}[{oi}]"] = counts[oi]
                if counts[oi] >= max_retries:
                    failing = sp.ops[oi]
                    sp.ops[oi] = PassThroughOp(replaces=failing.name)
                    stats["dummy"].append(
                        f"{sp.name}[{oi}]:{type(failing).__name__}")
                continue
    return current, stats


def _worker_main(node: str, conn: Any, store_conn: Any,
                 store_spec: Dict[str, Any],
                 stream_server: Optional[PartitionStreamServer] = None
                 ) -> None:
    """Worker process entry: recv loop dispatching stage jobs onto lanes.

    ``conn``/``store_conn`` are duck-typed (``send``/``recv``/``close``):
    ``multiprocessing.Connection`` pipes on the default transport, framed
    sockets (``transport.FramedConnection``) on the socket fabric — the
    loop below is medium-agnostic.  ``stream_server`` is the socket
    transport's degraded-exchange endpoint: when a peer is not
    shm-reachable (another host), this worker's spill files stream to it
    from here (ISSUE 9)."""
    client = _WorkerStoreClient(node, store_conn, store_spec)
    exchange = PartitionExchange()   # resident partitions + fetch caches
    plans: Dict[str, Any] = {}
    lanes: Dict[str, _WorkerLane] = {}
    send_lock = threading.Lock()

    def send(msg: Any) -> bool:
        with send_lock:
            try:
                conn.send(msg)
                return True
            except (BrokenPipeError, OSError):
                return False

    def fetch_partitions(refs: List[Dict[str, Any]],
                         held: List[ShmLease]) -> List[IngestItem]:
        """Pull this node's incoming shuffle partitions: map peer segments
        zero-copy (leases land in ``held`` for the caller to release after
        the stage is done with the items), read spill files consume-on-read,
        pop the resident bucket.  ``keep`` retains the batch locally for a
        later consuming stage instead of destroying the source."""
        fetched: List[IngestItem] = []
        # bucket reads first: a peer batch cached below (keep) lands in the
        # same bucket, and collecting after the deposit would double-count it
        order = sorted(refs, key=lambda r: r["kind"] not in ("resident",
                                                             "cached"))
        for ref in order:
            kind = ref["kind"]
            keep = bool(ref.get("keep"))
            if kind in ("resident", "cached"):
                got, leases = exchange.collect(ref["xid"], node,
                                               last=not keep)
                held.extend(leases)
            elif kind == "shm":
                if keep:
                    got, _ = decode_partition(ref, copy=True)
                    exchange.deposit(ref["xid"], node, got,
                                     int(ref.get("nbytes", 0)))
                else:
                    got, lease = decode_partition(ref)   # zero-copy views
                    if lease is not None:
                        held.append(lease)
            elif kind == "file":
                # always consume-on-read: with keep, later consuming stages
                # are served from the cached bucket, never the file again
                got = read_partition_file(ref["path"], remove=True)
                if keep:
                    exchange.deposit(ref["xid"], node, got,
                                     int(ref.get("nbytes", 0)))
            elif kind == "stream":
                # degraded exchange (ISSUE 9): the producer is not
                # shm-reachable — stream its spill file worker-to-worker
                # over the framed protocol (the server deletes on a
                # successful send; the shared-dir direct read is the
                # single-host fallback, also consume-on-read)
                got = fetch_stream_partition(ref)
                if keep:
                    exchange.deposit(ref["xid"], node, got,
                                     int(ref.get("nbytes", 0)))
            else:
                raise ValueError(f"unknown exchange ref kind {kind!r}")
            fetched.extend(got)
        return fetched

    def deal_partitions(xs: Dict[str, Any], out: List[IngestItem],
                        input_leases: List[ShmLease],
                        peer_leases: List[ShmLease]) -> Dict[str, Any]:
        """Partition an exchange-boundary stage's output and hand it out:
        the node's own slice stays resident (holding shares of the input
        leases it may alias) — for a narrow round (``key=None``, ISSUE 5)
        that is the *entire* output — each peer slice crosses via its own
        segment or, past the per-edge spill share, a DFS spill file; an
        oversized resident slice spills under the ``resident_*`` naming.
        Returns the metadata-only manifest.

        On a columnar round (ISSUE 10) the output packs into one
        ColumnarBatch up front: each slice then crosses as a raw column
        buffer — straight into the shm segment, spill file, or stream
        source with no per-item pickling.  Sub-batches own their payload
        (``select`` copies), so resident deposits need no input-lease
        shares.  An output that doesn't pack falls back to the scalar
        path and flags the manifest."""
        hosts = xs.get("hosts") or {}
        my_host = hosts.get(node)

        def columnar_fn(dst: str, batch: ColumnarBatch, nb: int
                        ) -> Dict[str, Any]:
            if dst == node:
                if nb > xs["spill_share"]:
                    path = os.path.join(
                        xs["spill_dir"],
                        columnar_file_name(xs["epoch"], xs["xid"], node, node))
                    write_columnar_file(path, batch)
                    exchange.deposit(xs["xid"], node, None, nb, path=path)
                    return {"kind": "resident", "count": len(batch),
                            "nbytes": nb, "spilled": path, "columnar": True}
                exchange.deposit_batch(xs["xid"], node, batch)
                return {"kind": "resident", "count": len(batch),
                        "nbytes": nb, "columnar": True}
            cross_host = (my_host is not None and hosts.get(dst) is not None
                          and hosts.get(dst) != my_host)
            if cross_host or nb > xs["spill_share"]:
                path = os.path.join(
                    xs["spill_dir"],
                    columnar_file_name(xs["epoch"], xs["xid"], node, dst))
                desc = write_columnar_file(path, batch)
                if cross_host and stream_server is not None:
                    desc = {**desc, "kind": "stream",
                            "endpoint": list(stream_server.endpoint)}
                return desc
            desc, pl = encode_columnar_partition(batch)
            peer_leases.append(pl)
            return desc

        def part_fn(dst: str, its: Any, nb: int) -> Dict[str, Any]:
            if isinstance(its, ColumnarBatch):
                return columnar_fn(dst, its, nb)
            if dst == node:
                if nb > xs["spill_share"]:
                    path = os.path.join(
                        xs["spill_dir"],
                        resident_file_name(xs["epoch"], xs["xid"], node))
                    write_partition_file(path, its)
                    exchange.deposit(xs["xid"], node, None, nb, path=path)
                    return {"kind": "resident", "count": len(its),
                            "nbytes": nb, "spilled": path}
                shares = [l.share() for l in input_leases]
                exchange.deposit(xs["xid"], node, its, nb, leases=shares)
                return {"kind": "resident", "count": len(its), "nbytes": nb}
            if (my_host is not None and hosts.get(dst) is not None
                    and hosts.get(dst) != my_host):
                # degraded mode (ISSUE 9): the consumer cannot map this
                # worker's shm segments — write the partition as an
                # ordinary exchange spill (same naming, same gc_orphans
                # coverage) and advertise the stream endpoint so the peer
                # pulls the bytes worker-to-worker over the framed fabric
                path = os.path.join(
                    xs["spill_dir"],
                    exchange_file_name(xs["epoch"], xs["xid"], node, dst))
                desc = write_partition_file(path, its)
                if stream_server is not None:
                    desc = {**desc, "kind": "stream",
                            "endpoint": list(stream_server.endpoint)}
                return desc
            if nb > xs["spill_share"]:
                path = os.path.join(
                    xs["spill_dir"],
                    exchange_file_name(xs["epoch"], xs["xid"], node, dst))
                return write_partition_file(path, its)
            desc, pl = encode_partition(its)
            peer_leases.append(pl)
            return desc

        payload: Any = out
        fallback = False
        if xs.get("columnar") and out:
            batch = ColumnarBatch.from_items(out)
            if batch is None:
                fallback = True
            else:
                payload = batch
        manifest = build_manifest(payload, xs["key"], xs["targets"], part_fn,
                                  self_node=node)
        if fallback:
            manifest["columnar_fallback"] = True
        return manifest

    def run_job(jid: int, plan_key: str, si: int, payload: Dict[str, Any],
                ctx: Dict[str, Any]) -> None:
        lease = out_lease = None
        held: List[ShmLease] = []        # fetched-partition leases
        peer_leases: List[ShmLease] = []  # outgoing partition segments
        try:
            installed = plans.get(plan_key)
            if isinstance(installed, BaseException):
                raise installed
            if installed is None:
                raise KeyError(f"worker {node}: plan {plan_key!r} not installed")
            sp = installed[si]
            items, lease = decode_items(payload)   # zero-copy shm views
            src = ctx.get("source")
            src_stats: Optional[Tuple[int, int]] = None
            if src is not None:
                # worker-pull source (ISSUE 6): the coordinator shipped only
                # shard descriptors — open/read/parse them here, then route
                # with the source stage's predicates exactly as the
                # coordinator would have routed pushed items
                pulled: List[IngestItem] = []
                for d in src["descs"]:
                    pulled.extend(src["adapter"].read(d))
                src_stats = (len(pulled), items_nbytes(pulled))
                items = items + route_items(pulled, sp.predicates)
                del pulled
            refs = ctx.get("fetch")
            if refs:
                # incoming shuffle partitions merge with the pipe inputs;
                # the stage's label predicates apply to them here, exactly
                # as the coordinator applied them to the pipe inputs
                items = items + route_items(fetch_partitions(refs, held),
                                            sp.predicates)
            client.bind_live(ctx.get("live_nodes"))
            prev = client.set_epoch(ctx.get("epoch"))
            t0 = time.perf_counter()
            try:
                out, stats = _run_stage_ops(
                    sp, items, dict(ctx.get("injections") or {}),
                    int(ctx.get("max_retries", 3)))
            finally:
                client.set_epoch(prev)
            stats["worker_s"] = time.perf_counter() - t0
            if src_stats is not None:
                stats["source_items"], stats["source_bytes"] = src_stats
            xs = ctx.get("shuffle")
            if xs is not None:
                # exchange boundary (shuffle or narrow): partitions go
                # peer-to-peer or stay resident, the reply carries only the
                # manifest (metadata — zero item bytes cross the
                # coordinator pipe)
                input_leases = [l for l in [lease, *held] if l is not None]
                manifest = deal_partitions(xs, out, input_leases, peer_leases)
                out_payload: Dict[str, Any] = {"kind": "xmanifest",
                                               "manifest": manifest}
            elif ctx.get("sink"):
                # terminal stage: outputs die here — only the count returns
                out_payload = {"kind": "sink", "count": len(out),
                               "nbytes": items_nbytes(out)}
            else:
                # encode before releasing input leases: outputs may alias
                out_payload, out_lease = encode_items(out)
            del items, out
            for l in held:
                l.release()
            held = []
            if lease is not None:
                lease.release()
                lease = None
            if send(("done", jid, out_payload, stats)):
                if out_lease is not None:
                    out_lease.detach()
                for pl in peer_leases:   # consumers (or invalidation) unlink
                    pl.detach()
            else:
                if out_lease is not None:
                    out_lease.release()  # coordinator gone: don't leak segs
                for pl in peer_leases:
                    pl.release()
            out_lease = None
            peer_leases = []
        except BaseException as e:
            for l in held:
                l.release()
            if lease is not None:
                lease.release()
            if out_lease is not None:
                out_lease.release()
            for pl in peer_leases:
                pl.release()
            import traceback
            tb = traceback.format_exc()
            if isinstance(e, StopIteration):
                # a StopIteration must not cross into Future.result() —
                # inside a generator frame it would silently end iteration
                # instead of surfacing; carry the worker traceback instead
                e = RuntimeError(f"worker {node}: StopIteration escaped a "
                                 f"stage job\n{tb}")
            else:
                try:
                    pickle.dumps(e)
                except Exception:
                    # unpicklable: ship the worker-side traceback, which the
                    # pickled exception would have dropped anyway
                    e = RuntimeError(f"{type(e).__name__}: {e}\n{tb}")
            send(("fail", jid, e))

    while True:
        try:
            msg = conn.recv()
        except (EOFError, OSError):
            break
        kind = msg[0]
        if kind == "stop":
            break
        if kind == "ping":
            # heartbeat (ISSUE 8): answered inline from the recv loop — stage
            # jobs run on lanes, so a *busy* worker still pongs; only a dead
            # or wedged (SIGSTOP'd) process goes silent, which is exactly the
            # condition the coordinator's LivenessMonitor wants to observe
            send(("pong", msg[1]))
        elif kind == "install":
            _, key, blob = msg
            try:
                sps = pickle.loads(blob)
                for sp in sps:
                    for op in sp.ops:
                        if isinstance(op.params.get("store"), _StoreToken):
                            op.params["store"] = client
                            op.store = client
                plans[key] = sps
            except BaseException as e:      # surfaced when a job needs it
                plans[key] = e
        elif kind == "drop":
            # epoch invalidation: clear resident/cached exchange rounds
            exchange.drop(msg[1])
        elif kind == "stall":
            # test hook (ISSUE 9 satellite): block THIS recv loop for
            # ``seconds`` — the exact starvation a long decode or a fork of
            # the GIL inflicts on a healthy worker — while (optionally)
            # issuing store RPCs every ``rpc_every`` seconds, the way a busy
            # stage job does.  Store traffic must keep the worker alive even
            # though no pong can be answered here.
            _, seconds, rpc_every = msg
            deadline = time.monotonic() + seconds
            while time.monotonic() < deadline:
                step = min(rpc_every or 0.05,
                           max(deadline - time.monotonic(), 0.0))
                time.sleep(step)
                if rpc_every:
                    try:
                        client.staging_epoch_ids()
                    except RuntimeError:
                        break
        elif kind == "run":
            _, jid, plan_key, si, lane, payload, ctx = msg
            ln = lanes.get(lane)
            if ln is None:
                ln = lanes[lane] = _WorkerLane(f"{node}:{lane}")
            ln.jobs.put(lambda j=jid, k=plan_key, s=si, p=payload, c=ctx:
                        run_job(j, k, s, p, c))
    exchange.close()
    for ln in lanes.values():
        ln.jobs.put(None)


def _socket_worker_main(node: str, address: Tuple[str, int], token: str,
                        store_spec: Dict[str, Any]) -> None:
    """Socket-transport worker entry (ISSUE 9): instead of inheriting pipe
    ends, the worker *dials back* to its executor's listener — twice, once
    per channel (``role="ctrl"`` / ``"store"``), authenticated by the
    per-executor token — then runs the identical ``_worker_main`` loop over
    the framed connections.  It also stands up its own
    ``PartitionStreamServer`` over the exchange spill dir and advertises
    the endpoint in the ctrl hello, so peers on other hosts can pull this
    worker's partitions in degraded mode."""
    stream_server = PartitionStreamServer(
        store_spec.get("dfs_dir") or store_spec["root"])
    conn = store_conn = None
    try:
        conn = connect_framed(
            address, role="ctrl", node=node, token=token,
            info={"exchange_endpoint": list(stream_server.endpoint)})
        store_conn = connect_framed(address, role="store", node=node,
                                    token=token)
        _worker_main(node, conn, store_conn, store_spec, stream_server)
    finally:
        for c in (conn, store_conn):
            if c is not None:
                c.close()
        stream_server.close()


# ---------------------------------------------------------------------------
# Coordinator side
# ---------------------------------------------------------------------------
class ProcessNodeExecutor:
    """Coordinator handle for one node's worker process.

    Mirrors ``NodeExecutor``'s surface (install once, lane-addressed jobs,
    shutdown) but jobs are stage descriptors shipped over a control pipe, and
    results come back on a receiver thread that resolves Futures by job id.
    A second pipe services the worker's store RPCs (put_block metadata,
    flush) against the coordinator's ``DataStore``.
    """

    #: test hook (ISSUE 8): called once per spawn attempt before the fork —
    #: raising OSError from here simulates a transient fork/shm failure
    spawn_fault: Optional[Callable[[str, int], None]] = None
    #: spawn retry policy (bounded backoff + jitter via liveness.retry_call)
    spawn_attempts: int = 3
    spawn_base_delay_s: float = 0.05
    #: socket-transport handshake window (both channels must dial back)
    accept_timeout_s: float = 15.0

    def __init__(self, node: str, store: DataStore, *,
                 transport: str = "pipe",
                 host: Optional[str] = None,
                 chaos_shim: bool = False,
                 local_worker: bool = True,
                 bulk_registration: bool = False) -> None:
        if transport not in ("pipe", "socket"):
            raise ValueError(f"unknown transport {transport!r} "
                             f"(expected 'pipe' or 'socket')")
        self.node = node
        self.store = store
        self.transport = transport
        #: which machine the worker runs on — drives the liveness monitor's
        #: per-host quorum and the degraded-exchange routing (ISSUE 9);
        #: purely a label here, the fork is local either way in this repo
        self.host = host if host is not None else LOCAL_HOST
        #: whether THIS coordinator spawned the worker pid locally — only
        #: then may the pid-prefix /dev/shm sweep run (ISSUE 9 satellite:
        #: a remote worker's pid names some unrelated local process)
        self.local_worker = local_worker
        #: sweep passes skipped because the worker is not local (reported
        #: as ``sweep_skipped_remote`` — we cannot see a remote /dev/shm,
        #: so we count the skip honestly instead of pretending we swept)
        self.sweep_skips = 0
        #: the worker's PartitionStreamServer address (socket transport)
        self.exchange_endpoint: Optional[Tuple[str, int]] = None
        self._listener: Optional[FrameListener] = None
        self._proxy: Optional[ChaosProxy] = None
        ctx = _mp_context()
        spec = {"root": store.root, "nodes": list(store.nodes),
                "durable": store.durable, "compress": store.compress,
                "compress_level": store.compress_level,
                "journal_commits": store.journal_commits,
                "dfs_dir": store.dfs_dir,
                # columnar data plane (ISSUE 10): the store stage registers
                # a whole block batch in ONE put_batch RPC instead of one
                # synchronous round trip per block; off reproduces the
                # per-block PR-9 protocol exactly
                "bulk_registration": bulk_registration}
        attempt_no = itertools.count(1)

        def spawn_pipe() -> None:
            """One spawn attempt: pipes + fork + start, atomically retried —
            a transient fork/pipe failure used to abort the whole run on
            first try (satellite of ISSUE 8)."""
            n = next(attempt_no)
            if ProcessNodeExecutor.spawn_fault is not None:
                ProcessNodeExecutor.spawn_fault(node, n)
            self._conn, child_conn = ctx.Pipe()
            self._store_conn, child_store = ctx.Pipe()
            self._proc = ctx.Process(target=_worker_main,
                                     args=(node, child_conn, child_store, spec),
                                     daemon=True, name=f"ingest-node-{node}")
            self._proc.start()
            child_conn.close()
            child_store.close()

        def spawn_socket() -> None:
            """One socket-fabric spawn attempt: bind a listener, fork the
            worker with the dial-back address + token, accept both framed
            channels.  Any failure tears the half-built transport down and
            re-raises OSError so ``retry_call`` retries the whole attempt.
            With ``chaos_shim`` the worker dials a :class:`ChaosProxy` in
            front of the listener — the seam the chaos harness's network
            events (partition/drop/delay_conn) render onto."""
            n = next(attempt_no)
            if ProcessNodeExecutor.spawn_fault is not None:
                ProcessNodeExecutor.spawn_fault(node, n)
            self._listener = FrameListener()
            worker_addr = self._listener.address
            if chaos_shim:
                self._proxy = ChaosProxy(self._listener.address)
                worker_addr = self._proxy.address
            token = uuid.uuid4().hex
            self._proc = ctx.Process(target=_socket_worker_main,
                                     args=(node, worker_addr, token, spec),
                                     daemon=True, name=f"ingest-node-{node}")
            self._proc.start()
            try:
                conns: Dict[str, Any] = {}
                deadline = time.monotonic() + self.accept_timeout_s
                while not ("ctrl" in conns and "store" in conns):
                    left = deadline - time.monotonic()
                    if left <= 0:
                        raise TimeoutError(
                            f"worker {node}: handshake incomplete "
                            f"(got {sorted(conns)})")
                    c, role, _n, info = self._listener.accept_framed(
                        token, timeout_s=left)
                    conns[role] = c
                    if role == "ctrl":
                        ep = info.get("exchange_endpoint")
                        if ep:
                            self.exchange_endpoint = (ep[0], int(ep[1]))
                self._conn = conns["ctrl"]
                self._store_conn = conns["store"]
            except (OSError, TimeoutError) as e:
                self._close_transport()
                try:
                    self._proc.kill()
                except (ProcessLookupError, OSError):
                    pass
                raise OSError(f"socket spawn of {node} failed: {e}") from e

        _, used = retry_call(
            spawn_socket if transport == "socket" else spawn_pipe,
            attempts=self.spawn_attempts,
            base_delay_s=self.spawn_base_delay_s,
            retry_on=(OSError,))
        self.spawn_retries = used - 1   # attempts beyond the first
        self._last_beat = time.monotonic()
        self._ping_seq = itertools.count()
        self._send_lock = threading.Lock()
        self._lock = threading.Lock()
        self._pending: Dict[int, Future] = {}
        self._inflight_shm: Dict[int, str] = {}   # jid -> input segment name
        self._plans: Dict[int, Tuple[Any, str]] = {}   # id(orig) -> (pin, key)
        self._jid = itertools.count()
        self._dead = False
        self._recv_thread = threading.Thread(target=self._recv_loop,
                                             daemon=True,
                                             name=f"recv-{node}")
        self._store_thread = threading.Thread(target=self._store_loop,
                                              daemon=True,
                                              name=f"store-rpc-{node}")
        self._recv_thread.start()
        self._store_thread.start()

    # --------------------------------------------------------------- liveness
    @property
    def alive(self) -> bool:
        return not self._dead and self._proc.is_alive()

    def kill(self) -> None:
        """Test hook: simulated machine failure (SIGTERM the worker)."""
        self._proc.terminate()

    def hang(self) -> None:
        """Test hook: wedge the worker (SIGSTOP) — the process freezes with
        its pipe still open, the exact blind spot heartbeat liveness covers."""
        import signal
        os.kill(self._proc.pid, signal.SIGSTOP)

    def resume(self) -> None:
        """Undo :meth:`hang` (SIGCONT).  No-op on an exited process."""
        import signal
        try:
            os.kill(self._proc.pid, signal.SIGCONT)
        except (ProcessLookupError, OSError):
            pass

    # ------------------------------------------------- heartbeats (ISSUE 8)
    def send_ping(self) -> None:
        """Best-effort heartbeat probe.  Any reply — the pong, or whatever
        job traffic beats it — refreshes ``heartbeat_age``.  Send failures
        are swallowed: a closed pipe is the EOF path's business."""
        if self._dead:
            return
        try:
            self._send(("ping", next(self._ping_seq)))
        except WorkerDeath:
            pass

    def heartbeat_age(self) -> float:
        """Seconds since the worker last said anything on its pipe."""
        return time.monotonic() - self._last_beat

    def fail_unresponsive(self) -> None:
        """Declare a silent worker dead: SIGKILL (a SIGSTOP'd process never
        delivers SIGTERM — kill is the only signal a stopped process cannot
        hold off) and fail every in-flight future with WorkerDeath so the
        runtime's NodeFailure recovery takes over immediately instead of
        waiting on an EOF that may never come.  The transport is closed
        too: under a network partition the proxy never forwards the dead
        worker's EOF, so a blocked receiver thread must be unblocked from
        this side."""
        try:
            self._proc.kill()
        except (ProcessLookupError, OSError):
            pass
        self._mark_dead()
        self._close_transport()
        self._sweep_segments()

    # ------------------------------------------------ network chaos (ISSUE 9)
    def net_partition(self) -> None:
        """Chaos hook: go dark on this worker's link — the proxy stops
        pumping both directions, heartbeats die, and the liveness monitor's
        per-host quorum declares the host partitioned.  No-op without the
        chaos shim (pipe transport, or shim disabled)."""
        if self._proxy is not None:
            self._proxy.partition()

    def net_heal(self) -> None:
        if self._proxy is not None:
            self._proxy.heal()

    def net_drop(self, n: int = 64) -> None:
        """Chaos hook: discard the next ``n`` bytes worker->coordinator —
        the next coordinator recv sees a garbled/torn frame (FrameError ->
        WorkerDeath), never a hang."""
        if self._proxy is not None:
            self._proxy.drop_bytes(n)

    def net_delay(self, seconds: float) -> None:
        """Chaos hook: one-shot forwarding stall (slow link)."""
        if self._proxy is not None:
            self._proxy.delay(seconds)

    def stall_recv(self, seconds: float, rpc_every: float = 0.0) -> None:
        """Test hook (ISSUE 9 satellite): make the worker's recv loop go
        silent for ``seconds`` — no pongs — while issuing store RPCs every
        ``rpc_every`` seconds, reproducing a saturated-but-healthy worker
        deterministically."""
        try:
            self._send(("stall", float(seconds), float(rpc_every)))
        except WorkerDeath:
            pass

    # ------------------------------------------------------------------- send
    def _send(self, msg: Any) -> None:
        if self._dead:
            raise WorkerDeath(self.node)
        with self._send_lock:
            try:
                self._conn.send(msg)
            except (BrokenPipeError, OSError) as e:
                raise WorkerDeath(self.node) from e

    # ------------------------------------------------------------------ plans
    def install_plan(self, stage_plans: List[StagePlan]) -> str:
        """Ship the compiled plan once (the launch_remote seam, realized:
        the pickled DAG crosses to the worker, which keeps it resident)."""
        key_id = id(stage_plans)
        with self._lock:
            cached = self._plans.get(key_id)
            if cached is not None and cached[0] is stage_plans:
                return cached[1]
        blob = serialize_plans_for_worker(stage_plans, self.store)
        key = f"plan-{key_id:x}"
        self._send(("install", key, blob))
        with self._lock:
            self._plans[key_id] = (stage_plans, key)
        return key

    # ------------------------------------------------------------------- jobs
    def run_stage(self, plan_key: str, stage_idx: int,
                  items: List[IngestItem], *, lane: str = "main",
                  epoch: Optional[int] = None,
                  live_nodes: Optional[Sequence[str]] = None,
                  injections: Optional[Dict[int, int]] = None,
                  max_retries: int = 3,
                  shuffle_ctx: Optional[Dict[str, Any]] = None,
                  fetch_refs: Optional[List[Dict[str, Any]]] = None,
                  sink: bool = False,
                  source_ctx: Optional[Dict[str, Any]] = None) -> Future:
        """Run one stage over ``items`` on the worker; resolves to
        ``(output_items, stats)`` — or ``(manifest_payload, stats)`` when
        ``shuffle_ctx`` marks the stage a shuffle boundary (the worker dealt
        its partitions to the peers and replied metadata only).
        ``fetch_refs`` are the incoming partition descriptors the worker
        must merge into the stage's inputs.  ``source_ctx`` carries a
        worker-pull source: ``{"adapter", "descs"}`` shard descriptors the
        worker reads itself (ISSUE 6) — metadata on the pipe, never item
        bytes.  Fails with WorkerDeath if the node dies mid-flight (mapped
        to NodeFailure by the runtime)."""
        fut: Future = Future()
        if self._dead:
            fut.set_exception(WorkerDeath(self.node))
            return fut
        payload, lease = encode_items(items)
        jid = next(self._jid)
        with self._lock:
            self._pending[jid] = fut
            if payload.get("shm"):
                # registered before the send: a worker dying at any point
                # after this cannot leak the segment (_mark_dead reclaims)
                self._inflight_shm[jid] = payload["shm"]
        ctx = {"epoch": epoch,
               "live_nodes": list(live_nodes) if live_nodes else None,
               "injections": dict(injections or {}),
               "max_retries": max_retries,
               "shuffle": dict(shuffle_ctx) if shuffle_ctx else None,
               "fetch": list(fetch_refs) if fetch_refs else None,
               "sink": sink,
               "source": dict(source_ctx) if source_ctx else None}
        try:
            self._send(("run", jid, plan_key, stage_idx, lane, payload, ctx))
            if lease is not None:
                lease.detach()   # disown: consumer (or _mark_dead) unlinks
        except WorkerDeath as e:
            with self._lock:
                known = self._pending.pop(jid, None)
                self._inflight_shm.pop(jid, None)
            if lease is not None:
                lease.release()
            if known is not None:
                # still ours to fail; otherwise _mark_dead raced us here and
                # already failed the future with WorkerDeath
                fut.set_exception(e)
        return fut

    # -------------------------------------------------------------- receivers
    def _recv_loop(self) -> None:
        try:
            while True:
                msg = self._conn.recv()
                self._last_beat = time.monotonic()   # any traffic is a beat
                kind = msg[0]
                if kind == "pong":
                    continue
                if kind == "done":
                    _, jid, payload, stats = msg
                    with self._lock:
                        fut = self._pending.pop(jid, None)
                        self._inflight_shm.pop(jid, None)
                    if fut is None:
                        continue
                    try:
                        if (isinstance(payload, dict)
                                and payload.get("kind") in ("xmanifest",
                                                            "sink")):
                            # exchange manifest / sink count: metadata only
                            fut.set_result((payload, stats))
                        else:
                            # copy=True: results outlive the hop (retained
                            # epoch outputs) — the segment dies here
                            items, _ = decode_items(payload, copy=True)
                            fut.set_result((items, stats))
                    except BaseException as e:
                        fut.set_exception(e)
                elif kind == "fail":
                    _, jid, exc = msg
                    with self._lock:
                        fut = self._pending.pop(jid, None)
                        self._inflight_shm.pop(jid, None)
                    if fut is not None:
                        fut.set_exception(
                            exc if isinstance(exc, BaseException)
                            else RuntimeError(str(exc)))
        except (EOFError, OSError):
            pass
        finally:
            self._mark_dead()

    def _mark_dead(self) -> None:
        """Pipe EOF == the sentinel: the worker process is gone.  Every
        pending and future job fails with WorkerDeath, which the runtime's
        stage barrier converts into the NodeFailure fault path.  Input
        segments the dead worker never consumed are reclaimed here."""
        with self._lock:
            self._dead = True
            pending, self._pending = list(self._pending.values()), {}
            orphans, self._inflight_shm = list(self._inflight_shm.values()), {}
        for name in orphans:
            try:
                from multiprocessing import shared_memory
                seg = shared_memory.SharedMemory(name=name)
                seg.close()
                seg.unlink()
            except (FileNotFoundError, OSError):
                pass
        for fut in pending:
            fut.set_exception(WorkerDeath(self.node))

    def _sweep_segments(self) -> None:
        """Reclaim every segment the dead worker *created* (named
        ``psm_ing<pid>_*``, see ``items.create_segment``), announced or not.
        A SIGKILLed worker cannot clean up after itself, and a segment it
        created mid-produce was never registered anywhere the coordinator's
        bookkeeping could find it.  Two callers, both past the point where a
        live reader could race the unlink: the liveness declaration path
        (the worker was frozen for the whole miss window, so consumers of
        its announced segments have long attached) and ``shutdown`` (the
        engine is closing — no jobs in flight, nothing will attach again).
        The latter also catches survivors' orphans: a job result carrying a
        manifest can be preempted by a peer's NodeFailure before the
        coordinator records it, leaving segments only the producing worker's
        pid prefix still names.

        Remote workers (``local_worker=False``) are *skipped*, not swept:
        their ``/dev/shm`` is another machine's, and their pid can name an
        unrelated local process — unlinking by that prefix here would be
        both useless and dangerous.  The skip is counted (``sweep_skips``,
        surfaced as ``sweep_skipped_remote`` in run reports) so the old
        silent no-op can't masquerade as a clean sweep."""
        if not self.local_worker:
            self.sweep_skips += 1
            return
        pid = getattr(self._proc, "pid", None)
        if pid is None:
            return
        self._proc.join(timeout=2)   # let the SIGKILL land first
        sweep_pid_segments(pid)

    def _store_loop(self) -> None:
        try:
            while True:
                msg = self._store_conn.recv()
                # satellite fix (ISSUE 9): store RPCs are proof of life too.
                # A worker saturated in a long batch block starves its ctrl
                # recv loop (no pongs) while actively committing blocks —
                # without this refresh the liveness monitor would SIGKILL a
                # healthy, working node.
                self._last_beat = time.monotonic()
                kind = msg[0]
                try:
                    if kind == "put":
                        kw = dict(msg[1])
                        entry = self.store.register_block_file(
                            kw.pop("node"), kw.pop("tmp_path"), **kw)
                        reply = ("ok", asdict(entry))
                    elif kind == "put_batch":
                        # columnar data plane (ISSUE 10): one round trip
                        # registers the whole block batch, order preserved —
                        # each record is exactly a "put" payload, so the
                        # store-side semantics (and retry story) are the
                        # per-block path's, minus the per-block latency.
                        # The reply carries only what the coordinator
                        # assigned (block id + final path); the worker holds
                        # everything else in the records it just sent
                        ents = self.store.register_block_batch(msg[1])
                        reply = ("ok", [(e.block_id, e.path) for e in ents])
                    elif kind == "staging":
                        reply = ("ok", self.store.staging_epoch_ids())
                    elif kind == "flush":
                        self.store.flush_manifest()
                        reply = ("ok", None)
                    else:
                        reply = ("err", f"unknown store RPC {kind!r}")
                except BaseException as e:
                    reply = ("err", f"{type(e).__name__}: {e}")
                self._store_conn.send(reply)
        except (EOFError, OSError):
            pass
        finally:
            # the worker never closes its store channel while alive, so a
            # dead store loop means a dead (or garbled-link) worker: fail
            # in-flight work now instead of waiting for the ctrl channel
            # to notice.  Idempotent, so the orderly-shutdown call is free.
            self._mark_dead()

    # --------------------------------------------------------------- exchange
    def drop_exchange(self, xids: Sequence[int]) -> None:
        """Best-effort: tell the worker to drop invalidated exchange rounds
        (epoch abort/replay).  A dead worker's buckets died with it."""
        if self._dead or not xids:
            return
        try:
            self._send(("drop", list(xids)))
        except WorkerDeath:
            pass

    # --------------------------------------------------------------- shutdown
    def _close_transport(self) -> None:
        """Close both channels plus the socket fabric's listener/proxy.
        Safe on a half-built executor (spawn-attempt cleanup) and
        idempotent; closing unblocks receiver threads whose peer is
        partitioned and will never deliver an EOF."""
        for conn in (getattr(self, "_conn", None),
                     getattr(self, "_store_conn", None)):
            if conn is not None:
                try:
                    conn.close()
                except OSError:
                    pass
        if self._proxy is not None:
            self._proxy.close()
        if self._listener is not None:
            self._listener.close()

    def shutdown(self) -> None:
        if not self._dead:
            try:
                self._send(("stop",))
            except WorkerDeath:
                pass
        self._proc.join(timeout=5)
        if self._proc.is_alive():
            self._proc.terminate()
            self._proc.join(timeout=5)
        self._mark_dead()
        self._sweep_segments()
        self._close_transport()
