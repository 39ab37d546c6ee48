"""DataStore — the storage substrate under ingestion plans (the HDFS analogue).

Physical blocks live under ``<root>/nodes/<node>/`` with their lineage-encoded
names (paper Sec. VII: the filename *is* the metadata).  A JSON manifest adds
what HDFS's namenode would know: node placement, checksums, replica groups and
erasure stripes — enough for the post-ingestion fault-tolerance daemon to
detect and recover failures (paper Sec. VI-C2).

A shared ``<root>/dfs/`` directory mediates shuffles (paper Sec. VI-B: local
groups are copied to the distributed file system, then read back per group).

Streaming epochs: the micro-batch runtime stages each epoch's blocks under an
epoch id and publishes them atomically via ``commit_epoch`` — the manifest only
ever records blocks of *committed* epochs.  The exactly-once commit point is
one appended line in the epoch journal (``manifest.epochs.jsonl``): a whole
line is a committed epoch, a torn line is not; ``flush_manifest`` (temp-write
+ rename) periodically compacts the journal into the base snapshot.  Blocks
with ``epoch=-1`` are batch-ingested and always visible.

Pipelined epochs (DESIGN.md §3): several epochs may stage *concurrently* —
each writer thread binds its epoch with ``epoch_context`` so ``put_block``
attributes blocks unambiguously — and the **commit sequencer** publishes
commits strictly in epoch-id order: ``commit_epoch(e)`` blocks while any
epoch < e is still staging, so ``since_epoch`` readers only ever observe a
gap-free, in-order prefix of the epoch sequence.
"""
from __future__ import annotations

import contextlib
import json
import os
import shutil
import threading
import time
import zlib
from dataclasses import asdict, dataclass, field
from typing import Any, Dict, Iterable, Iterator, List, Optional, Sequence, Set, Tuple

import numpy as np

from .. import tracing
from ..layouts import SerializedBlock
from .items import Granularity, IngestItem, Label


@dataclass
class BlockEntry:
    """Manifest entry for one stored physical block."""

    block_id: str              # unique id: lineage name + disambiguator
    node: str                  # placement node
    path: str                  # path relative to store root
    checksum: str
    nbytes: int
    labels: List[List[Any]]    # [[op, value], ...] lineage
    layout: str = "raw"
    logical_id: str = ""       # identifies the logical content (replicas share it)
    replica_index: int = 0     # which replica of logical_id this is
    stripe_id: str = ""        # erasure stripe membership ("" = not striped)
    stripe_pos: int = -1       # position within the stripe (data: 0..k-1, parity: k..k+m-1)
    is_parity: bool = False
    epoch: int = -1            # streaming epoch that wrote this block (-1 = batch)
    compressed: bool = False   # payload is zlib-compressed at rest
    raw_nbytes: int = -1       # logical (uncompressed) size; -1 = same as nbytes
    meta: Dict[str, Any] = field(default_factory=dict)

    def logical_nbytes(self) -> int:
        return self.raw_nbytes if self.raw_nbytes >= 0 else self.nbytes

    def to_manifest(self) -> Dict[str, Any]:
        """The exact dict ``dataclasses.asdict`` would build, minus its
        recursive deep-copy walk — every field here is already a plain
        value, and the manifest writer only serializes the result.  At
        ~30µs per ``asdict`` call a few hundred entries turn every
        manifest flush into a two-digit-millisecond stall (ISSUE 10)."""
        return {"block_id": self.block_id, "node": self.node,
                "path": self.path, "checksum": self.checksum,
                "nbytes": self.nbytes, "labels": self.labels,
                "layout": self.layout, "logical_id": self.logical_id,
                "replica_index": self.replica_index,
                "stripe_id": self.stripe_id, "stripe_pos": self.stripe_pos,
                "is_parity": self.is_parity, "epoch": self.epoch,
                "compressed": self.compressed,
                "raw_nbytes": self.raw_nbytes, "meta": self.meta}


@dataclass
class EpochEntry:
    """Manifest entry for one committed streaming epoch."""

    epoch: int
    n_blocks: int = 0
    n_items: int = 0           # source items the epoch consumed
    committed_at: float = 0.0  # wall-clock commit timestamp


def prepare_block_payload(data: Any, compress: bool,
                          compress_level: int) -> Tuple[bytes, str, int]:
    """Item payload -> (stored bytes, layout id, logical size).  Shared by
    ``DataStore.put_block`` and the process backend's worker-side store
    client, so both backends accept exactly the same payload types and
    apply at-rest compression identically."""
    if isinstance(data, SerializedBlock):
        payload, layout = data.tobytes(), data.layout
    elif isinstance(data, np.ndarray):
        payload, layout = data.tobytes(), "raw"
    elif isinstance(data, (bytes, bytearray)):
        payload, layout = bytes(data), "raw"
    else:
        raise TypeError(f"cannot store payload of type {type(data)}")
    raw_nbytes = len(payload)
    if compress:   # at-rest compression: transparent to readers
        payload = zlib.compress(payload, compress_level)
    return payload, layout, raw_nbytes


class DataStore:
    #: how long a commit waits on out-of-order predecessors before giving up
    COMMIT_SEQUENCE_TIMEOUT_S = 60.0

    def __init__(self, root: str, nodes: Sequence[str] = ("node0",),
                 durable: bool = False, compress: bool = False,
                 compress_level: int = 3, journal_commits: bool = True,
                 journal_compact_lines: int = 512) -> None:
        """``durable=True`` fsyncs staged block files and the epoch-commit
        journal line — a committed epoch survives power loss, not just
        process death.  ``compress=True`` zlib-compresses block payloads at
        rest (transparent: ``read_payload`` decompresses; checksums stay
        logical).  ``journal_commits=False`` commits by rewriting the full
        manifest snapshot instead of appending a journal line — a single
        manifest file, at O(store) cost per commit (the pre-ISSUE-2
        behavior, kept for ops that want one file and as the pipelining
        benchmark's baseline).  ``journal_compact_lines`` bounds the epoch
        journal: once it exceeds that many commit lines, the next commit
        auto-folds it into the base snapshot (``flush_manifest``), so a
        long-running stream never replays an unbounded journal on open
        (0/None disables auto-compaction)."""
        self.root = root
        self.nodes = list(nodes)
        self.durable = durable
        self.compress = compress
        self.compress_level = compress_level
        self.journal_commits = journal_commits
        self.journal_compact_lines = journal_compact_lines
        self._journal_lines = 0      # commit lines currently in the journal
        self._lock = threading.Lock()
        self._commit_cv = threading.Condition(self._lock)
        self.entries: Dict[str, BlockEntry] = {}
        self.epochs: Dict[int, EpochEntry] = {}   # committed epochs only
        self._staging: Set[int] = set()           # epochs currently staging
        # staging epoch -> its block ids, so commit/abort are O(epoch
        # blocks), not an O(store) scan
        self._epoch_blocks: Dict[int, List[str]] = {}
        self._epoch_ctx = threading.local()       # per-thread staging binding
        self._dead_nodes: Set[str] = set()        # in-flight node deaths
        # shuffle-exchange spill paths of *live* rounds (leased by the
        # ShuffleCoordinator): gc_orphans keeps these and reclaims the rest
        # — after a crash a fresh store holds no leases, so a dead epoch's
        # partition files become reclaimable garbage
        self._exchange_leases: Set[str] = set()
        os.makedirs(self.dfs_dir, exist_ok=True)
        for n in self.nodes:
            os.makedirs(self.node_dir(n), exist_ok=True)
        self._load_manifest()

    # ----------------------------------------------------------------- layout
    @property
    def manifest_path(self) -> str:
        return os.path.join(self.root, "manifest.json")

    @property
    def epoch_journal_path(self) -> str:
        return os.path.join(self.root, "manifest.epochs.jsonl")

    @property
    def dfs_dir(self) -> str:
        return os.path.join(self.root, "dfs")

    def node_dir(self, node: str) -> str:
        return os.path.join(self.root, "nodes", node)

    # --------------------------------------------------------------- manifest
    def _load_manifest(self) -> None:
        if os.path.exists(self.manifest_path):
            with open(self.manifest_path) as f:
                raw = json.load(f)
            if "blocks" in raw:        # epoch-aware format
                self.entries = {k: BlockEntry(**v) for k, v in raw["blocks"].items()}
                self.epochs = {int(k): EpochEntry(**v)
                               for k, v in raw.get("epochs", {}).items()}
            else:                      # legacy flat block map
                self.entries = {k: BlockEntry(**v) for k, v in raw.items()}
        self._replay_epoch_journal()

    def _replay_epoch_journal(self) -> None:
        """Apply epoch-commit journal lines on top of the base snapshot.

        A torn trailing line (crash mid-append) is simply an epoch that never
        committed — its blocks stay unreferenced and ``gc_orphans`` reclaims
        them; lines for epochs already in the snapshot are skipped (crash
        between snapshot rename and journal truncation)."""
        if not os.path.exists(self.epoch_journal_path):
            return
        with open(self.epoch_journal_path) as f:
            for line in f:
                try:
                    rec = json.loads(line)
                except json.JSONDecodeError:
                    break   # torn tail: everything after it never committed
                self._journal_lines += 1
                entry = EpochEntry(**rec["epoch"])
                if entry.epoch in self.epochs:
                    continue
                self.epochs[entry.epoch] = entry
                for k, v in rec["blocks"].items():
                    self.entries[k] = BlockEntry(**v)

    def flush_manifest(self) -> None:
        """Atomically publish the full manifest snapshot (write-temp + rename)
        and compact the epoch-commit journal into it.

        Blocks of a still-staging epoch are withheld: a crash before
        ``commit_epoch`` leaves at most orphaned ``.blk`` files that no
        manifest references — the epoch never half-commits.
        """
        with self._lock:
            blocks = {k: v.to_manifest() for k, v in self.entries.items()
                      if v.epoch < 0 or v.epoch in self.epochs}
            payload = {"blocks": blocks,
                       "epochs": {str(k): asdict(v) for k, v in self.epochs.items()}}
            tmp = self.manifest_path + ".tmp"
            with open(tmp, "w") as f:
                # one buffered write of a compact dump: indent (even 0)
                # forces json's pure-Python encoder — on a manifest with
                # hundreds of blocks that is a ~100ms stall per flush,
                # ~10x the C encoder this way (ISSUE 10)
                f.write(json.dumps(payload, separators=(",", ":")))
                if self.durable:
                    f.flush()
                    os.fsync(f.fileno())
            os.replace(tmp, self.manifest_path)
            # journal lines are now folded into the snapshot; a crash right
            # here only leaves duplicate records, which replay skips
            if os.path.exists(self.epoch_journal_path):
                os.remove(self.epoch_journal_path)
            self._journal_lines = 0

    # ------------------------------------------------------------------ epochs
    def begin_epoch(self, epoch: int) -> None:
        """Start staging blocks under ``epoch``.  Re-ingesting a committed
        epoch is refused — the exactly-once guard for replays.

        Several epochs may stage concurrently (pipelined streaming overlaps
        epoch N's store/commit with epoch N+1's ingest).  A writer thread that
        stages blocks while more than one epoch is open must bind its epoch
        with ``epoch_context`` so ``put_block`` attributes them unambiguously.
        Re-beginning a still-staging epoch is a no-op (epoch replay)."""
        with self._lock:
            if epoch in self.epochs:
                raise ValueError(f"epoch {epoch} already committed")
            self._staging.add(epoch)

    @contextlib.contextmanager
    def epoch_context(self, epoch: Optional[int]) -> Iterator[None]:
        """Bind ``put_block`` calls on this thread to a staging epoch (None =
        no binding: batch writes, or single-staging-epoch legacy mode)."""
        prev = getattr(self._epoch_ctx, "epoch", None)
        self._epoch_ctx.epoch = epoch
        try:
            yield
        finally:
            self._epoch_ctx.epoch = prev

    def _current_epoch(self) -> int:
        """Epoch to attribute a put_block to: thread binding first, else the
        single staging epoch, else batch (-1).  Ambiguity is an error — a
        block silently attached to the wrong epoch would break atomicity."""
        bound = getattr(self._epoch_ctx, "epoch", None)
        if bound is not None:
            return bound
        if not self._staging:
            return -1
        if len(self._staging) == 1:
            return next(iter(self._staging))
        raise RuntimeError(
            f"epochs {sorted(self._staging)} are staging concurrently; "
            f"writers must bind one with DataStore.epoch_context")

    def commit_epoch(self, epoch: int, n_items: int = 0) -> EpochEntry:
        """Atomically publish every block staged under ``epoch``.

        The commit sequencer: if any *smaller* epoch id is still staging, this
        call blocks until that epoch commits or aborts, so commits land in
        strict epoch order and readers never observe a gap in the committed
        sequence (DESIGN.md §3).

        The durable commit point is one appended journal line (O(epoch
        blocks), not an O(store) manifest rewrite): a fully-written line is a
        committed epoch, a torn line is not — ``flush_manifest`` periodically
        folds the journal into the snapshot."""
        with tracing.span("ib.store.commit"):
            return self._commit_epoch(epoch, n_items)

    def _commit_epoch(self, epoch: int, n_items: int) -> EpochEntry:
        deadline = time.monotonic() + self.COMMIT_SEQUENCE_TIMEOUT_S
        with self._commit_cv:
            if epoch in self.epochs:
                raise ValueError(f"epoch {epoch} already committed")
            while any(s < epoch for s in self._staging):
                remaining = deadline - time.monotonic()
                if remaining <= 0:
                    raise RuntimeError(
                        f"commit of epoch {epoch} timed out waiting for "
                        f"staged predecessors {sorted(s for s in self._staging if s < epoch)}")
                self._commit_cv.wait(timeout=remaining)
            if epoch in self.epochs:      # re-check after waiting
                raise ValueError(f"epoch {epoch} already committed")
            blocks = {k: self.entries[k].to_manifest()
                      for k in self._epoch_blocks.pop(epoch, [])
                      if k in self.entries}
            entry = EpochEntry(epoch=epoch, n_blocks=len(blocks),
                               n_items=n_items, committed_at=time.time())
            if self.journal_commits:
                # the commit point: one whole journal line lands (or doesn't)
                with open(self.epoch_journal_path, "a") as f:
                    f.write(json.dumps({"epoch": asdict(entry), "blocks": blocks}))
                    f.write("\n")
                    f.flush()
                    if self.durable:
                        os.fsync(f.fileno())
                self._journal_lines += 1
            self.epochs[epoch] = entry
            self._staging.discard(epoch)
            self._commit_cv.notify_all()
        if not self.journal_commits:
            self.flush_manifest()   # snapshot commit: temp-write + rename
        elif (self.journal_compact_lines
              and self._journal_lines > self.journal_compact_lines):
            # auto-compaction: fold the oversized journal into the snapshot
            # so a long-running stream never replays an unbounded journal
            self.flush_manifest()
        return entry

    def abort_epoch(self, epoch: int) -> int:
        """Roll back a failed epoch attempt: drop its staged entries and
        delete their physical files.  Committed epochs cannot be aborted."""
        with self._commit_cv:
            if epoch in self.epochs:
                raise ValueError(f"epoch {epoch} already committed")
            victims = [k for k in self._epoch_blocks.pop(epoch, [])
                       if k in self.entries]
            for k in victims:
                full = os.path.join(self.root, self.entries[k].path)
                if os.path.exists(full):
                    os.remove(full)
                del self.entries[k]
            self._staging.discard(epoch)
            self._commit_cv.notify_all()
        return len(victims)

    def epoch_committed(self, epoch: int) -> bool:
        return epoch in self.epochs

    def committed_epoch_ids(self) -> List[int]:
        with self._lock:   # consistent snapshot while the committer publishes
            return sorted(self.epochs)

    def staging_epoch_ids(self) -> List[int]:
        with self._lock:
            return sorted(self._staging)

    def next_epoch_id(self) -> int:
        with self._lock:
            return max(max(self.epochs, default=-1),
                       max(self._staging, default=-1)) + 1

    # ----------------------------------------------------- exchange leases
    def lease_exchange_path(self, path: str) -> None:
        """Pin a shuffle-exchange spill path (file or legacy spill dir) as
        belonging to a live round — ``gc_orphans`` will not reclaim it."""
        with self._lock:
            self._exchange_leases.add(os.path.abspath(path))

    def release_exchange_path(self, path: str) -> None:
        with self._lock:
            self._exchange_leases.discard(os.path.abspath(path))

    # ---------------------------------------------------------- node liveness
    def mark_node_dead(self, node: str) -> None:
        """In-flight node failure (runtime): stop placing new blocks there —
        its location IDs flow to the surviving nodes (paper Sec. VI-C1)."""
        self._dead_nodes.add(node)

    def mark_node_live(self, node: str) -> None:
        self._dead_nodes.discard(node)

    def live_nodes(self) -> List[str]:
        return [n for n in self.nodes if n not in self._dead_nodes]

    # ------------------------------------------------------------------- write
    def put_block(self, item: IngestItem, node: str, *, logical_id: str = "",
                  replica_index: int = 0, stripe_id: str = "", stripe_pos: int = -1,
                  is_parity: bool = False) -> BlockEntry:
        payload, layout, raw_nbytes = prepare_block_payload(
            item.data, self.compress, self.compress_level)
        base = item.lineage_name()
        with self._lock:
            block_id = base
            k = 0
            while block_id in self.entries:
                k += 1
                block_id = f"{base}_{k}"
            rel = os.path.join("nodes", node, block_id + ".blk")
            entry = BlockEntry(
                block_id=block_id, node=node, path=rel,
                checksum=item.checksum(), nbytes=len(payload),
                labels=[[l.op, l.value] for l in item.labels],
                layout=layout, logical_id=logical_id or self._logical_id(item),
                replica_index=replica_index, stripe_id=stripe_id,
                stripe_pos=stripe_pos, is_parity=is_parity,
                epoch=self._current_epoch(),
                compressed=self.compress, raw_nbytes=raw_nbytes,
                meta=dict(item.meta),
            )
            self.entries[block_id] = entry
            if entry.epoch >= 0:   # index for O(epoch) commit/abort
                self._epoch_blocks.setdefault(entry.epoch, []).append(block_id)
        full = os.path.join(self.root, rel)
        os.makedirs(os.path.dirname(full), exist_ok=True)
        with open(full, "wb") as f:
            f.write(payload)
            if self.durable:   # staged data must survive a crash-then-commit
                f.flush()
                os.fsync(f.fileno())
        return entry

    #: columnar data plane (ISSUE 10): direct-call stores take the bulk
    #: registration path unconditionally — without an RPC boundary it is
    #: byte-for-byte the per-block loop, so thread-backend runs are
    #: identical columnar on or off
    bulk_registration = True

    def put_block_batch(self, reqs: Sequence[Dict[str, Any]]
                        ) -> List["BlockEntry"]:
        """Register a whole block batch, order preserved (ISSUE 10).  Each
        request is a ``put_block`` call as a dict (``item``, ``node``, plus
        the keyword metadata); on a direct-call store this IS the per-block
        loop — the worker-side twin (``_WorkerStoreClient``) collapses it
        into one coordinator round trip."""
        return [self.put_block(r["item"], r["node"],
                               **{k: v for k, v in r.items()
                                  if k not in ("item", "node")})
                for r in reqs]

    def register_block_file(self, node: str, tmp_path: str, *, base: str,
                            checksum: str, nbytes: int, raw_nbytes: int,
                            compressed: bool, labels: List[List[Any]],
                            layout: str, logical_id: str, replica_index: int,
                            stripe_id: str, stripe_pos: int, is_parity: bool,
                            meta: Dict[str, Any], epoch: int) -> BlockEntry:
        """Adopt a block file a *worker process* already wrote (DESIGN.md §6).

        The process backend keeps the heavy work — serialization, compression,
        the disk write — in the worker, which writes to a ``.tmp`` name the
        orphan GC never scans; only this metadata registration is routed
        through the coordinator, which owns the manifest: it allocates the
        unique block id under the store lock, records the entry (attributed
        to the worker's staging ``epoch``), and renames the temp file into
        its final lineage-encoded path.  Entry-before-rename preserves the
        ``gc_orphans`` invariant: every visible ``.blk`` file has an entry.
        """
        with self._lock:
            if epoch >= 0 and epoch in self.epochs:
                raise ValueError(f"epoch {epoch} already committed")
            block_id = base
            k = 0
            while block_id in self.entries:
                k += 1
                block_id = f"{base}_{k}"
            rel = os.path.join("nodes", node, block_id + ".blk")
            entry = BlockEntry(
                block_id=block_id, node=node, path=rel, checksum=checksum,
                nbytes=nbytes, labels=labels, layout=layout,
                logical_id=logical_id or base, replica_index=replica_index,
                stripe_id=stripe_id, stripe_pos=stripe_pos,
                is_parity=is_parity, epoch=epoch, compressed=compressed,
                raw_nbytes=raw_nbytes, meta=dict(meta))
            self.entries[block_id] = entry
            if epoch >= 0:
                self._epoch_blocks.setdefault(epoch, []).append(block_id)
        full = os.path.join(self.root, rel)
        os.makedirs(os.path.dirname(full), exist_ok=True)
        os.replace(tmp_path, full)
        return entry

    def register_block_batch(self, records: Sequence[Dict[str, Any]]
                             ) -> List[BlockEntry]:
        """Bulk twin of :meth:`register_block_file` (ISSUE 10): adopt a whole
        worker-written batch under ONE lock acquisition, order preserved.
        Each record is exactly a ``register_block_file`` call as a dict
        (``node``, ``tmp_path``, plus the keyword metadata).  Every epoch is
        validated and every entry recorded before any temp file renames, so
        entry-before-rename holds batch-wide; the renames share one
        made-directory memo instead of 512 ``makedirs`` round trips."""
        entries: List[BlockEntry] = []
        renames: List[Tuple[str, str]] = []
        with self._lock:
            for rec in records:
                epoch = rec["epoch"]
                if epoch >= 0 and epoch in self.epochs:
                    raise ValueError(f"epoch {epoch} already committed")
            for rec in records:
                base = rec["base"]
                block_id = base
                k = 0
                while block_id in self.entries:
                    k += 1
                    block_id = f"{base}_{k}"
                rel = os.path.join("nodes", rec["node"], block_id + ".blk")
                entry = BlockEntry(
                    block_id=block_id, node=rec["node"], path=rel,
                    checksum=rec["checksum"], nbytes=rec["nbytes"],
                    labels=rec["labels"], layout=rec["layout"],
                    logical_id=rec["logical_id"] or base,
                    replica_index=rec["replica_index"],
                    stripe_id=rec["stripe_id"], stripe_pos=rec["stripe_pos"],
                    is_parity=rec["is_parity"], epoch=rec["epoch"],
                    compressed=rec["compressed"],
                    raw_nbytes=rec["raw_nbytes"], meta=dict(rec["meta"]))
                self.entries[block_id] = entry
                if entry.epoch >= 0:
                    self._epoch_blocks.setdefault(entry.epoch,
                                                  []).append(block_id)
                entries.append(entry)
                renames.append((rec["tmp_path"],
                                os.path.join(self.root, rel)))
        made = set()
        for tmp, full in renames:
            d = os.path.dirname(full)
            if d not in made:
                os.makedirs(d, exist_ok=True)
                made.add(d)
            os.replace(tmp, full)
        return entries

    @staticmethod
    def _logical_id(item: IngestItem) -> str:
        """Replica-invariant identity: the lineage minus replicate/locate labels."""
        keep = [l for l in item.labels if not l.op.startswith(("replicate", "locate", "upload"))]
        return "_".join(str(l) for l in keep) or "raw"

    # -------------------------------------------------------------------- read
    def read_payload(self, block_id: str) -> bytes:
        """The block's *logical* payload (at-rest compression is peeled off)."""
        entry = self.entries[block_id]
        with open(os.path.join(self.root, entry.path), "rb") as f:
            raw = f.read()
        return zlib.decompress(raw) if entry.compressed else raw

    def read_block(self, block_id: str) -> SerializedBlock:
        entry = self.entries[block_id]
        raw = self.read_payload(block_id)
        if entry.layout == "raw":
            return SerializedBlock(layout="raw", payload=raw)
        return SerializedBlock.frombytes(raw)

    def read_item(self, block_id: str) -> IngestItem:
        entry = self.entries[block_id]
        labels = tuple(Label(op, v) for op, v in entry.labels)
        return IngestItem(self.read_block(block_id), Granularity.BLOCK, labels,
                          dict(entry.meta))

    # ------------------------------------------------------------------- query
    def blocks(self) -> List[BlockEntry]:
        with self._lock:   # consistent snapshot while a streaming epoch writes
            return list(self.entries.values())

    def blocks_with_label(self, op: str, value: Any = None) -> List[BlockEntry]:
        out = []
        for e in self.blocks():
            for lop, lval in e.labels:
                if lop == op and (value is None or lval == value):
                    out.append(e)
                    break
        return out

    def replicas_of(self, logical_id: str) -> List[BlockEntry]:
        return [e for e in self.blocks() if e.logical_id == logical_id]

    def stripe_members(self, stripe_id: str) -> List[BlockEntry]:
        out = [e for e in self.blocks() if e.stripe_id == stripe_id]
        return sorted(out, key=lambda e: e.stripe_pos)

    def total_bytes(self) -> int:
        return sum(e.nbytes for e in self.blocks())

    # --------------------------------------------------- failure detect/inject
    def verify_block(self, block_id: str) -> bool:
        """True if the physical file exists and matches its recorded size."""
        entry = self.entries.get(block_id)
        if entry is None:
            return False
        full = os.path.join(self.root, entry.path)
        if not os.path.exists(full):
            return False
        return os.path.getsize(full) == entry.nbytes

    def failed_blocks(self) -> List[str]:
        """The fault daemon's ``detect`` scan source (paper Fig. 3)."""
        return [e.block_id for e in self.blocks() if not self.verify_block(e.block_id)]

    def gc_orphans(self) -> List[str]:
        """Delete files no live reference covers and return their paths.

        Two kinds of crash garbage exist (the commit + exchange protocols
        guarantee there are no others):

        * ``.blk`` block files the manifest never references — an epoch
          aborted or crashed mid-stage.  Blocks of epochs still staging in
          *this* process are referenced by in-memory entries and are kept;
          after a crash, a fresh DataStore loads only the committed
          manifest, so the dead epoch's files become orphans here.
        * exchange spill files under ``dfs/`` — peer partition files
          (``exchange_*.part``), resident-bucket spills of narrow edges and
          pinned cross-segment rounds (``resident_*.part``, a crash
          mid-slice leaves them with no consumer), and legacy barrier group
          dirs (``shuffle_*``).  Live rounds lease their paths
          (``lease_exchange_path``); a crash drops the leases with the
          process, so a fresh store reclaims the files.

        The ``.blk`` scan holds the store lock and ``put_block`` registers
        the entry under it *before* writing the file, so a concurrently
        staged block can never be swept.  Exchange files are weaker: a
        worker writes the spill before its manifest reaches the coordinator
        (which leases the path on arrival), so running this scan
        *concurrently with an in-flight shuffle round* can race that window
        and sweep a not-yet-leased partition — the consumer then fails the
        stage and the epoch replays (an availability blip, never data
        loss).  Treat exchange-file reclamation as a crash-recovery /
        idle-time operation."""
        removed: List[str] = []
        with self._lock:
            referenced = {os.path.normpath(e.path) for e in self.entries.values()}
            for node in self.nodes:
                ndir = self.node_dir(node)
                if not os.path.isdir(ndir):
                    continue
                for fn in sorted(os.listdir(ndir)):
                    if not fn.endswith(".blk"):
                        continue
                    rel = os.path.normpath(os.path.join("nodes", node, fn))
                    if rel not in referenced:
                        os.remove(os.path.join(self.root, rel))
                        removed.append(rel)
            # ---- stale shuffle/exchange spills (ISSUE 4 satellite)
            from .exchange import is_exchange_file
            dfs = self.dfs_dir
            if os.path.isdir(dfs):
                for fn in sorted(os.listdir(dfs)):
                    full = os.path.abspath(os.path.join(dfs, fn))
                    if full in self._exchange_leases:
                        continue
                    rel = os.path.normpath(os.path.join("dfs", fn))
                    if os.path.isfile(full) and is_exchange_file(fn):
                        os.remove(full)
                        removed.append(rel)
                    elif os.path.isdir(full) and fn.startswith("shuffle_"):
                        shutil.rmtree(full, ignore_errors=True)
                        removed.append(rel)
        return removed

    def corrupt_block(self, block_id: str) -> None:
        entry = self.entries[block_id]
        full = os.path.join(self.root, entry.path)
        with open(full, "wb") as f:
            f.write(b"\x00corrupt")

    def kill_node(self, node: str) -> None:
        """Simulate a node failure: its local storage disappears."""
        shutil.rmtree(self.node_dir(node), ignore_errors=True)

    def restore_file(self, entry: BlockEntry, payload: bytes, node: Optional[str] = None) -> None:
        """Write a recovered *logical* payload back (optionally onto a
        different node), re-applying at-rest compression."""
        if node is not None and node != entry.node:
            entry.node = node
            entry.path = os.path.join("nodes", node, entry.block_id + ".blk")
        entry.raw_nbytes = len(payload)
        if self.compress:
            payload = zlib.compress(payload, self.compress_level)
            entry.compressed = True
        else:
            entry.compressed = False
        full = os.path.join(self.root, entry.path)
        os.makedirs(os.path.dirname(full), exist_ok=True)
        with open(full, "wb") as f:
            f.write(payload)
        entry.nbytes = len(payload)
