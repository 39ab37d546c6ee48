"""Ingestion-aware training feeder (the Spark/MapReduce integration analogue).

``ingest_corpus`` runs the canonical LM ingestion plan — parse, length-
partition, pack into device-shaped blocks, serialize, store — and
``BlockFeeder`` replays the ingested blocks as train batches:

* replica/layout choice via ``filterReplica`` (packed blocks for training),
* block->task assignment via ``splitByKey`` folded to the mesh data-axis size,
* deserialize with projection pushdown (only tokens/mask reach the host batch),
* resumable position (checkpoint/restart integration) and a work-stealing
  queue across feeder tasks (straggler mitigation).
"""
from __future__ import annotations

import queue
import threading
import time
from typing import Any, Dict, Iterator, List, Optional, Sequence, Tuple

import numpy as np

from .. import tracing
from ..core import (DataAccess, DataStore, IngestItem, IngestPlan, create_stage,
                    format_, ingest, select, store)
from ..core.items import Columns
from .generators import as_file_items


def build_lm_plan(data_store: DataStore, *, seq_len: int, rows_per_block: int,
                  pad_id: int = 0, replicas: int = 1,
                  length_partitions: Optional[Sequence[int]] = None,
                  use_pallas: bool = False,
                  erasure: Optional[Dict[str, Any]] = None,
                  name: str = "lm_corpus") -> IngestPlan:
    """The canonical LM ingestion plan (DESIGN.md §2 table).

    ``use_pallas`` packs rows with the ``pack_tokens`` kernel; ``erasure``
    (the ``ErasureOp`` arguments, e.g. ``{"k": 10, "m": 3}``) stores the
    packed blocks Reed-Solomon coded."""
    plan = IngestPlan(name)
    s1 = select(plan, replicate=replicas if replicas > 1 else None)
    fmt_kw: Dict[str, Any] = {
        "pack": {"seq_len": seq_len, "rows_per_block": rows_per_block,
                 "pad_id": pad_id, "use_pallas": use_pallas},
        "serialize": "packed",
        "erasure": erasure,
    }
    if length_partitions is not None:
        fmt_kw["partition"] = {"key": "length", "scheme": "length",
                               "bounds": list(length_partitions)}
    s2 = format_(plan, s1, **fmt_kw)
    s3 = store(plan, s2, locate="roundrobin",
               locate_args={"num_locations": len(data_store.nodes)},
               upload=data_store)
    create_stage(plan, using=[s1, s2, s3], name="main")
    return plan


def ingest_corpus(docs: Columns, data_store: DataStore, *, seq_len: int,
                  rows_per_block: int, pad_id: int = 0, shards: int = 8,
                  replicas: int = 1,
                  length_partitions: Optional[Sequence[int]] = None):
    """Ingest a ragged-token corpus into packed blocks. Returns the RunReport."""
    plan = build_lm_plan(data_store, seq_len=seq_len, rows_per_block=rows_per_block,
                         pad_id=pad_id, replicas=replicas,
                         length_partitions=length_partitions)
    items = as_file_items(docs, shards)
    return ingest(plan, items, data_store)


class BlockFeeder:
    """Yields (tokens, loss_mask, positions, segment_ids) batches from ingested
    packed blocks, sharded across ``num_tasks`` feeder tasks (one per data-axis
    slot / host)."""

    FIELDS = ("tokens", "loss_mask", "positions", "segment_ids")

    def __init__(self, data_store: DataStore, *, num_tasks: int = 1, task: int = 0,
                 batch_rows: Optional[int] = None, seed: int = 0,
                 fields: Sequence[str] = FIELDS, start_step: int = 0,
                 start_offset: int = 0) -> None:
        self.store = data_store
        self.num_tasks, self.task = num_tasks, task
        self.batch_rows = batch_rows
        self.fields = tuple(fields)
        self.seed = seed
        # resumable position (checkpoint/restart): ``step`` is the first
        # block with unconsumed rows, ``offset`` how many of its rows earlier
        # batches already consumed — without the offset, the carry rows left
        # when batch_rows doesn't divide a block were dropped or replayed on
        # restart (bugfix, ISSUE 6)
        self.step = start_step
        self.offset = start_offset
        self.my_blocks = self._assigned_blocks()
        # deterministic per-epoch order shared by all tasks
        self._order = np.random.default_rng(seed).permutation(len(self.my_blocks))

    def _assigned_blocks(self):
        """This task's packed blocks: replica choice + block->task assignment
        (the one policy shared by construction and live refresh)."""
        self.access = DataAccess(self.store).filter_replica("serialize", "packed")
        splits = self.access.split_by_key("pack", num_tasks=self.num_tasks)
        return splits[self.task].blocks if self.task < len(splits) else []

    def __len__(self) -> int:
        return len(self.my_blocks)

    def _read(self, idx: int) -> Columns:
        e = self.my_blocks[int(self._order[idx % len(self._order)])]
        block = self.store.read_block(e.block_id)
        from ..layouts import deserialize_block
        return deserialize_block(block, projection=list(self.fields))

    def batches(self, num_steps: int) -> Iterator[Dict[str, np.ndarray]]:
        """Sequential, resumable batch stream.

        After every yielded batch, ``(self.step, self.offset)`` is the exact
        resume point: a fresh feeder constructed with
        ``start_step=step, start_offset=offset`` continues the stream with
        identical batches — no carry rows are lost or replayed.

        Building each batch (block reads, decode, concatenation) is one
        ``ib.feeder.batch`` span, closed before the batch is handed out."""
        built = self._build_batches(num_steps)
        while True:
            with tracing.span("ib.feeder.batch"):
                out = next(built, None)
                if out is None:
                    return
                tracing.annotate(rows=len(out[self.fields[0]]))
            yield out

    def _build_batches(self, num_steps: int
                       ) -> Iterator[Dict[str, np.ndarray]]:
        if not self.my_blocks:
            return
        buf: Dict[str, List[np.ndarray]] = {f: [] for f in self.fields}
        rows = 0
        produced = 0
        idx = self.step
        skip = self.offset
        # blocks backing ``buf``: [block index, rows consumed, total rows]
        pending: List[List[int]] = []
        while produced < num_steps:
            cols = self._read(idx)
            total = len(cols[self.fields[0]])
            start = min(skip, total)
            skip = 0
            take = total - start
            if take > 0:
                for f in self.fields:
                    buf[f].append(cols[f][start:] if start else cols[f])
                pending.append([idx, start, total])
                rows += take
            idx += 1
            target = self.batch_rows or take
            while target > 0 and rows >= target and produced < num_steps:
                cat = {f: np.concatenate(buf[f]) for f in self.fields}
                out = {f: cat[f][:target] for f in self.fields}
                buf = {f: [cat[f][target:]] for f in self.fields}
                rows -= target
                # advance the consumed-row cursor through the backing blocks
                need = target
                while need > 0 and pending:
                    blk = pending[0]
                    used = min(blk[2] - blk[1], need)
                    blk[1] += used
                    need -= used
                    if blk[1] >= blk[2]:
                        pending.pop(0)
                if pending:
                    self.step, self.offset = pending[0][0], pending[0][1]
                else:
                    self.step, self.offset = idx, 0
                produced += 1
                yield out

    # ------------------------------------------------------------- live tailing
    def refresh(self) -> int:
        """Pick up blocks committed since construction (or the last refresh):
        the streaming engine commits epochs while training runs, and the
        feeder's view extends without re-shuffling what it already replayed.
        Returns the number of newly visible blocks for this task."""
        fresh = self._assigned_blocks()
        known = {e.block_id for e in self.my_blocks}
        added = [e for e in fresh if e.block_id not in known]
        if added:
            start = len(self.my_blocks)
            self.my_blocks.extend(added)
            # new blocks replay in commit order after the shuffled prefix
            self._order = np.concatenate(
                [self._order, np.arange(start, len(self.my_blocks))]).astype(np.int64)
        return len(added)

    def tail(self, num_steps: int, poll_s: float = 0.05,
             timeout_s: float = 10.0) -> Iterator[Columns]:
        """Follow a live store: read each packed block once, in order, waiting
        for newly committed epochs when caught up.  Stops after ``num_steps``
        blocks or when no new epoch commits within ``timeout_s``."""
        from ..layouts import deserialize_block
        pos = 0
        deadline = time.monotonic() + timeout_s
        while pos < num_steps:
            if pos >= len(self.my_blocks):
                if self.refresh() == 0:
                    if time.monotonic() > deadline:
                        return
                    time.sleep(poll_s)
                    continue
                deadline = time.monotonic() + timeout_s
            block = self.store.read_block(self.my_blocks[pos].block_id)
            yield deserialize_block(block, projection=list(self.fields))
            pos += 1

    # ------------------------------------------------------------ work stealing
    @staticmethod
    def stealing_queue(feeders: Sequence["BlockFeeder"], num_steps: int
                       ) -> "queue.Queue[Dict[str, np.ndarray]]":
        """Fan several feeder tasks into one queue; fast tasks pull more work —
        a straggling feeder merely contributes fewer batches (DESIGN.md §5).

        The returned queue carries two extras: ``q.stop()`` — the shutdown
        path a consumer abandoning the stream early MUST call so the workers
        unblock and exit (bugfix, ISSUE 6: workers used to block forever on a
        full queue, and the old ``done`` event was never set) — and
        ``q.delivered()``, the number of batches actually enqueued (a permit
        claimed for a batch that was never placed is returned, so the count
        no longer includes undelivered batches)."""
        q: "queue.Queue[Dict[str, np.ndarray]]" = queue.Queue(maxsize=8)
        remaining = threading.Semaphore(num_steps)
        done = threading.Event()
        lock = threading.Lock()
        enqueued = [0]

        def work(f: "BlockFeeder") -> None:
            for b in f.batches(num_steps):
                if done.is_set():
                    return
                if not remaining.acquire(blocking=False):
                    return   # global quota claimed by faster tasks
                placed = False
                while not done.is_set():
                    try:
                        q.put(b, timeout=0.05)   # bounded: re-check shutdown
                        placed = True
                        break
                    except queue.Full:
                        continue
                if not placed:
                    remaining.release()   # never delivered: return the permit
                    return
                with lock:
                    enqueued[0] += 1
                    if enqueued[0] >= num_steps:
                        done.set()   # quota delivered: stop every worker

        threads = [threading.Thread(target=work, args=(f,), daemon=True)
                   for f in feeders]
        for t in threads:
            t.start()
        q.stop = done.set                    # type: ignore[attr-defined]
        q.delivered = lambda: enqueued[0]    # type: ignore[attr-defined]
        q.workers = threads                  # type: ignore[attr-defined]
        return q
