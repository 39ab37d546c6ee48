"""FORMAT-side ingestion operators: partition / chunk / order / serialize.

Paper Sec. IV-A: ``FORMAT s PARTITION BY p CHUNK BY c ORDER BY o SERIALIZE AS
z`` chains the operators in statement order; operators may repeat (multi-level
partitioning) or be reordered by the user (global vs per-chunk sort).
"""
from __future__ import annotations

from typing import Any, Callable, Dict, Iterable, List, Optional, Sequence

import numpy as np

from .. import tracing
from ..layouts import serialize_block
from .items import Columns, Granularity, IngestItem, concat_columns, num_rows, take_rows
from .operators import IngestOp, OpMode, register_op


# ------------------------------------------------------------------- partition
@register_op("partition")
class PartitionOp(IngestOp):
    """CHUNK -> CHUNK split by a partitioning function.

    Built-in schemes: ``hash`` (on ``key``), ``range`` (on ``key`` into
    ``num_partitions`` quantile ranges over ``bounds``), ``field`` (group by
    exact value), ``length`` (token-sequence length buckets — LM packing aid),
    or a custom callable Columns -> int array of partition ids.
    """

    name = "partition"
    granularity_in = Granularity.CHUNK
    granularity_out = Granularity.CHUNK
    # already numpy-vectorized per chunk; the default scalar-loop
    # process_batch is identical, and marking it capable lets partition
    # stages anchor columnar edges (ISSUE 10)
    batch_capable = True

    def __init__(self, key: Optional[str] = None, scheme: str = "hash",
                 num_partitions: int = 8, bounds: Optional[Sequence[float]] = None,
                 fn: Optional[Callable[[Columns], np.ndarray]] = None,
                 tag: Optional[str] = None, **kw: Any) -> None:
        super().__init__(key=key, scheme=scheme, num_partitions=num_partitions,
                         bounds=bounds, fn=fn, tag=tag, **kw)
        self.key, self.scheme, self.num_partitions = key, scheme, num_partitions
        self.bounds = None if bounds is None else np.asarray(bounds)
        self.fn = fn
        self.tag = tag

    @property
    def label_key(self) -> str:
        return self.tag or self.name

    def _pids(self, cols: Columns) -> np.ndarray:
        if self.fn is not None:
            return np.asarray(self.fn(cols), dtype=np.int64)
        vals = cols[self.key]
        if self.scheme == "hash":
            if vals.dtype.kind in "iu":
                h = vals.astype(np.uint64) * np.uint64(0x9E3779B97F4A7C15)
                return (h >> np.uint64(33)).astype(np.int64) % self.num_partitions
            return np.array([hash(v) % self.num_partitions for v in vals], dtype=np.int64)
        if self.scheme == "range":
            bounds = self.bounds
            if bounds is None:
                qs = np.linspace(0, 1, self.num_partitions + 1)[1:-1]
                bounds = np.quantile(vals.astype(np.float64), qs)
            return np.searchsorted(bounds, vals, side="right").astype(np.int64)
        if self.scheme == "field":
            _, inv = np.unique(vals, return_inverse=True)
            return inv.astype(np.int64)
        if self.scheme == "length":
            lens = vals if vals.ndim == 1 else (vals >= 0).sum(axis=-1)
            edges = np.asarray(self.bounds if self.bounds is not None
                               else [256, 512, 1024, 2048, 4096])
            return np.searchsorted(edges, lens, side="left").astype(np.int64)
        raise ValueError(f"unknown partition scheme {self.scheme!r}")

    def process(self, item: IngestItem) -> Iterable[IngestItem]:
        cols = item.data
        pids = self._pids(cols)
        for pid in np.unique(pids):
            part = take_rows(cols, np.nonzero(pids == pid)[0])
            yield IngestItem(part, item.granularity, item.labels, dict(item.meta)).with_label(
                self.label_key, int(pid))


# ----------------------------------------------------------------------- chunk
@register_op("chunk")
class ChunkOp(IngestOp):
    """CHUNK -> CHUNK re-chunking into ~``target_bytes`` (or ``target_rows``)
    units — the HDFS "100mbBlocks" analogue.  Buffers rows across inputs with
    the same upstream labels so chunk boundaries do not fragment partitions."""

    name = "chunk"
    granularity_in = Granularity.CHUNK
    granularity_out = Granularity.CHUNK
    batch_capable = True

    def __init__(self, target_bytes: Optional[int] = None, target_rows: Optional[int] = None,
                 **kw: Any) -> None:
        super().__init__(target_bytes=target_bytes, target_rows=target_rows, **kw)
        if target_bytes is None and target_rows is None:
            target_bytes = 4 << 20
        self.target_bytes, self.target_rows = target_bytes, target_rows

    def _rows_per_chunk(self, cols: Columns) -> int:
        if self.target_rows is not None:
            return max(1, self.target_rows)
        n = num_rows(cols)
        if n == 0:
            return 1
        bytes_per_row = max(1, sum(v.nbytes for v in cols.values()) // n)
        return max(1, int(self.target_bytes) // bytes_per_row)

    def process(self, item: IngestItem) -> Iterable[IngestItem]:
        cols = item.data
        n = num_rows(cols)
        step = self._rows_per_chunk(cols)
        idx = 0
        for start in range(0, max(n, 1), step):
            part = take_rows(cols, np.arange(start, min(start + step, n)))
            yield IngestItem(part, Granularity.CHUNK, item.labels, dict(item.meta)).with_label(
                self.name, idx)
            idx += 1


# ----------------------------------------------------------------------- order
@register_op("order")
class OrderOp(IngestOp):
    """CHUNK -> CHUNK sort rows by ``key`` (per-item; placing OrderOp before
    ChunkOp in the statement yields a global order, after it a per-chunk
    order — exactly the paper's s2/s3 discussion)."""

    name = "order"
    granularity_in = Granularity.CHUNK
    granularity_out = Granularity.CHUNK
    batch_capable = True

    def __init__(self, key: str, descending: bool = False, **kw: Any) -> None:
        super().__init__(key=key, descending=descending, **kw)
        self.key, self.descending = key, descending

    def process(self, item: IngestItem) -> Iterable[IngestItem]:
        cols = item.data
        order = np.argsort(cols[self.key], kind="stable")
        if self.descending:
            order = order[::-1]
        yield IngestItem(take_rows(cols, order), item.granularity, item.labels,
                         dict(item.meta)).with_label(self.name, self.key)


# ------------------------------------------------------------------- serialize
@register_op("serialize")
class SerializeOp(IngestOp):
    """CHUNK -> BLOCK: encode a record batch into a physical layout.

    Granularity changes here, so the pipelining rule keeps a materialization
    barrier after serialize.  CPU-heavy: runs in parallel mode by default
    (paper Sec. VI-A forks one instance per core for serialize).
    """

    name = "serialize"
    granularity_in = Granularity.CHUNK
    granularity_out = Granularity.BLOCK
    cpu_heavy = True
    batch_capable = True

    def __init__(self, layout: str = "columnar",
                 layouts: Optional[Sequence[str]] = None, **layout_kw: Any) -> None:
        super().__init__(layout=layout, layouts=layouts, **layout_kw)
        self.layout = layout
        # hybrid replicas (paper Sec. II-C): cycle layouts across a replica's
        # blocks so queries likely find some blocks in a favorable layout
        self.layouts = tuple(layouts) if layouts else None
        self._idx = 0
        self.layout_kw = {k: v for k, v in layout_kw.items()
                          if k not in ("num_threads", "layouts")}

    def process(self, item: IngestItem) -> Iterable[IngestItem]:
        layout = self.layout
        if self.layouts:
            layout = self.layouts[self._idx % len(self.layouts)]
            self._idx += 1
        block = serialize_block(item.data, layout, **self.layout_kw)
        out = IngestItem(block, Granularity.BLOCK, item.labels, dict(item.meta))
        yield out.with_label(self.name, layout)

    def process_batch(self, items: Sequence[IngestItem]) -> List[IngestItem]:
        """Batch serialize over the columnar chunk dicts (ISSUE 7): layout
        assignment is computed up front (the hybrid-layout cycle becomes
        deterministic, matching the serial iterator's order), then the
        per-chunk encodes fan out over the shared pool."""
        items = list(items)
        if self.layouts:
            layouts = [self.layouts[(self._idx + i) % len(self.layouts)]
                       for i in range(len(items))]
            self._idx += len(items)
        else:
            layouts = [self.layout] * len(items)
        if self.mode is OpMode.PARALLEL and len(items) > 1:
            blocks = list(self._ensure_pool().map(
                lambda p: serialize_block(p[0].data, p[1], **self.layout_kw),
                zip(items, layouts)))
        else:
            blocks = [serialize_block(it.data, ly, **self.layout_kw)
                      for it, ly in zip(items, layouts)]
        return [IngestItem(b, Granularity.BLOCK, it.labels, dict(it.meta))
                .with_label(self.name, ly)
                for b, it, ly in zip(blocks, items, layouts)]


# ------------------------------------------------------------------- pack (LM)
@register_op("pack")
class PackOp(IngestOp):
    """CHUNK -> CHUNK: pack ragged token sequences into fixed (rows, seq_len)
    matrices with loss masks + positions — the TPU-era serialize hot path
    (DESIGN.md §2).  Sequences are greedily packed first-fit into rows; rows
    are emitted when the buffer reaches ``rows_per_block``.

    Input fields: ``tokens`` (object array of 1-D int arrays) or
    (``tokens``, ``length``) padded matrix.  Output fields: ``tokens``,
    ``loss_mask``, ``positions``, ``segment_ids`` each (rows, seq_len).
    """

    name = "pack"
    granularity_in = Granularity.CHUNK
    granularity_out = Granularity.CHUNK
    cpu_heavy = True
    batch_capable = True

    def __init__(self, seq_len: int = 2048, rows_per_block: int = 64, pad_id: int = 0,
                 use_pallas: bool = False, **kw: Any) -> None:
        super().__init__(seq_len=seq_len, rows_per_block=rows_per_block,
                         pad_id=pad_id, use_pallas=use_pallas, **kw)
        self.seq_len, self.rows_per_block, self.pad_id = seq_len, rows_per_block, pad_id
        self.use_pallas = use_pallas
        self._pack_kernel = None
        if use_pallas:
            from ..kernels import ops as k_ops  # lazy: jax import
            self._pack_kernel = k_ops.pack_tokens
        self._block_idx = 0

    def _sequences(self, cols: Columns) -> List[np.ndarray]:
        toks = cols["tokens"]
        if toks.dtype == object:
            return [np.asarray(t, dtype=np.int32) for t in toks]
        if "length" in cols:
            return [toks[i, : cols["length"][i]].astype(np.int32) for i in range(len(toks))]
        return [t.astype(np.int32) for t in toks]

    def _pack_rows(self, item: IngestItem) -> List[Dict[str, np.ndarray]]:
        """Stateless packing of one chunk's sequences into row dicts — the
        CPU-heavy half of ``process``, shared with the batch path so both can
        fan it out without racing on ``_block_idx``."""
        seqs = self._sequences(item.data)
        S = self.seq_len
        rows: List[Dict[str, np.ndarray]] = []
        cur_tok = np.full(S, self.pad_id, np.int32)
        cur_mask = np.zeros(S, np.int32)
        cur_pos = np.zeros(S, np.int32)
        cur_seg = np.zeros(S, np.int32)
        fill, seg = 0, 0

        def flush_row():
            nonlocal cur_tok, cur_mask, cur_pos, cur_seg, fill, seg
            rows.append({"tokens": cur_tok, "loss_mask": cur_mask,
                         "positions": cur_pos, "segment_ids": cur_seg})
            cur_tok = np.full(S, self.pad_id, np.int32)
            cur_mask = np.zeros(S, np.int32)
            cur_pos = np.zeros(S, np.int32)
            cur_seg = np.zeros(S, np.int32)
            fill, seg = 0, 0

        for s in seqs:
            # over-long documents are SPLIT across rows (never dropped:
            # packing conserves tokens — tests/test_properties.py)
            for off in range(0, len(s), S):
                piece = s[off : off + S]
                if fill + len(piece) > S and fill > 0:
                    flush_row()
                seg += 1
                n = len(piece)
                cur_tok[fill : fill + n] = piece
                cur_mask[fill : fill + n] = 1
                cur_pos[fill : fill + n] = np.arange(n, dtype=np.int32)
                cur_seg[fill : fill + n] = seg
                fill += n
                if fill == S:
                    flush_row()
        if fill > 0:
            flush_row()
        return rows

    def _emit_blocks(self, item: IngestItem,
                     rows: List[Dict[str, np.ndarray]]) -> Iterable[IngestItem]:
        for start in range(0, len(rows), self.rows_per_block):
            batch = rows[start : start + self.rows_per_block]
            out = {k: np.stack([r[k] for r in batch]) for k in batch[0]}
            yield IngestItem(out, Granularity.CHUNK, item.labels, dict(item.meta)).with_label(
                self.name, self._block_idx)
            self._block_idx += 1

    def process(self, item: IngestItem) -> Iterable[IngestItem]:
        yield from self._emit_blocks(item, self._pack_rows(item))

    # --------------------------------------------- kernel route (ISSUE 10)
    def _plan_rows(self, item: IngestItem) -> List[List[np.ndarray]]:
        """First-fit planning only: the exact walk of ``_pack_rows`` (same
        split/flush decisions), recording each row's pieces instead of
        writing row buffers — the host half of the kernel route."""
        seqs = self._sequences(item.data)
        S = self.seq_len
        rows: List[List[np.ndarray]] = []
        cur: List[np.ndarray] = []
        fill = 0
        for s in seqs:
            for off in range(0, len(s), S):
                piece = s[off : off + S]
                if fill + len(piece) > S and fill > 0:
                    rows.append(cur)
                    cur, fill = [], 0
                cur.append(piece)
                fill += len(piece)
                if fill == S:
                    rows.append(cur)
                    cur, fill = [], 0
        if fill > 0:
            rows.append(cur)
        return rows

    def _kernel_pack(self, items: List[IngestItem]
                     ) -> List[List[Dict[str, np.ndarray]]]:
        """Pack every item's rows through ``kernels.pack_tokens`` in ONE
        launch: the host-side first-fit plan concatenates all pieces into a
        flat int32 stream (a row's pieces are adjacent by construction), the
        kernel gathers each row's [start, len) slice into the padded
        (R, seq_len) token matrix and the valid-mask plane (== loss_mask —
        a row fills contiguously from 0).  Per-piece ``positions`` /
        ``segment_ids`` are cheap host-side fills from the plan.  Output
        rows are byte-identical to ``_pack_rows`` — the scalar path stays
        the correctness oracle (tests/test_columnar_plane.py)."""
        plans = [self._plan_rows(it) for it in items]
        all_rows = [row for plan in plans for row in plan]
        if not all_rows:
            return [[] for _ in plans]
        S = self.seq_len
        flat_parts: List[np.ndarray] = []
        starts, lens = [], []
        off = 0
        for row in all_rows:
            n = sum(len(p) for p in row)
            starts.append(off)
            lens.append(n)
            flat_parts.extend(row)
            off += n
        # pad stream and row table to bucketed sizes (empty padding rows):
        # the kernel then compiles for a few shapes, not one per batch
        from ..kernels.ops import bucket
        flat = np.zeros(bucket(off), np.int32)
        np.concatenate(flat_parts, out=flat[:off])
        R = bucket(len(all_rows))
        table = np.zeros((2, R), np.int32)
        table[0, :len(starts)] = starts
        table[1, :len(lens)] = lens
        # the span holds the launch, the device work and the copy back
        with tracing.span("ib.kernel.pack_tokens"):
            toks, mask, _ = self._pack_kernel(flat, table[0], table[1], S,
                                              pad_id=self.pad_id)
            toks, mask = np.asarray(toks), np.asarray(mask)
        self.kernel_calls += 1
        out_rows: List[Dict[str, np.ndarray]] = []
        for r, row in enumerate(all_rows):
            pos = np.zeros(S, np.int32)
            sid = np.zeros(S, np.int32)
            fill = 0
            for pi, piece in enumerate(row):
                n = len(piece)
                pos[fill : fill + n] = np.arange(n, dtype=np.int32)
                sid[fill : fill + n] = pi + 1
                fill += n
            out_rows.append({"tokens": toks[r], "loss_mask": mask[r],
                             "positions": pos, "segment_ids": sid})
        split: List[List[Dict[str, np.ndarray]]] = []
        i = 0
        for plan in plans:
            split.append(out_rows[i : i + len(plan)])
            i += len(plan)
        return split

    def process_batch(self, items: Sequence[IngestItem]) -> List[IngestItem]:
        """Batch pack (ISSUE 7): the stateless row packing fans out over the
        shared pool; block labels are assigned serially afterwards, so the
        output (and ``_block_idx`` order) is byte-identical to the serial
        iterator — unlike scalar parallel mode, where threads race on the
        block counter.  With ``use_pallas`` the whole batch routes through
        the ``pack_tokens`` kernel instead; a kernel failure raises."""
        items = list(items)
        if self._pack_kernel is not None and items:
            packed = self._kernel_pack(items)
        elif self.mode is OpMode.PARALLEL and len(items) > 1:
            packed = list(self._ensure_pool().map(self._pack_rows, items))
        else:
            packed = [self._pack_rows(it) for it in items]
        out: List[IngestItem] = []
        for item, rows in zip(items, packed):
            out.extend(self._emit_blocks(item, rows))
        return out
