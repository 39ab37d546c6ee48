"""Ingest data items — the unit of data flowing through an ingestion plan.

The paper (Sec. III) defines *ingest data items* as raw files that may be broken
into smaller items (chunks, records, blocks) for fine-grained ingestion logic,
each carrying a list of *labels* denoting its lineage.

TPU-era adaptation (DESIGN.md §2): an item's payload is columnar — a dict of
equal-length numpy arrays — so operators are vectorized over whole chunks while
the item remains the paper's unit of control flow.  A RECORD-granularity item is
simply a chunk of length 1; a BLOCK is a device-ready, fixed-size packed array.
"""
from __future__ import annotations

import enum
import hashlib
import itertools
import os
import pickle
import threading
from dataclasses import dataclass, field, replace
from typing import Any, Dict, List, Optional, Sequence, Tuple

import numpy as np

# ---------------------------------------------------------------------------
# Attributable shared-memory segments (ISSUE 8)
# ---------------------------------------------------------------------------
_SEG_SEQ = itertools.count()


def create_segment(size: int):
    """Create a shared-memory segment named ``psm_ing<pid>_<seq>``.

    The default anonymous ``psm_<random>`` names are unattributable: when
    the liveness monitor SIGKILLs a wedged worker (the only signal a
    SIGSTOP'd process cannot hold off), any segment it created but had not
    yet announced to the coordinator would leak forever.  Encoding the
    creating pid into the name lets the coordinator sweep a dead worker's
    leftovers by prefix (see ``ProcessNodeExecutor._sweep_segments``).
    The ``psm_`` prefix is kept so existing leak detectors still match."""
    from multiprocessing import shared_memory
    while True:
        name = f"psm_ing{os.getpid()}_{next(_SEG_SEQ)}"
        try:
            return shared_memory.SharedMemory(create=True, size=size,
                                              name=name)
        except FileExistsError:
            continue   # stale leftover from a recycled pid: try the next seq


def sweep_pid_segments(pid: int) -> int:
    """Unlink every ``/dev/shm`` segment a (dead) worker pid created —
    the coordinator-side safety net behind the attributable naming above.
    Returns how many segments were reclaimed.

    This glob only sees the *local* host's ``/dev/shm``: a worker running
    on another machine leaves its segments in that machine's tmpfs, where
    this sweep cannot reach.  Callers with remote workers must therefore
    not call this and pretend the sweep happened — see
    ``ProcessNodeExecutor._sweep_segments``, which counts the skip into
    the run report instead (ISSUE 9 satellite)."""
    import glob
    swept = 0
    for path in glob.glob(f"/dev/shm/psm_ing{pid}_*"):
        try:
            os.unlink(path)
            swept += 1
        except OSError:
            pass
    return swept


class Granularity(enum.IntEnum):
    """Granularity ladder of ingest data items (paper Sec. III)."""

    FILE = 0      # raw input file (bytes, unparsed)
    CHUNK = 1     # parsed slice of a file: columnar record batch
    RECORD = 2    # single record (chunk of length 1)
    BLOCK = 3     # packed, serialized block — the storage/consumption unit


# Columnar payload: field name -> equal-length np.ndarray.
Columns = Dict[str, np.ndarray]


def num_rows(columns: Columns) -> int:
    if not columns:
        return 0
    return len(next(iter(columns.values())))


def concat_columns(parts: List[Columns]) -> Columns:
    parts = [p for p in parts if p and num_rows(p) > 0]
    if not parts:
        return {}
    keys = list(parts[0].keys())
    return {k: np.concatenate([p[k] for p in parts]) for k in keys}


def take_rows(columns: Columns, idx: np.ndarray) -> Columns:
    return {k: v[idx] for k, v in columns.items()}


# ------------------------------------------------------------------ device I/O
def as_device_array(arr: np.ndarray) -> Any:
    """Copy a host array onto the default JAX device for a kernel-backed
    stage, and check that it landed there: an array left on another device
    would make the kernel compile for that device instead.  JAX itself is
    imported lazily: the scalar tier never pays for it.
    """
    import jax
    device = jax.devices()[0]
    out = jax.device_put(np.ascontiguousarray(arr), device)
    if out.devices() != {device}:
        raise RuntimeError(f"array placed on {out.devices()}, not on the "
                           f"default device {device}")
    return out


def as_device_columns(columns: Columns) -> Dict[str, Any]:
    """``as_device_array`` over a decoded batch's columnar dict; non-array
    values (object columns) pass through untouched."""
    return {k: as_device_array(v) if isinstance(v, np.ndarray)
            and v.dtype != object else v
            for k, v in columns.items()}


@dataclass(frozen=True)
class Label:
    """One lineage entry: the operator that touched the item and the value it assigned."""

    op: str
    value: Any

    def __str__(self) -> str:  # used in lineage-encoded filenames
        return f"{self.op}-{self.value}"


@dataclass
class IngestItem:
    """A labelled ingest data item.

    ``data`` is payload whose type depends on granularity:
      FILE   -> bytes or str (path-like raw content)
      CHUNK  -> Columns (dict of equal-length numpy arrays)
      RECORD -> Columns with a single row
      BLOCK  -> SerializedBlock (see layouts/) or raw ndarray/bytes
    ``labels`` is the ordered lineage (paper Sec. VII: filename-encoded).
    """

    data: Any
    granularity: Granularity = Granularity.FILE
    labels: Tuple[Label, ...] = ()
    meta: Dict[str, Any] = field(default_factory=dict)

    # ------------------------------------------------------------------ labels
    def with_label(self, op: str, value: Any) -> "IngestItem":
        return replace(self, labels=self.labels + (Label(op, value),))

    def label_value(self, op: str, default: Any = None) -> Any:
        """Latest label value assigned by operator ``op`` (None if never touched)."""
        for lab in reversed(self.labels):
            if lab.op == op:
                return lab.value
        return default

    def label_values(self, op: str) -> List[Any]:
        return [l.value for l in self.labels if l.op == op]

    def lineage_name(self) -> str:
        """The paper's label-encoded physical file name: label1_label2_..._labeln."""
        return "_".join(str(l) for l in self.labels) or "raw"

    # ------------------------------------------------------------------- sizes
    def nbytes(self) -> int:
        d = self.data
        if isinstance(d, (bytes, bytearray, str)):
            return len(d)
        if isinstance(d, np.ndarray):
            return int(d.nbytes)
        if isinstance(d, dict):
            return int(sum(v.nbytes for v in d.values() if isinstance(v, np.ndarray)))
        if hasattr(d, "nbytes"):
            return int(d.nbytes)
        return 0

    def nrows(self) -> int:
        if isinstance(self.data, dict):
            return num_rows(self.data)
        if isinstance(self.data, np.ndarray):
            return len(self.data)
        return 1

    def checksum(self) -> str:
        h = hashlib.sha256()
        d = self.data
        if isinstance(d, (bytes, bytearray)):
            h.update(d)
        elif isinstance(d, str):
            h.update(d.encode())
        elif isinstance(d, np.ndarray):
            h.update(np.ascontiguousarray(d).tobytes())
        elif isinstance(d, dict):
            for k in sorted(d):
                h.update(k.encode())
                h.update(np.ascontiguousarray(d[k]).tobytes())
        elif hasattr(d, "tobytes"):
            h.update(d.tobytes())
        return h.hexdigest()[:16]


def items_nbytes(items: Sequence["IngestItem"]) -> int:
    """Total payload bytes of an item batch — the unit every dataflow byte
    counter (`stage_coordinator_bytes`, `shuffle_peer_bytes`,
    `stage_resident_bytes`) accounts in, so thread- and process-backend
    numbers are comparable.  Accepts a ColumnarBatch (same accounting:
    payload bytes only)."""
    if isinstance(items, ColumnarBatch):
        return items.nbytes
    return sum(it.nbytes() for it in items)


# ---------------------------------------------------------------------------
# Shared-memory item codec (DESIGN.md §6: the process backend's data plane)
# ---------------------------------------------------------------------------
# Item batches crossing a process boundary are encoded with pickle protocol 5:
# every C-contiguous numpy buffer is exported out-of-band and packed into ONE
# ``multiprocessing.shared_memory`` segment, so the receiving process rebuilds
# the arrays as zero-copy views over the mapped segment (numpy's protocol-5
# ``_frombuffer`` path).  Small batches (< ``shm_min_bytes`` of array payload)
# skip the segment and ship fully inline — a pipe write is cheaper than a
# segment create/map for tiny epochs.  Object-dtype columns and non-array
# payloads ride in the in-band pickle either way.
#
# Lifetime: each segment has exactly one producer and one consumer.  The
# producer copies buffers in, then ``ShmLease.detach()``-es (close + drop the
# resource-tracker registration so the consumer's unlink is authoritative);
# the consumer maps it, uses the views, and ``release()``-s (close + unlink)
# when the decoded items are no longer referenced.

SHM_MIN_BYTES = 64 << 10   # below this, inline pickle beats a segment


class ShmLease:
    """Owns one shared-memory segment end-to-end of a transfer leg.

    A lease starts with one holder; ``share()`` adds one.  ``release()``
    drops a holder and only the *last* release unmaps/unlinks the segment —
    the multi-consumer lifetime rule of the worker-side partition exchange
    (DESIGN.md §4): a worker's resident partition may alias the segment a
    stage's input rode in on, so the stage job and the resident buffer each
    hold a share and the segment dies deterministically when the final
    consumer lets go."""

    def __init__(self, shm: Any) -> None:
        self._shm = shm
        self._refs = 1
        self._lock = threading.Lock()

    @property
    def name(self) -> Optional[str]:
        return self._shm.name if self._shm is not None else None

    @property
    def holders(self) -> int:
        with self._lock:
            return self._refs if self._shm is not None else 0

    def share(self) -> "ShmLease":
        """Add a holder (returns self): the segment now needs one more
        ``release()`` before it is unmapped and unlinked."""
        with self._lock:
            if self._shm is None:
                raise ValueError("cannot share a released/detached lease")
            self._refs += 1
        return self

    def detach(self) -> None:
        """Producer side: unmap and disown (the consumer will unlink)."""
        shm, self._shm = self._shm, None
        if shm is None:
            return
        try:
            from multiprocessing import resource_tracker
            resource_tracker.unregister(shm._name, "shared_memory")
        except Exception:
            pass
        shm.close()

    def release(self, unlink: bool = True) -> None:
        """Consumer side: drop one holder; the last release unmaps and (by
        default) destroys the segment."""
        with self._lock:
            if self._shm is not None:
                self._refs -= 1
                if self._refs > 0:
                    return
            shm, self._shm = self._shm, None
        if shm is None:
            return
        try:
            shm.close()
        except BufferError:
            # a view still points into the mapping: the unlink below frees
            # the name now and the memory when the last view dies
            pass
        finally:
            if unlink:
                try:
                    shm.unlink()
                except FileNotFoundError:
                    pass


def encode_items(items: Sequence["IngestItem"],
                 shm_min_bytes: int = SHM_MIN_BYTES
                 ) -> Tuple[Dict[str, Any], Optional[ShmLease]]:
    """Encode an item batch for a process hop.

    Returns ``(payload, lease)``; ``lease`` is None for the inline-pickle
    fallback, else the producer must ``detach()`` it once the payload has been
    handed to the transport.  ``payload`` is a plain picklable dict.

    Columnar fast path (ISSUE 10): a :class:`ColumnarBatch` writes its one
    contiguous column buffer straight into the segment — no per-item
    pickling; ``decode_items`` hands back the batch.
    """
    if isinstance(items, ColumnarBatch):
        header = pickle.dumps(items.header(), protocol=5)
        pay = np.ascontiguousarray(items.payload)
        if pay.nbytes < shm_min_bytes:
            return {"kind": "pickle", "columnar": True, "meta": header,
                    "buffers": [bytearray(memoryview(pay).cast("B"))]}, None
        shm = create_segment(max(pay.nbytes, 1))
        shm.buf[:pay.nbytes] = memoryview(pay).cast("B")
        return {"kind": "shm", "columnar": True, "meta": header,
                "shm": shm.name, "payload_nbytes": pay.nbytes}, ShmLease(shm)
    buffers: List[pickle.PickleBuffer] = []
    meta = pickle.dumps(list(items), protocol=5,
                        buffer_callback=buffers.append)
    views = [b.raw() for b in buffers]
    total = sum(v.nbytes for v in views)
    if total < shm_min_bytes:
        # inline fast path, one pickle pass: ship the out-of-band buffers
        # next to the meta stream (bytearray: reconstructed arrays must stay
        # writable, like the shm path's views)
        inline = [bytearray(v) for v in views]
        for b in buffers:
            b.release()
        return {"kind": "pickle", "meta": meta, "buffers": inline}, None
    shm = create_segment(max(total, 1))
    offsets: List[Tuple[int, int]] = []
    off = 0
    for v in views:
        shm.buf[off:off + v.nbytes] = v.cast("B")
        offsets.append((off, v.nbytes))
        off += v.nbytes
    for b in buffers:
        b.release()
    return {"kind": "shm", "meta": meta, "shm": shm.name,
            "offsets": offsets}, ShmLease(shm)


def decode_items(payload: Dict[str, Any], copy: bool = False
                 ) -> Tuple[List["IngestItem"], Optional[ShmLease]]:
    """Decode a batch produced by :func:`encode_items`.

    With ``copy=False`` the arrays are zero-copy views over the mapped
    segment: the caller must hold the returned lease alive while the items
    are in use and ``release()`` it afterwards.  With ``copy=True`` the
    arrays are materialized and the segment is released (and unlinked)
    before returning — the safe mode when decoded items outlive the call.

    A payload carrying ``columnar=True`` (see the ``encode_items`` fast
    path) decodes to the :class:`ColumnarBatch` itself instead of an item
    list — same ``(value, lease)`` contract.
    """
    if payload.get("columnar"):
        header = pickle.loads(payload["meta"])
        if payload["kind"] == "pickle":
            pay = np.frombuffer(payload["buffers"][0], np.uint8)
            return ColumnarBatch.from_header(header, pay), None
        from multiprocessing import shared_memory
        shm = shared_memory.SharedMemory(name=payload["shm"])
        lease = ShmLease(shm)
        pay = np.frombuffer(shm.buf, np.uint8,
                            count=payload["payload_nbytes"])
        batch = ColumnarBatch.from_header(header, pay)
        if not copy:
            return batch, lease
        batch.payload = pay.copy()
        del pay
        lease.release()
        return batch, None
    if payload["kind"] == "pickle":
        return pickle.loads(payload["meta"],
                            buffers=payload.get("buffers") or ()), None
    from multiprocessing import shared_memory
    shm = shared_memory.SharedMemory(name=payload["shm"])
    lease = ShmLease(shm)
    base = memoryview(shm.buf)
    items = pickle.loads(payload["meta"],
                         buffers=[base[o:o + l] for o, l in payload["offsets"]])
    if not copy:
        return items, lease
    # comprehension scope: no loop variable may outlive the release below,
    # or the segment unmaps with exported views (BufferError at GC)
    out = [_materialize_item(it) for it in items]
    del items, base
    lease.release()
    return out, None


def _materialize_item(item: "IngestItem") -> "IngestItem":
    """Deep-copy any array payload out of a shared-memory view."""
    d = item.data
    if isinstance(d, np.ndarray):
        d = d.copy()
    elif isinstance(d, dict):
        d = {k: (v.copy() if isinstance(v, np.ndarray) else v)
             for k, v in d.items()}
    else:
        return item
    return replace(item, data=d)


# ---------------------------------------------------------------------------
# Columnar batch plane (ISSUE 10): the unit that crosses stage edges
# ---------------------------------------------------------------------------
# A ColumnarBatch is one contiguous uint8 payload buffer + an int64 offsets
# vector + struct-of-arrays label/meta columns.  It represents a batch of
# IngestItems whose payload type and label shape are uniform — the common case
# between two batch-mode pipeline blocks — without any per-item pickling.
# ``from_items`` returns None for anything non-uniform: the scalar
# item-at-a-time path stays the fallback and correctness oracle everywhere.
#
# Payload kinds:
#   "bytes"   — raw byte payloads; ``offsets`` are byte offsets per item
#   "array"   — same-dtype ndarrays; byte offsets + per-item shapes in aux
#   "columns" — dict-of-arrays chunks sharing a schema; payload is
#               column-major (one region per field, regions in schema order)
#               and ``offsets`` are ROW offsets per item
#   "block"   — SerializedBlock payload bytes; layouts/headers in aux


def _label_column(vals: List[Any]) -> np.ndarray:
    """One label position across the batch as a column.  Tight numpy dtypes
    only when every value is exactly the same scalar type (``np.asarray``
    would silently stringify mixed lists and overflow huge ints); everything
    else rides an object column and round-trips through pickle faithfully."""
    t0 = type(vals[0])
    if t0 in (int, bool, float, str) and all(type(v) is t0 for v in vals):
        try:
            col = np.asarray(vals)
            if col.shape == (len(vals),) and col.dtype.kind in "biufU":
                return col
        except (OverflowError, ValueError):
            pass
    col = np.empty(len(vals), dtype=object)
    col[:] = vals
    return col


def _label_at(col: np.ndarray, i: int) -> Any:
    v = col[i]
    return v.item() if isinstance(v, np.generic) else v


class ColumnarBatch:
    """A batch of uniform IngestItems as column buffers (ISSUE 10)."""

    __slots__ = ("payload", "offsets", "kind", "aux",
                 "label_ops", "label_cols", "grans", "metas")

    def __init__(self, payload: np.ndarray, offsets: np.ndarray, kind: str,
                 aux: Dict[str, Any], label_ops: Tuple[str, ...],
                 label_cols: Tuple[np.ndarray, ...], grans: np.ndarray,
                 metas: Optional[List[Dict[str, Any]]]) -> None:
        self.payload = payload        # 1-D uint8, may view a shm segment
        self.offsets = offsets        # int64, len == count + 1
        self.kind = kind
        self.aux = aux
        self.label_ops = label_ops    # uniform per-item label op sequence
        self.label_cols = label_cols  # one value column per label position
        self.grans = grans            # int8 Granularity codes
        self.metas = metas            # None == every item's meta was empty

    def __len__(self) -> int:
        return len(self.grans)

    @property
    def nbytes(self) -> int:
        """Payload bytes only — exactly ``sum(it.nbytes())`` of the items, so
        manifest byte accounting is identical columnar on/off."""
        return int(self.payload.nbytes)

    # -------------------------------------------------------------- building
    @classmethod
    def from_items(cls, items: Sequence["IngestItem"]
                   ) -> Optional["ColumnarBatch"]:
        """Column-pack a batch; None when the batch is not uniform enough
        (mixed payload types/dtypes/schemas or divergent label shapes) — the
        caller falls back to the scalar path silently."""
        items = list(items)
        n = len(items)
        if n == 0:
            return cls(np.empty(0, np.uint8), np.zeros(1, np.int64), "bytes",
                       {}, (), (), np.empty(0, np.int8), None)
        try:
            ops0 = tuple(l.op for l in items[0].labels)
            for it in items[1:]:
                if tuple(l.op for l in it.labels) != ops0:
                    return None
            d0 = items[0].data
            if type(d0) is bytes:
                packed = cls._pack_bytes(items)
            elif type(d0) is np.ndarray:
                packed = cls._pack_arrays(items)
            elif type(d0) is dict:
                packed = cls._pack_columns(items)
            else:
                from ..layouts.blocks import SerializedBlock
                if type(d0) is SerializedBlock:
                    packed = cls._pack_blocks(items)
                else:
                    return None
            if packed is None:
                return None
            kind, payload, offsets, aux = packed
            label_cols = tuple(
                _label_column([it.labels[j].value for it in items])
                for j in range(len(ops0)))
            grans = np.fromiter((int(it.granularity) for it in items),
                                np.int8, n)
            metas = (None if all(not it.meta for it in items)
                     else [dict(it.meta) for it in items])
            return cls(payload, offsets, kind, aux, ops0, label_cols,
                       grans, metas)
        except Exception:
            return None   # fallback is sacred: never fail a uniformity probe

    @staticmethod
    def _byte_offsets(lens: List[int]) -> np.ndarray:
        offsets = np.zeros(len(lens) + 1, np.int64)
        np.cumsum(np.asarray(lens, np.int64), out=offsets[1:])
        return offsets

    @classmethod
    def _pack_bytes(cls, items):
        for it in items:
            if type(it.data) is not bytes:
                return None
        offsets = cls._byte_offsets([len(it.data) for it in items])
        payload = np.empty(int(offsets[-1]), np.uint8)
        for it, o in zip(items, offsets[:-1]):
            if it.data:
                payload[int(o):int(o) + len(it.data)] = \
                    np.frombuffer(it.data, np.uint8)
        return "bytes", payload, offsets, {}

    @classmethod
    def _pack_arrays(cls, items):
        d0 = items[0].data
        if d0.dtype.kind not in "biufSU":
            return None
        arrs = []
        for it in items:
            if type(it.data) is not np.ndarray or it.data.dtype != d0.dtype:
                return None
            arrs.append(np.ascontiguousarray(it.data))
        offsets = cls._byte_offsets([a.nbytes for a in arrs])
        payload = np.empty(int(offsets[-1]), np.uint8)
        for a, o in zip(arrs, offsets[:-1]):
            if a.nbytes:
                payload[int(o):int(o) + a.nbytes] = \
                    a.reshape(-1).view(np.uint8)
        return "array", payload, offsets, {
            "dtype": d0.dtype.str, "shapes": tuple(a.shape for a in arrs)}

    @classmethod
    def _pack_columns(cls, items):
        d0 = items[0].data
        keys = tuple(d0.keys())
        schema = []
        for k in keys:
            a0 = d0[k]
            if type(a0) is not np.ndarray or a0.dtype.kind not in "biufSU":
                return None
            schema.append((k, a0.dtype.str, a0.shape[1:]))
        rows = []
        for it in items:
            if type(it.data) is not dict or tuple(it.data.keys()) != keys:
                return None
            r = None
            for k, dstr, ts in schema:
                a = it.data[k]
                if (type(a) is not np.ndarray or a.dtype.str != dstr
                        or a.shape[1:] != ts):
                    return None
                if r is None:
                    r = a.shape[0]
                elif a.shape[0] != r:
                    return None
            rows.append(0 if r is None else r)
        offsets = cls._byte_offsets(rows)
        total_rows = int(offsets[-1])
        sizes = [np.dtype(dstr).itemsize * int(np.prod(ts, dtype=np.int64))
                 for _, dstr, ts in schema]
        payload = np.empty(total_rows * sum(sizes), np.uint8)
        pos = 0
        for (k, dstr, ts), rowbytes in zip(schema, sizes):
            size = total_rows * rowbytes
            region = payload[pos:pos + size].view(np.dtype(dstr)) \
                .reshape((total_rows,) + ts)
            r = 0
            for it in items:
                a = it.data[k]
                region[r:r + a.shape[0]] = a
                r += a.shape[0]
            pos += size
        return "columns", payload, offsets, {"schema": tuple(schema)}

    @classmethod
    def _pack_blocks(cls, items):
        from ..layouts.blocks import SerializedBlock
        for it in items:
            if type(it.data) is not SerializedBlock:
                return None
        offsets = cls._byte_offsets([len(it.data.payload) for it in items])
        payload = np.empty(int(offsets[-1]), np.uint8)
        for it, o in zip(items, offsets[:-1]):
            if it.data.payload:
                payload[int(o):int(o) + len(it.data.payload)] = \
                    np.frombuffer(it.data.payload, np.uint8)
        return "block", payload, offsets, {
            "layouts": tuple(it.data.layout for it in items),
            "headers": tuple(dict(it.data.header) for it in items)}

    # ------------------------------------------------------------- accessors
    def columns(self) -> Columns:
        """The whole batch's fields as full-length column views over the
        payload buffer — zero-copy, and the direct feed for
        :func:`as_device_columns` (ingest -> accelerator without a gather)."""
        if self.kind != "columns":
            raise ValueError(f"columns() on kind {self.kind!r}")
        total_rows = int(self.offsets[-1])
        out: Columns = {}
        pos = 0
        for k, dstr, ts in self.aux["schema"]:
            dt = np.dtype(dstr)
            size = total_rows * dt.itemsize * int(np.prod(ts, dtype=np.int64))
            out[k] = self.payload[pos:pos + size].view(dt) \
                .reshape((total_rows,) + tuple(ts))
            pos += size
        return out

    def device_columns(self) -> Dict[str, Any]:
        """Device arrays of the (possibly shm-backed) column buffers —
        :func:`as_device_array` places each field view."""
        return as_device_columns(self.columns())

    def label_col(self, op: str) -> Optional[np.ndarray]:
        """Value column of the LAST label written by ``op`` (mirrors
        ``IngestItem.label_value``'s last-wins scan), or None."""
        for j in range(len(self.label_ops) - 1, -1, -1):
            if self.label_ops[j] == op:
                return self.label_cols[j]
        return None

    # ----------------------------------------------------------- round trips
    def to_items(self) -> List["IngestItem"]:
        """Rebuild the IngestItems.  Array/columns payloads come back as
        views over the batch payload — the caller keeps the batch (or its
        shm lease) alive while the items are in use, exactly the
        ``decode_items(copy=False)`` contract."""
        n = len(self)
        labels = [tuple(Label(op, _label_at(col, i))
                        for op, col in zip(self.label_ops, self.label_cols))
                  for i in range(n)]
        metas = self.metas or [{} for _ in range(n)]
        pay, off = self.payload, self.offsets
        datas: List[Any]
        if self.kind == "bytes":
            datas = [pay[int(off[i]):int(off[i + 1])].tobytes()
                     for i in range(n)]
        elif self.kind == "array":
            dt = np.dtype(self.aux["dtype"])
            datas = [pay[int(off[i]):int(off[i + 1])].view(dt)
                     .reshape(self.aux["shapes"][i]) for i in range(n)]
        elif self.kind == "columns":
            cols = self.columns()
            datas = [{k: v[int(off[i]):int(off[i + 1])]
                      for k, v in cols.items()} for i in range(n)]
        else:
            from ..layouts.blocks import SerializedBlock
            datas = [SerializedBlock(self.aux["layouts"][i],
                                     pay[int(off[i]):int(off[i + 1])]
                                     .tobytes(),
                                     dict(self.aux["headers"][i]))
                     for i in range(n)]
        return [IngestItem(datas[i], Granularity(int(self.grans[i])),
                           labels[i], dict(metas[i])) for i in range(n)]

    def select(self, idx: np.ndarray) -> "ColumnarBatch":
        """Order-preserving item selection into a fresh, self-owned batch
        (the vectorized-partition building block)."""
        idx = np.asarray(idx, np.int64)
        n2 = len(idx)
        off = self.offsets
        lens = off[idx + 1] - off[idx] if n2 else np.empty(0, np.int64)
        new_off = np.zeros(n2 + 1, np.int64)
        np.cumsum(lens, out=new_off[1:])
        label_cols = tuple(col[idx] for col in self.label_cols)
        grans = self.grans[idx]
        metas = (None if self.metas is None
                 else [dict(self.metas[int(i)]) for i in idx])
        aux = self.aux
        if self.kind == "columns":
            if n2:
                row_idx = np.concatenate(
                    [np.arange(int(off[i]), int(off[i + 1])) for i in idx])
            else:
                row_idx = np.empty(0, np.int64)
            cols = self.columns()
            total2 = len(row_idx)
            sizes = [np.dtype(d).itemsize * int(np.prod(ts, dtype=np.int64))
                     for _, d, ts in aux["schema"]]
            payload = np.empty(total2 * sum(sizes), np.uint8)
            pos = 0
            for (k, dstr, ts), rowbytes in zip(aux["schema"], sizes):
                size = total2 * rowbytes
                region = payload[pos:pos + size].view(np.dtype(dstr)) \
                    .reshape((total2,) + tuple(ts))
                region[:] = cols[k][row_idx]
                pos += size
        else:
            if n2:
                payload = np.concatenate(
                    [self.payload[int(off[i]):int(off[i + 1])] for i in idx])
            else:
                payload = np.empty(0, np.uint8)
            if self.kind == "array":
                aux = {"dtype": aux["dtype"],
                       "shapes": tuple(aux["shapes"][int(i)] for i in idx)}
            elif self.kind == "block":
                aux = {"layouts": tuple(aux["layouts"][int(i)] for i in idx),
                       "headers": tuple(dict(aux["headers"][int(i)])
                                        for i in idx)}
        return ColumnarBatch(payload, new_off, self.kind, aux,
                             self.label_ops, label_cols, grans, metas)

    # ----------------------------------------------------------------- codec
    def header(self) -> Dict[str, Any]:
        """Everything but the payload buffer, as one picklable dict."""
        return {"kind": self.kind, "offsets": self.offsets, "aux": self.aux,
                "label_ops": self.label_ops, "label_cols": self.label_cols,
                "grans": self.grans, "metas": self.metas,
                "nbytes": self.nbytes}

    @classmethod
    def from_header(cls, header: Dict[str, Any], payload: np.ndarray
                    ) -> "ColumnarBatch":
        if payload.nbytes != header["nbytes"]:
            raise ValueError(
                f"columnar payload is {payload.nbytes} bytes, header "
                f"says {header['nbytes']}")
        return cls(payload, header["offsets"], header["kind"], header["aux"],
                   header["label_ops"], header["label_cols"],
                   header["grans"], header["metas"])


def matches(item: IngestItem, predicates: Dict[str, Any]) -> bool:
    """Label-predicate match used by the dataflow stages (paper Sec. IV-B).

    ``predicates`` maps operator name -> required label value; a predicate
    value may also be a callable for inequality predicates such as the
    paper's ``l_parser > now-1``.
    """
    for op, want in predicates.items():
        have = item.label_value(op)
        if callable(want):
            if not want(have):
                return False
        elif have != want:
            return False
    return True
