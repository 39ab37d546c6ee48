"""STORE-side ingestion operators: locate / upload (+ erasure-coding store ops).

Paper Sec. IV-A: ``STORE s LOCATE USING locator UPLOAD TO target``.  The
locator maps items to *location IDs* (logical placement, Sec. VI-B); upload
binds to the registered storage target and publishes physical blocks with
lineage-encoded names.
"""
from __future__ import annotations

import itertools
from typing import Any, Callable, Dict, Iterable, List, Optional, Sequence

import numpy as np

from .. import tracing
from ..erasure import ReedSolomon
from ..layouts import SerializedBlock, serialize_block
from .items import Granularity, IngestItem
from .operators import BatchFallback, IngestOp, register_op
from .store import DataStore


# --------------------------------------------------------------------- locate
@register_op("locate")
class LocateOp(IngestOp):
    """Assign a logical location ID to each item (paper Sec. VI-B Placement).

    Schemes:
      random    — uniform random location
      roundrobin— cycle locations in order
      disjoint  — replicas of the same logical item get different locations
                  (anti-location; the paper's disjointLocator)
      content   — location = value of an upstream label (content-based placement,
                  e.g. the range-partition id), ``by=<label op>``
      colocate  — same as content but hashing the label value into num_locations
                  (co-location of equal keys across datasets)
    """

    name = "locate"
    batch_capable = True

    def __init__(self, scheme: str = "roundrobin", num_locations: int = 4,
                 by: Optional[str] = None, seed: int = 0, **kw: Any) -> None:
        super().__init__(scheme=scheme, num_locations=num_locations, by=by, seed=seed, **kw)
        self.scheme, self.num_locations, self.by = scheme, num_locations, by
        self._rng = np.random.default_rng(seed)
        self._rr = itertools.count()
        self._replica_seen: Dict[str, int] = {}

    def _loc(self, item: IngestItem) -> int:
        if self.scheme == "random":
            return int(self._rng.integers(self.num_locations))
        if self.scheme == "roundrobin":
            return next(self._rr) % self.num_locations
        if self.scheme == "disjoint":
            key = DataStore._logical_id(item)
            idx = self._replica_seen.get(key, 0)
            self._replica_seen[key] = idx + 1
            return idx % self.num_locations
        if self.scheme == "content":
            return int(item.label_value(self.by, 0)) % self.num_locations
        if self.scheme == "colocate":
            return hash(item.label_value(self.by, 0)) % self.num_locations
        raise ValueError(f"unknown locator scheme {self.scheme!r}")

    def process(self, item: IngestItem) -> Iterable[IngestItem]:
        yield item.with_label(self.name, self._loc(item))


# --------------------------------------------------------------------- erasure
@register_op("erasure")
class ErasureOp(IngestOp):
    """BLOCK -> BLOCK* Reed-Solomon striping (paper Sec. II-D / VI-C2).

    Collects ``k`` data blocks into a stripe and emits them unchanged plus
    ``m`` parity blocks (labelled ``erasure=p<i>``); stripe membership is
    recorded in item.meta for the upload operator.  Different FORMAT stages
    can use different (k, m) — the paper's *flexible erasure coding*.
    """

    name = "erasure"
    granularity_in = Granularity.BLOCK
    granularity_out = Granularity.BLOCK
    # NOT parallel-mode despite being CPU-heavy: stripe accumulation is
    # stateful (self._stripe) — thread-pool processing interleaved items
    # from different stripes (found by benchmarks/bench_recovery)
    cpu_heavy = False
    # the batch path keeps stripes in arrival order, so it IS safe to
    # vectorize: one stacked GF(256) matmul over all of a batch's stripes
    batch_capable = True
    expansion = 1.3

    def __init__(self, k: int = 10, m: int = 3, use_pallas: bool = False, **kw: Any) -> None:
        super().__init__(k=k, m=m, use_pallas=use_pallas, **kw)
        import uuid
        self.k, self.m = k, m
        self.rs = ReedSolomon(k, m, use_pallas=use_pallas)
        self._stripe: List[IngestItem] = []
        self._stripe_idx = 0
        # unique per operator instance: every node clones its own instance,
        # and stripe ids must not collide across nodes in the shared manifest
        self._nonce = uuid.uuid4().hex[:8]
        self.expansion = (k + m) / k

    def _payload(self, item: IngestItem) -> bytes:
        d = item.data
        if isinstance(d, SerializedBlock):
            return d.tobytes()
        if isinstance(d, (bytes, bytearray)):
            return bytes(d)
        if isinstance(d, np.ndarray):
            return d.tobytes()
        raise TypeError(f"erasure needs BLOCK payloads, got {type(d)}")

    def _emit_encoded(self, stripe: List[IngestItem], parity: np.ndarray,
                      pad_len: int) -> Iterable[IngestItem]:
        """Emit one encoded stripe: the data items labelled in place plus the
        ``m`` parity items.  Shared by the scalar and batch paths — the only
        difference between them is who computed ``parity``."""
        stripe_id = f"stripe-{self._nonce}-{self._stripe_idx}"
        self._stripe_idx += 1
        for pos, it in enumerate(stripe):
            out = it.with_label(self.name, f"d{pos}")
            out.meta.update(stripe_id=stripe_id, stripe_pos=pos, is_parity=False,
                            stripe_k=self.k, stripe_m=self.m, stripe_pad=pad_len)
            yield out
        for j in range(self.m):
            pit = IngestItem(parity[j].tobytes(), Granularity.BLOCK,
                             stripe[0].labels, {})
            pit = pit.with_label(self.name, f"p{j}")
            pit.meta.update(stripe_id=stripe_id, stripe_pos=self.k + j, is_parity=True,
                            stripe_k=self.k, stripe_m=self.m, stripe_pad=pad_len)
            yield pit

    def _emit_stripe(self) -> Iterable[IngestItem]:
        payloads = [self._payload(it) for it in self._stripe]
        parity, pad_len = self.rs.encode_payloads(payloads)
        yield from self._emit_encoded(self._stripe, parity, pad_len)
        self._stripe = []

    def process(self, item: IngestItem) -> Iterable[IngestItem]:
        self._stripe.append(item)
        if len(self._stripe) == self.k:
            yield from self._emit_stripe()

    # ------------------------------------------------- batch tier (ISSUE 7)
    def _payload_view(self, item: IngestItem) -> np.ndarray:
        """Flat uint8 view of a BLOCK payload, without a copy where the
        buffer protocol allows (bytes, contiguous arrays)."""
        d = item.data
        if isinstance(d, (bytes, bytearray)):
            return np.frombuffer(d, dtype=np.uint8)
        if isinstance(d, np.ndarray):
            return np.ascontiguousarray(d).view(np.uint8).ravel()
        if isinstance(d, SerializedBlock):
            return np.frombuffer(d.tobytes(), dtype=np.uint8)
        raise BatchFallback(f"erasure batch: unsupported payload {type(d)}")

    def process_batch(self, items: Sequence[IngestItem]) -> List[IngestItem]:
        """Encode S stripes in one stacked GF(256) matmul (``(m x k) @
        (k x sum L_s)``) instead of S per-stripe encodes.  Stripe grouping,
        per-stripe padding, labels, and metadata are byte-identical to the
        scalar iterator path; a trailing partial stripe is drained with
        virtual zero blocks exactly like the scalar ``set_input`` drain."""
        pending = self._stripe + list(items)
        self._stripe = []
        if not pending:
            return []
        stripes = [pending[i:i + self.k]
                   for i in range(0, len(pending), self.k)]
        views = [[self._payload_view(it) for it in s] for s in stripes]
        encoded = self.rs.encode_payload_batch(views)
        if self.rs.use_pallas:
            self.kernel_calls += 1
        out: List[IngestItem] = []
        for stripe, (parity, pad_len) in zip(stripes, encoded):
            out.extend(self._emit_encoded(stripe, parity, pad_len))
        return out

    def finalize(self) -> None:
        # NOTE: trailing partial stripe is encoded with the same (k, m) by
        # zero-padding virtual blocks; handled in set_input drain below.
        super().finalize()

    def set_input(self, items: Sequence[IngestItem]) -> None:  # drain partial stripe
        super().set_input(items)
        base = self._outputs

        def drained():
            yield from base
            if self._stripe:
                yield from self._emit_stripe()

        self._outputs = drained()


# ---------------------------------------------------------------------- upload
@register_op("upload")
class UploadOp(IngestOp):
    """BLOCK -> BLOCK publish into the DataStore target (paper Sec. VIII-A).

    * maps each physical partition/block to a store file named by its lineage,
    * honours the replication already present in the plan (replica labels),
    * maps location IDs to nodes (user map or round-robin over the slaves list),
    * records stripe metadata for erasure-coded blocks.
    """

    name = "upload"
    granularity_in = Granularity.BLOCK
    granularity_out = Granularity.BLOCK
    commit_side = True  # publishes into the DataStore -> store-segment stage
    # store registration is per-item and order-preserving either way; capable
    # so the store stage's first block anchors columnar edges (ISSUE 10)
    batch_capable = True

    def __init__(self, store: Optional[DataStore] = None,
                 location_map: Optional[Dict[int, str]] = None,
                 serialize_default: str = "columnar", **kw: Any) -> None:
        super().__init__(store=store, location_map=location_map,
                         serialize_default=serialize_default, **kw)
        self.store = store
        self.location_map = location_map
        self.serialize_default = serialize_default
        self._replica_counter: Dict[str, int] = {}

    def _node_for(self, item: IngestItem) -> str:
        # location IDs map over the *live* slaves: a node the runtime marked
        # dead takes no new blocks — its location ids flow to the survivors
        # (paper Sec. VI-C1)
        nodes = self.store.live_nodes() or self.store.nodes
        loc = item.label_value("locate")
        if loc is None:
            loc = abs(hash(item.lineage_name()))
        if self.location_map and loc in self.location_map:
            return self.location_map[loc]
        return nodes[int(loc) % len(nodes)]  # round-robin over slaves (Sec. VI-B)

    def process(self, item: IngestItem) -> Iterable[IngestItem]:
        if self.store is None:
            raise RuntimeError("UploadOp has no bound DataStore target")
        if isinstance(item.data, dict):  # un-serialized chunk: apply default layout
            item = IngestItem(serialize_block(item.data, self.serialize_default),
                              Granularity.BLOCK, item.labels, dict(item.meta))
            item = item.with_label("serialize", self.serialize_default)
        logical = DataStore._logical_id(item)
        ridx = self._replica_counter.get(logical, 0)
        self._replica_counter[logical] = ridx + 1
        entry = self.store.put_block(
            item, self._node_for(item),
            logical_id=logical, replica_index=ridx,
            stripe_id=item.meta.get("stripe_id", ""),
            stripe_pos=item.meta.get("stripe_pos", -1),
            is_parity=item.meta.get("is_parity", False),
        )
        yield item.with_label(self.name, entry.node)

    def process_batch(self, items: Sequence[IngestItem]) -> List[IngestItem]:
        """Columnar data plane (ISSUE 10): publish the whole batch through
        ONE ``put_block_batch`` call.  Replica counting, node mapping, and
        registration order are exactly the serial iterator's, so the store
        entries are byte-identical; what changes is the control plane — a
        worker-side store registers N blocks in one coordinator round trip
        instead of N synchronous per-block RPCs.  Stores without bulk
        registration (or with it switched off: the item-at-a-time oracle)
        keep the per-block protocol."""
        if self.store is None:
            raise RuntimeError("UploadOp has no bound DataStore target")
        if not (getattr(self.store, "bulk_registration", False)
                and hasattr(self.store, "put_block_batch")):
            return super().process_batch(items)
        reqs = []
        prepped: List[IngestItem] = []
        for item in items:
            if isinstance(item.data, dict):  # un-serialized chunk
                item = IngestItem(
                    serialize_block(item.data, self.serialize_default),
                    Granularity.BLOCK, item.labels, dict(item.meta))
                item = item.with_label("serialize", self.serialize_default)
            logical = DataStore._logical_id(item)
            ridx = self._replica_counter.get(logical, 0)
            self._replica_counter[logical] = ridx + 1
            prepped.append(item)
            reqs.append({
                "item": item, "node": self._node_for(item),
                "logical_id": logical, "replica_index": ridx,
                "stripe_id": item.meta.get("stripe_id", ""),
                "stripe_pos": item.meta.get("stripe_pos", -1),
                "is_parity": item.meta.get("is_parity", False),
            })
        entries = self.store.put_block_batch(reqs)
        tracing.annotate(bytes=sum(e.nbytes for e in entries))
        return [it.with_label(self.name, e.node)
                for it, e in zip(prepped, entries)]

    def finalize(self) -> None:
        # while an epoch stages, a manifest flush publishes nothing (staged
        # blocks are withheld) — skip the O(store) rewrite; the epoch commit
        # is the publish point.  Batch runs still flush per stage, and
        # snapshot-commit stores (journal_commits=False) keep the manifest
        # continuously current, as before ISSUE 2.
        if self.store is not None and (
                not getattr(self.store, "journal_commits", True)
                or not self.store.staging_epoch_ids()):
            self.store.flush_manifest()
        super().finalize()
