"""Ingest traffic: corpus shards into the LM plan, through the streaming
engine, into the store.

The traffic file says how shards arrive:

- ``"arrivals": "backlog"`` -- a backlog the window cannot drain, offered
  as fast as the engine's bounded queue takes it (closed loop);
- ``"arrivals": "poisson"`` -- open loop: shard ``i`` is due at the i-th
  arrival of a Poisson process of ``rate_shards_per_s``, and is put then,
  whatever the engine is doing.  Each block of 1024 gaps is the same set of
  exponential quantiles in an order drawn from the seed, so every seed
  offers the same load.

Set-up builds the store and the plan, compiles every kernel shape the
window can use, and runs until ``warmup_epochs`` epochs have committed.
The window then lasts ``--seconds``.  Afterwards arrivals stop, the engine
drains, and every shard that arrived is checked for exactly-once commit; a
sample drawn from the seed is compared plane by plane with the plain
packer, and a sample of stripes byte by byte with the plain GF(2^8)
encoder.

Throughput counts whole epochs: the tokens of the epochs that committed
after the warm-up's last commit and by the window's end, over the time
between those two commits.  Latency is per shard, from its due time to its
epoch's commit, over every shard due in the window.
"""
from __future__ import annotations

import os
import threading
import time
from typing import Any, Dict, List, Optional, Tuple

import numpy as np

import corpus as corpus_mod
import harness
from reference import gf256 as ref_gf
from reference import packer as ref_pack


# ---------------------------------------------------------------- arrivals
class Arrivals(threading.Thread):
    """Puts shards into the engine's queues: a backlog, or on a schedule."""

    GAP_BLOCK = 1024

    def __init__(self, queues, seed: int, corpus: Dict[str, Any],
                 traffic: Dict[str, Any]) -> None:
        super().__init__(daemon=True, name="arrivals")
        self.queues, self.seed, self.corpus = queues, seed, corpus
        self.poisson = traffic["arrivals"] == "poisson"
        #: a backlog stops on a whole epoch, so no epoch of the drain is
        #: partial (a partial epoch would need kernel shapes of its own)
        self.align = 1 if self.poisson else int(traffic["epoch_items"])
        self.rate = float(traffic.get("rate_shards_per_s", 0.0))
        self.stop_at: Optional[float] = None      # time.time() of the close
        self.stopped = threading.Event()
        self.due: List[float] = []      # time.time() each shard was due
        self.put_at: List[float] = []   # ... and when its put began
        self.error: Optional[BaseException] = None

    def gaps(self, block: int) -> np.ndarray:
        q = (np.arange(self.GAP_BLOCK) + 0.5) / self.GAP_BLOCK
        rng = np.random.default_rng(np.random.SeedSequence(
            [int(self.seed) & 0xFFFFFFFFFFFFFFFF, 0x6A9, block]))
        return rng.permutation(-np.log1p(-q) / self.rate)

    def run(self) -> None:
        try:
            t = time.time()
            i = 0
            gaps: np.ndarray = np.empty(0)
            while not self.stopped.is_set():
                item = corpus_mod.shard_item(self.seed, i, self.corpus)
                if self.poisson:
                    if i % self.GAP_BLOCK == 0:
                        gaps = self.gaps(i // self.GAP_BLOCK)
                    t += float(gaps[i % self.GAP_BLOCK])
                    if self.stop_at is not None and t >= self.stop_at:
                        break
                    wait = t - time.time()
                    if wait > 0 and self.stopped.wait(wait):
                        break
                    due = t
                else:
                    due = time.time()
                    if (self.stop_at is not None and due >= self.stop_at
                            and i % self.align == 0):
                        break
                began = time.time()
                if not self.queues.put(item):
                    raise RuntimeError(f"shard {i} was refused by the queues")
                self.due.append(due)
                self.put_at.append(began)
                i += 1
        except BaseException as e:      # reported by the driver, not lost
            self.error = e
        finally:
            self.queues.close()


# ------------------------------------------------------------- kernel shapes
def kernel_shapes(cfg: Dict[str, Any], traffic: Dict[str, Any],
                  seed: int) -> Tuple[List[Tuple[int, int]], List[int]]:
    """Every (stream, rows) shape of ``pack_tokens`` and every stripe width
    of ``gf256_matmul`` that an epoch of this traffic can use, from the rows
    and blocks that the seed's first shards pack into."""
    from repro.kernels.ops import bucket
    from repro.layouts import serialize_block
    S, R = cfg["seq_len"], cfg["rows_per_block"]
    k = cfg["erasure"]["k"]
    c = cfg["corpus"]
    rows = [len(ref_pack.plan_rows(corpus_mod.shard_docs(seed, i, c), S))
            for i in range(int(traffic.get("shape_sample_shards", 64)))]
    lo_rows, hi_rows = max(1, min(rows) - 2), max(rows) + 2
    lo_blocks = -(-lo_rows // R)
    hi_blocks = -(-hi_rows // R)
    full = serialize_block({p: np.zeros((R, S), np.int32)
                            for p in ref_pack.PLANES}, "packed")
    width = -(-len(full.tobytes()) // 128) * 128
    n_lo, n_hi = traffic["epoch_shards"]
    packs, stripes = set(), set()
    for n in range(n_lo, n_hi + 1):
        flat = bucket(n * c["shard_tokens"])
        for r in range(n * lo_rows, n * hi_rows + 1):
            packs.add((flat, bucket(r)))
        for s in range(-(-n * lo_blocks // k), -(-n * hi_blocks // k) + 1):
            stripes.add(bucket(s * width))
    return sorted(packs), sorted(stripes)


def warm_kernels(cfg: Dict[str, Any], traffic: Dict[str, Any], seed: int) -> int:
    """Compile (or load from the cache) every kernel shape of the window."""
    import jax
    from repro.core.items import as_device_array
    from repro.erasure.gf256 import GF256
    from repro.kernels import ops
    packs, widths = kernel_shapes(cfg, traffic, seed)
    S = cfg["seq_len"]
    for flat, rows in packs:
        out = ops.pack_tokens(np.zeros(flat, np.int32), np.zeros(rows, np.int32),
                              np.zeros(rows, np.int32), S, pad_id=0)
        jax.block_until_ready(out)
    # the stripe goes to the device as the erasure operator places it
    # (committed to its device), which is part of the compiled program's key
    code = GF256.cauchy_matrix(cfg["erasure"]["m"], cfg["erasure"]["k"])
    for w in widths:
        jax.block_until_ready(ops.gf256_matmul(
            code, as_device_array(np.zeros((cfg["erasure"]["k"], w), np.uint8))))
    return len(packs) + len(widths)


# -------------------------------------------------------------- the store
def read_store(store, k: int):
    """Data blocks by shard, stripes by id, and per-epoch parity widths."""
    shard_blocks: Dict[int, List[Any]] = {}
    stripes: Dict[str, List[Any]] = {}
    parity_cols: Dict[int, int] = {}
    for e in store.blocks():
        if e.stripe_id:
            stripes.setdefault(e.stripe_id, []).append(e)
        if e.is_parity:
            if e.stripe_pos == k:
                parity_cols[e.epoch] = parity_cols.get(e.epoch, 0) + e.nbytes
            continue
        labels = dict((op, v) for op, v in e.labels)
        shard_blocks.setdefault(int(labels["shard"]), []).append(e)
    for v in shard_blocks.values():
        v.sort(key=lambda e: dict((op, x) for op, x in e.labels)["pack"])
    return shard_blocks, stripes, parity_cols


def compare_planes(store, blocks, want: Dict[str, np.ndarray]) -> int:
    """Values of the stored blocks that differ from the reference planes;
    a missing or extra row counts as a whole row of differences."""
    from repro.layouts import deserialize_block
    got = {p: [] for p in ref_pack.PLANES}
    for e in blocks:
        cols = deserialize_block(store.read_block(e.block_id),
                                 projection=list(ref_pack.PLANES))
        for p in ref_pack.PLANES:
            got[p].append(np.asarray(cols[p]))
    bad = 0
    for p in ref_pack.PLANES:
        stored = (np.concatenate(got[p]) if got[p]
                  else np.zeros((0,) + want[p].shape[1:], np.int32))
        n = min(len(stored), len(want[p]))
        bad += int(np.count_nonzero(stored[:n] != want[p][:n]))
        bad += abs(len(stored) - len(want[p])) * want[p].shape[1]
    return bad


def compare_stripe(store, members, k: int, m: int) -> int:
    """Stored parity bytes that differ from the plain encoder's; a missing
    parity block counts as all its bytes."""
    members = sorted(members, key=lambda e: e.stripe_pos)
    data = [store.read_payload(e.block_id) for e in members if not e.is_parity]
    parity = {e.stripe_pos - k: store.read_payload(e.block_id)
              for e in members if e.is_parity}
    want = ref_gf.stripe_parity(data, k, m)
    bad = 0
    for j in range(m):
        if j not in parity:
            bad += want.shape[1]
            continue
        got = np.frombuffer(parity[j], np.uint8)
        n = min(len(got), want.shape[1])
        bad += int(np.count_nonzero(got[:n] != want[j, :n]))
        bad += abs(len(got) - want.shape[1])
    bad += sum(len(p) for j, p in parity.items() if j >= m)
    return bad


# -------------------------------------------------------------------- run
def build(ctx: harness.Context):
    from repro.core import DataStore
    from repro.data.feeder import build_lm_plan
    cfg = ctx.cell.config
    store = DataStore(os.path.join(ctx.work_dir, "store"),
                      nodes=[f"n{i}" for i in range(cfg["store_nodes"])])
    ec = cfg["erasure"]
    plan = build_lm_plan(store, seq_len=cfg["seq_len"],
                         rows_per_block=cfg["rows_per_block"], use_pallas=True,
                         erasure={"k": ec["k"], "m": ec["m"], "use_pallas": True})
    return store, plan


def run(ctx: harness.Context) -> harness.Outcome:
    from repro.core.streaming import IngestQueues, StreamingRuntimeEngine
    cfg, traffic = ctx.cell.config, ctx.cell.traffic
    S, R = cfg["seq_len"], cfg["rows_per_block"]
    k, m = cfg["erasure"]["k"], cfg["erasure"]["m"]
    T = cfg["corpus"]["shard_tokens"]
    t_setup = time.perf_counter()
    store, plan = build(ctx)
    n_shapes = warm_kernels(cfg, traffic, ctx.seed)
    harness.log(f"[setup] kernel shapes warmed: {n_shapes}")
    nodes = list(store.nodes)
    engine = StreamingRuntimeEngine(
        store, epoch_items=cfg["epoch"]["items"],
        epoch_seconds=cfg["epoch"]["seconds"],
        queue_capacity=traffic["queue_capacity"], backend="thread")
    queues = IngestQueues.manual(nodes, traffic["queue_capacity"])
    arrivals = Arrivals(queues, ctx.seed, cfg["corpus"], traffic)
    report: Dict[str, Any] = {}

    def stream() -> None:
        try:
            report["stream"] = engine.run_stream(plan, queues=queues)
        except BaseException as e:
            report["error"] = e
            queues.stop()
            arrivals.stopped.set()

    runner = threading.Thread(target=stream, daemon=True, name="stream")
    record: Dict[str, Any] = {"config": cfg, "traffic": traffic}
    compiles = harness.CompileCounter()
    try:
        arrivals.start()
        runner.start()
        warm = int(traffic["warmup_epochs"])
        while len(store.committed_epoch_ids()) < warm:
            if "error" in report or not runner.is_alive():
                raise RuntimeError(f"the stream ended during warm-up: "
                                   f"{report.get('error')!r}")
            time.sleep(0.005)
        setup_s = time.perf_counter() - t_setup
        t0 = time.time()
        t_end = t0 + ctx.seconds
        with harness.traced(ctx, record), compiles.counting():
            with ctx.spans.span("window"):
                arrivals.stop_at = t_end
                time.sleep(max(0.0, t_end - time.time()))
                # every shard due in the window commits before the check
                runner.join(timeout=ctx.seconds + 120.0)
        if runner.is_alive():
            raise RuntimeError("the stream did not drain within 120 s of "
                               "the window's close")
        arrivals.join(timeout=10.0)
    finally:
        arrivals.stopped.set()
        queues.stop()
        runner.join(timeout=30.0)
        engine.close()
    if "error" in report:
        raise report["error"]
    if arrivals.error is not None:
        raise arrivals.error
    peak = harness.memory_peak_bytes(ctx.devices)
    harness.log(f"[window] compiles inside the window: {compiles.count}")

    # ------------------------------------------------------ what committed
    epochs = sorted(store.epochs.values(), key=lambda e: e.epoch)
    n_put = len(arrivals.due)
    shard_epoch = np.full(n_put, -1, np.int64)
    first = 0
    for e in epochs:
        shard_epoch[first:first + e.n_items] = e.epoch
        first += e.n_items
    commit_at = {e.epoch: e.committed_at for e in epochs}
    before = [e for e in epochs if e.committed_at <= t0]
    inside = [e for e in epochs if t0 < e.committed_at <= t_end]
    if not before or not inside:
        raise RuntimeError(f"no epoch committed inside the window "
                           f"({len(epochs)} epochs in all)")
    span_s = inside[-1].committed_at - before[-1].committed_at
    tokens = sum(e.n_items for e in inside) * T
    due = np.asarray(arrivals.due)
    if traffic["arrivals"] == "poisson":
        measured = np.nonzero((due >= t0) & (due < t_end))[0]
    else:
        ids = {e.epoch for e in inside}
        measured = np.nonzero(np.isin(shard_epoch, list(ids)))[0]
    lat = np.array([commit_at.get(int(shard_epoch[i]), np.inf) - due[i]
                    for i in measured])
    # below capacity every shard commits, so the rate is the offered one:
    # there the tail is the end-to-end metric
    if traffic["arrivals"] == "poisson":
        end_to_end = {"commit_latency_p95_ms": float(np.percentile(lat, 95) * 1000.0)}
    else:
        end_to_end = {"ingest_tokens_per_s": tokens / span_s}

    # ------------------------------------------------------------ the check
    shard_blocks, stripes, parity_cols = read_store(store, k)
    c = cfg["corpus"]
    docs = [corpus_mod.shard_docs(ctx.seed, i, c) for i in range(n_put)]
    rows = [len(ref_pack.plan_rows(d, S)) for d in docs]
    lost = 0
    for i in range(n_put):
        blocks = shard_blocks.get(i, [])
        ok = (len(blocks) == -(-rows[i] // R)
              and {b.epoch for b in blocks} == {int(shard_epoch[i])}
              and int(shard_epoch[i]) in commit_at)
        lost += not ok
    lost += sum(1 for s in shard_blocks if s >= n_put)
    rng = np.random.default_rng(np.random.SeedSequence(
        [int(ctx.seed) & 0xFFFFFFFFFFFFFFFF, 0xC4EC]))
    pick = rng.choice(measured, size=min(len(measured),
                                         int(traffic["check_shards"])),
                      replace=False)
    differing = sum(compare_planes(store, shard_blocks.get(int(i), []),
                                   ref_pack.pack(docs[int(i)], S))
                    for i in pick)
    window_epochs = {int(shard_epoch[i]) for i in measured}
    sids = sorted(s for s, ms in stripes.items()
                  if ms[0].epoch in window_epochs)
    spick = rng.choice(len(sids), size=min(len(sids),
                                           int(traffic["check_stripes"])),
                       replace=False)
    parity_bad = sum(compare_stripe(store, stripes[sids[j]], k, m)
                     for j in spick)
    checks = [harness.Check("shards_not_committed_exactly_once", lost, 0),
              harness.Check("packed_values_differing", differing, 0),
              harness.Check("parity_bytes_differing", parity_bad, 0)]
    failed = int(sum(1 for i in measured
                     if int(shard_epoch[i]) not in commit_at))

    # ---------------------------------------------------- per-layer record
    epoch_rows: Dict[int, int] = {}
    for i in range(n_put):
        epoch_rows[int(shard_epoch[i])] = (epoch_rows.get(int(shard_epoch[i]), 0)
                                           + rows[i])
    sreport = report["stream"]
    latency = {e.epoch: e.commit_latency_s for e in sreport.epochs}
    record.update({
        "epochs": [{"epoch": e.epoch, "committed_at": e.committed_at,
                    "items": e.n_items, "tokens": e.n_items * T,
                    "rows": epoch_rows.get(e.epoch, 0),
                    "parity_cols": parity_cols.get(e.epoch, 0),
                    "commit_latency_s": latency.get(e.epoch)}
                   for e in epochs],
        "window_epochs": [e.epoch for e in inside],
        "lags_s": [a - d for a, d in zip(arrivals.put_at, arrivals.due)
                   if t0 <= d < t_end],
        "compiles_in_window": compiles.count,
        "seq_len": S, "k": k, "m": m,
    })
    harness.log(f"[window] epochs={len(inside)} tokens={tokens} "
                f"span_s={span_s:.4f} shards_put={n_put} "
                f"shards_measured={len(measured)}")
    return harness.Outcome(setup_s=setup_s, end_to_end=end_to_end,
                           attempted=int(len(measured)), failed=failed,
                           checks=checks, memory_peak_bytes=peak,
                           record=record)
