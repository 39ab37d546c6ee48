#!/usr/bin/env python3
"""Bring-up run of INGESTBASE's LM device path on a TPU, checked end to end.

    python chip_smoke.py [--seed 0] [--tokens 16777216]   # one chip
    python chip_smoke.py --chips 4                        # data-parallel feed

One chip (the default) drives the main path once through its public entry
points and checks every phase against a plain reference:

1. corpus  -- ``--tokens`` tokens (vocab 49152) in documents of lognormal
   length (median 600, sigma 1.2, capped at 32k), made from ``--seed``;
2. ingest  -- ``build_lm_plan`` with ``pack(use_pallas=True)`` at seq 2048
   and an RS(10,3) ``erasure(use_pallas=True)`` replica, run by the
   ``StreamingRuntimeEngine`` (thread backend) in epochs of ~1M tokens;
3. checks  -- packed rows byte-identical to the scalar ``PackOp``; parity
   equal to the numpy GF(2^8) path on every stripe; every committed block
   read back through ``BlockFeeder`` (row count, token multiset = corpus);
   the pack and erasure programs hold a Mosaic kernel (``tpu_custom_call``);
4. train   -- full-width smollm-135m (random weights from ``--seed``), one
   warm-up step and 5 timed steps of batch 8x2048 on fed batches; loss and
   grad norm must be finite.

``--chips 4`` runs only the cross-chip path: 4 ``BlockFeeder`` tasks feed a
``("data",)`` mesh of 4 devices, each placing its rows on its own device, and
the per-step losses must match the same global batch on one device.

The script refuses to run without a TPU: it exits non-zero, and prints no
result, when ``jax.devices()[0].platform`` is not ``"tpu"`` or when the
``repro`` package is not beside it.  Timings and memory go to earlier lines;
the last line of standard output is one JSON object.
"""
from __future__ import annotations

import argparse
import json
import os
import shutil
import sys
import tempfile
import time
from typing import Any, Dict, List, Optional, Sequence

import numpy as np

ROOT = os.path.dirname(os.path.abspath(__file__))
SRC = os.path.join(ROOT, "src")

VOCAB = 49152
SEQ_LEN = 2048
ROWS_PER_BLOCK = 8        # one (8, 128) int32 tile of rows per packed block
SHARD_TOKENS = 1 << 16    # tokens per source shard (one ingest item)
EPOCH_TOKENS = 1 << 20    # tokens per streaming epoch
RS_K, RS_M = 10, 3
ARCH = "smollm-135m"
BATCH = 8
STEPS = 5
LR = 1e-3
#: per-step loss agreement of the 4-device data-parallel step with one
#: device: two bf16 ulps of relative error (2 * 2^-8)
DP_RTOL = 2.0 ** -7


class SmokeFailure(RuntimeError):
    """A phase produced a result its reference disagrees with."""


def log(msg: str) -> None:
    print(msg, flush=True)


def check(cond: bool, msg: str) -> None:
    if not cond:
        raise SmokeFailure(msg)


# --------------------------------------------------------------- device
def require_tpu(count: int = 1) -> Dict[str, Any]:
    """The device JAX reports; refuses anything but ``count`` or more TPUs."""
    import jax
    devices = jax.devices()
    platform = devices[0].platform
    if platform != "tpu":
        raise SystemExit(f"chip_smoke: no TPU found (JAX platform is "
                         f"{platform!r}); refusing to run")
    if len(devices) < count:
        raise SystemExit(f"chip_smoke: {count} TPU chips needed, JAX finds "
                         f"{len(devices)}")
    return {"platform": platform, "kind": devices[0].device_kind,
            "count": len(devices)}


def peak_bytes(device) -> Optional[int]:
    stats = device.memory_stats() or {}
    return stats.get("peak_bytes_in_use")


# --------------------------------------------------------------- corpus
def make_corpus(seed: int, n_tokens: int, *, vocab: int = VOCAB,
                median: float = 600.0, sigma: float = 1.2,
                cap: int = 32768) -> Dict[str, Any]:
    """``n_tokens`` uniform tokens cut into lognormal-length documents.

    Returns ``{"flat": (n_tokens,) int32, "docs": Columns}``; the documents
    are views of ``flat``, in order, and together hold every token once."""
    rng = np.random.default_rng(seed)
    flat = rng.integers(0, vocab, n_tokens, dtype=np.int32)
    est = int(n_tokens / (median * np.exp(sigma ** 2 / 2))) + 16
    lens = np.empty(0, np.int64)
    while lens.sum() < n_tokens:
        more = rng.lognormal(np.log(median), sigma, est)
        lens = np.concatenate([lens, np.clip(more, 1, cap).astype(np.int64)])
    ends = np.cumsum(lens)
    n_docs = int(np.searchsorted(ends, n_tokens)) + 1
    ends = np.minimum(ends[:n_docs], n_tokens)
    starts = np.concatenate([[0], ends[:-1]])
    docs = np.empty(n_docs, object)
    for i, (s, e) in enumerate(zip(starts, ends)):
        docs[i] = flat[s:e]
    return {"flat": flat,
            "docs": {"tokens": docs,
                     "length": (ends - starts).astype(np.int32),
                     "doc_id": np.arange(n_docs, dtype=np.int64)}}


# --------------------------------------------------------------- ingest
def ingest(corpus: Dict[str, Any], root: str, *, seq_len: int = SEQ_LEN,
           rows_per_block: int = ROWS_PER_BLOCK,
           shard_tokens: int = SHARD_TOKENS, epoch_tokens: int = EPOCH_TOKENS,
           k: int = RS_K, m: int = RS_M, use_pallas: bool = True):
    """Stream the corpus through the LM plan into a fresh one-node store.

    Returns ``(store, shard_items, stream_report)``."""
    from repro.core import DataStore
    from repro.core.streaming import StreamingRuntimeEngine
    from repro.data.feeder import build_lm_plan
    from repro.data.generators import as_file_items

    store = DataStore(root, nodes=["n0"])
    plan = build_lm_plan(store, seq_len=seq_len, rows_per_block=rows_per_block,
                         use_pallas=use_pallas,
                         erasure={"k": k, "m": m, "use_pallas": use_pallas})
    n_tokens = len(corpus["flat"])
    shards = max(1, -(-n_tokens // shard_tokens))
    items = as_file_items(corpus["docs"], shards)
    engine = StreamingRuntimeEngine(
        store, epoch_items=max(1, epoch_tokens // shard_tokens),
        backend="thread")
    try:
        report = engine.run_stream(plan, iter(items))
    finally:
        engine.close()
    check(report.total_items == len(items),
          f"ingest consumed {report.total_items} of {len(items)} shards")
    if use_pallas:
        check(report.kernel_calls() > 0, "no kernel launch counted: the "
              "ingest kernels did not run")
    return store, items, report


def _data_blocks(store) -> List[Any]:
    """Committed packed data blocks (no parity), in packing order."""
    from repro.core import DataAccess
    blocks = DataAccess(store).filter_replica("serialize", "packed").entries
    return sorted(blocks, key=lambda e: dict(e.labels)["pack"])


def check_packed(store, items: Sequence[Any], *, seq_len: int = SEQ_LEN,
                 rows_per_block: int = ROWS_PER_BLOCK) -> int:
    """Stored rows, in packing order, byte-identical to the scalar packer
    run over the same shards.  Returns the row count."""
    from repro.core.ops_format import PackOp
    from repro.layouts import deserialize_block

    fields = ("tokens", "loss_mask", "positions", "segment_ids")
    ref = PackOp(seq_len=seq_len, rows_per_block=rows_per_block)
    want = [r for it in items for r in ref._pack_rows(it)]
    got = {f: [] for f in fields}
    for e in _data_blocks(store):
        cols = deserialize_block(store.read_block(e.block_id),
                                 projection=list(fields))
        for f in fields:
            got[f].append(cols[f])
    check(bool(want), "the scalar packer produced no rows")
    for f in fields:
        stored = np.concatenate(got[f]) if got[f] else np.empty((0, seq_len))
        expect = np.stack([r[f] for r in want])
        check(stored.shape == expect.shape,
              f"{f}: stored {stored.shape} rows, scalar packer {expect.shape}")
        check(stored.dtype == expect.dtype, f"{f}: stored {stored.dtype}, "
              f"scalar packer {expect.dtype}")
        bad = np.argwhere(stored != expect)
        if len(bad):
            r, c = bad[0]
            raise SmokeFailure(
                f"{f}: {len(bad)} values differ from the scalar packer; "
                f"first at row {r} col {c}: stored {stored[r, c]}, "
                f"expected {expect[r, c]}")
    return len(want)


def check_parity(store, *, k: int = RS_K, m: int = RS_M) -> int:
    """Every stripe's stored parity equals the numpy GF(2^8) encode of its
    stored data blocks.  Returns the number of stripes."""
    from repro.erasure.reed_solomon import ReedSolomon
    rs = ReedSolomon(k, m)
    stripes: Dict[str, List[Any]] = {}
    for e in store.blocks():
        if e.stripe_id:
            stripes.setdefault(e.stripe_id, []).append(e)
    check(bool(stripes), "no erasure stripes were committed")
    for sid, members in stripes.items():
        members.sort(key=lambda e: e.stripe_pos)
        data = [store.read_payload(e.block_id) for e in members
                if not e.is_parity]
        parity = [store.read_payload(e.block_id) for e in members
                  if e.is_parity]
        check(len(parity) == m and 0 < len(data) <= k,
              f"{sid}: {len(data)} data and {len(parity)} parity blocks")
        want, _ = rs.encode_payloads(data)
        for j, p in enumerate(parity):
            check(p == want[j].tobytes(), f"{sid}: parity {j} differs from "
                  f"the numpy GF(2^8) path")
    return len(stripes)


def check_feed(store, flat: np.ndarray, *, vocab: int = VOCAB) -> Dict[str, int]:
    """Read every committed block back through ``BlockFeeder``: the rows
    add up and the valid tokens are the corpus, as a multiset."""
    from repro.data.feeder import BlockFeeder
    feeder = BlockFeeder(store)     # one batch per block
    n_blocks = len(feeder)
    check(n_blocks > 0 and n_blocks == len(_data_blocks(store)),
          f"feeder sees {n_blocks} blocks")
    rows = 0
    counts = np.zeros(vocab, np.int64)
    for batch in feeder.batches(n_blocks):
        rows += len(batch["tokens"])
        valid = batch["loss_mask"].astype(bool)
        counts += np.bincount(batch["tokens"][valid], minlength=vocab)
    check(feeder.step == n_blocks, f"feeder stopped at block {feeder.step} "
          f"of {n_blocks}")
    check(np.array_equal(counts, np.bincount(flat, minlength=vocab)),
          "fed tokens differ from the corpus")
    return {"blocks": n_blocks, "rows": rows}


def epoch_kernel_sizes(store, *, rows_per_block: int = ROWS_PER_BLOCK,
                       k: int = RS_K) -> Dict[str, int]:
    """Upper bounds, over the committed epochs, of the rows one pack launch
    packed and the stripe columns one erasure launch encoded (a parity
    block is as long as its stripe's padded width)."""
    blocks: Dict[int, int] = {}
    cols: Dict[int, int] = {}
    for e in store.blocks():
        if e.stripe_pos == k:
            cols[e.epoch] = cols.get(e.epoch, 0) + e.nbytes
        elif not e.is_parity:
            blocks[e.epoch] = blocks.get(e.epoch, 0) + 1
    return {"rows": max(blocks.values()) * rows_per_block,
            "cols": max(cols.values())}


def check_kernels_lowered(n_tokens: int, n_rows: int, n_cols: int, *,
                          seq_len: int = SEQ_LEN, k: int = RS_K,
                          m: int = RS_M) -> Dict[str, float]:
    """Compile the pack and erasure programs at the ingest's sizes, and
    require a Mosaic kernel in each.  Returns compile seconds."""
    import jax
    import jax.numpy as jnp
    from repro.erasure.gf256 import GF256
    from repro.kernels import ops
    i32 = lambda *s: jax.ShapeDtypeStruct(s, jnp.int32)
    u8 = lambda *s: jax.ShapeDtypeStruct(s, jnp.uint8)
    programs = {
        "pack_tokens": ops.pack_tokens.lower(
            i32(ops.bucket(n_tokens)), i32(ops.bucket(n_rows)),
            i32(ops.bucket(n_rows)), seq_len),
        "gf256_matmul": ops.gf256_matmul.lower(
            GF256.cauchy_matrix(m, k), u8(k, ops.bucket(n_cols))),
    }
    out = {}
    for name, lowered in programs.items():
        t0 = time.perf_counter()
        text = lowered.compile().as_text()
        out[name] = time.perf_counter() - t0
        check("tpu_custom_call" in text, f"{name}: no tpu_custom_call in "
              f"the compiled program")
    return out


# ---------------------------------------------------------------- train
def train(cfg, store, *, steps: int = STEPS, batch: int = BATCH,
          seq_len: int = SEQ_LEN, seed: int = 0, lr: float = LR
          ) -> Dict[str, Any]:
    """One warm-up step and ``steps`` timed steps of the production train
    step on one device, on batches the feeder reads from ``store``."""
    import jax
    from repro.data.feeder import BlockFeeder
    from repro.launch.train import build_mesh, make_batch, make_trainer

    trainer = make_trainer(cfg, build_mesh("1x1"), global_batch=batch,
                           seq_len=seq_len, lr=lr)
    params, opt_state = trainer.init_state(seed)
    feeder = BlockFeeder(store, batch_rows=batch, seed=seed)
    batches = [trainer.put_batch(make_batch(raw, seq_len))
               for raw in feeder.batches(steps + 1)]
    check(len(batches) == steps + 1, f"feeder gave {len(batches)} of "
          f"{steps + 1} batches")
    t0 = time.perf_counter()
    step = trainer.step.lower(params, opt_state, batches[0]).compile()
    compile_s = time.perf_counter() - t0
    t0 = time.perf_counter()
    params, opt_state, metrics = step(params, opt_state, batches[0])
    jax.block_until_ready(metrics)
    warmup_s = time.perf_counter() - t0
    losses, norms, step_s = [], [], []
    for b in batches[1:]:
        t0 = time.perf_counter()
        params, opt_state, metrics = step(params, opt_state, b)
        losses.append(float(metrics["loss"]))
        norms.append(float(metrics["grad_norm"]))
        step_s.append(time.perf_counter() - t0)
    check(bool(np.all(np.isfinite(losses + norms))),
          f"non-finite loss or grad norm: {losses} {norms}")
    return {"compile_s": compile_s, "warmup_s": warmup_s, "step_s": step_s,
            "losses": losses, "grad_norms": norms}


def compare_data_parallel(cfg, store, devices: Sequence[Any], *,
                          steps: int = STEPS, batch: int = BATCH,
                          seq_len: int = SEQ_LEN, seed: int = 0,
                          lr: float = LR, rtol: float = DP_RTOL
                          ) -> Dict[str, Any]:
    """``len(devices)`` feeder tasks, one per data-axis slot, each placing
    its rows on its own device of a ``("data",)`` mesh; per-step losses
    must match the same global batch and seed on one device."""
    import jax
    from repro.data.feeder import BlockFeeder
    from repro.launch.mesh import make_mesh
    from repro.launch.train import make_batch, make_trainer

    devices = list(devices)
    n = len(devices)
    check(batch % n == 0, f"batch {batch} does not split over {n} devices")
    rows = batch // n
    tasks = [list(BlockFeeder(store, num_tasks=n, task=t, batch_rows=rows,
                              seed=seed).batches(steps)) for t in range(n)]
    check(all(len(t) == steps for t in tasks),
          f"feeder tasks gave {[len(t) for t in tasks]} of {steps} batches")
    host = [[make_batch(t[s], seq_len) for t in tasks] for s in range(steps)]

    def run(mesh_devices, place):
        mesh = make_mesh((len(mesh_devices),), ("data",), mesh_devices)
        trainer = make_trainer(cfg, mesh, global_batch=batch,
                               seq_len=seq_len, lr=lr)
        params, opt_state = trainer.init_state(seed)
        losses = []
        for per_task in host:
            b = place(trainer, per_task)
            params, opt_state, metrics = trainer.step(params, opt_state, b)
            losses.append(float(metrics["loss"]))
        return losses

    def place_per_task(trainer, per_task):
        """Task t feeds data-axis slot t: its rows go straight to the device
        the mesh puts at that slot (the mesh orders devices along the
        chips' ring, not as listed), and each device must then hold exactly
        its task's share of the global batch."""
        out = {}
        for f, sharding in trainer.batch_sharding.items():
            index = sharding.addressable_devices_indices_map((batch, seq_len))
            slot = {d: i[0].indices(batch)[0] // rows
                    for d, i in index.items()}
            check(set(slot) == set(devices) and
                  sorted(slot.values()) == list(range(n)),
                  f"{f}: batch slots {slot} do not cover {n} devices")
            arr = jax.make_array_from_single_device_arrays(
                (batch, seq_len), sharding,
                [jax.device_put(per_task[t][f], d) for d, t in slot.items()])
            for shard in arr.addressable_shards:
                t = slot[shard.device]
                check(shard.data.shape == (rows, seq_len) and
                      np.array_equal(np.asarray(shard.data), per_task[t][f]),
                      f"{f}: device {shard.device} does not hold task {t}'s "
                      f"{rows} rows")
            out[f] = arr
        return out

    def place_global(trainer, per_task):
        return trainer.put_batch({f: np.concatenate([b[f] for b in per_task])
                                  for f in per_task[0]})

    dp_losses = run(devices, place_per_task)
    one_losses = run(devices[:1], place_global)
    diff = [abs(a - b) / abs(b) for a, b in zip(dp_losses, one_losses)]
    check(bool(np.all(np.isfinite(dp_losses + one_losses))),
          f"non-finite losses: {dp_losses} {one_losses}")
    check(max(diff) <= rtol, f"data-parallel losses {dp_losses} differ from "
          f"one device {one_losses} by {max(diff):.3g} > rtol {rtol:.3g}")
    return {"dp_losses": dp_losses, "one_device_losses": one_losses,
            "max_rel_diff": max(diff), "rows_per_device": rows}


# ----------------------------------------------------------------- main
def _one_chip(args, device: Dict[str, Any], work: str) -> None:
    import jax
    from repro.configs import get_config

    t0 = time.perf_counter()
    corpus = make_corpus(args.seed, args.tokens)
    log(f"[corpus] tokens={args.tokens} docs={len(corpus['docs']['tokens'])} "
        f"seconds={time.perf_counter() - t0:.3f}")

    t0 = time.perf_counter()
    store, items, report = ingest(corpus, os.path.join(work, "store"))
    log(f"[ingest] shards={len(items)} epochs={len(report.epochs)} "
        f"kernel_calls={report.kernel_calls()} "
        f"vectorized_rows={report.vectorized_rows()} "
        f"seconds={time.perf_counter() - t0:.3f}")

    t0 = time.perf_counter()
    n_rows = check_packed(store, items)
    log(f"[check] packed rows byte-identical to the scalar packer: "
        f"rows={n_rows} seconds={time.perf_counter() - t0:.3f}")
    t0 = time.perf_counter()
    n_stripes = check_parity(store)
    log(f"[check] parity equal to the numpy GF(2^8) path on every stripe: "
        f"stripes={n_stripes} seconds={time.perf_counter() - t0:.3f}")
    t0 = time.perf_counter()
    fed = check_feed(store, corpus["flat"])
    log(f"[check] every committed block read back through BlockFeeder: "
        f"blocks={fed['blocks']} rows={fed['rows']} "
        f"seconds={time.perf_counter() - t0:.3f}")
    check(fed["rows"] == n_rows, f"fed {fed['rows']} rows, packed {n_rows}")

    sizes = epoch_kernel_sizes(store)
    compile_k = check_kernels_lowered(EPOCH_TOKENS, sizes["rows"],
                                      sizes["cols"])
    log(f"[check] tpu_custom_call in both ingest kernels: compile_s="
        f"{json.dumps({k: round(v, 3) for k, v in compile_k.items()})}")

    cfg = get_config(ARCH)
    out = train(cfg, store, seed=args.seed)
    log(f"[train] arch={cfg.name} layers={cfg.num_layers} d={cfg.d_model} "
        f"batch={BATCH}x{SEQ_LEN} compile_s={out['compile_s']:.3f} "
        f"warmup_s={out['warmup_s']:.3f} "
        f"step_s={[round(s, 4) for s in out['step_s']]}")
    log(f"[train] losses={out['losses']} grad_norms={out['grad_norms']}")
    log(f"[memory] peak_bytes_in_use={peak_bytes(jax.devices()[0])}")


def _four_chips(args, device: Dict[str, Any], work: str) -> None:
    import jax
    from repro.configs import get_config

    t0 = time.perf_counter()
    corpus = make_corpus(args.seed, 1 << 20)
    store, items, _ = ingest(corpus, os.path.join(work, "store"),
                             use_pallas=False)
    log(f"[setup] host-packed {len(items)} shards into "
        f"{len(_data_blocks(store))} blocks "
        f"seconds={time.perf_counter() - t0:.3f}")
    cfg = get_config(ARCH)
    t0 = time.perf_counter()
    out = compare_data_parallel(cfg, store, jax.devices()[:4], seed=args.seed)
    log(f"[data-parallel] {len(jax.devices()[:4])} feeder tasks -> "
        f"('data',) mesh, rows_per_device={out['rows_per_device']} "
        f"seconds={time.perf_counter() - t0:.3f}")
    log(f"[data-parallel] losses 4 devices={out['dp_losses']}")
    log(f"[data-parallel] losses 1 device ={out['one_device_losses']}")
    log(f"[data-parallel] max relative loss diff={out['max_rel_diff']:.6g} "
        f"(rtol {DP_RTOL:.6g})")
    log(f"[memory] peak_bytes_in_use="
        f"{[peak_bytes(d) for d in jax.devices()[:4]]}")


def main(argv: Optional[Sequence[str]] = None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--tokens", type=int, default=1 << 24)
    args = ap.parse_args(argv)

    if not os.path.isdir(os.path.join(SRC, "repro")):
        raise SystemExit(f"chip_smoke: no repro package under {SRC}")
    sys.path.insert(0, SRC)
    device = require_tpu(args.chips)
    log(f"[device] platform={device['platform']} kind={device['kind']} "
        f"count={device['count']}")
    from repro.launch.compile_cache import enable_compile_cache
    log(f"[device] compile cache: {enable_compile_cache()}")

    work = tempfile.mkdtemp(prefix="chip_smoke_")
    t0 = time.perf_counter()
    try:
        (_four_chips if args.chips == 4 else _one_chip)(args, device, work)
    except SmokeFailure as e:
        log(f"[FAIL] {e}")
        return 1
    finally:
        shutil.rmtree(work, ignore_errors=True)
    log(f"[total] seconds={time.perf_counter() - t0:.3f}")
    device["count"] = args.chips
    print(json.dumps({"ok": True, "device": device}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
