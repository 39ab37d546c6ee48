"""Fed training traffic: the production train step on batches that
``BlockFeeder`` reads from a store the LM ingest plan filled.

Set-up fills the store with ``fill_shards`` corpus shards through the
configuration's feed (``build_lm_plan`` with both kernels on, the streaming
engine, one store node), makes the weights from the seed on the device in
one jitted call, builds the train step (``launch.train.make_trainer``) and
drives that same step through its first ``check_steps`` steps along the
window's own path: ``BlockFeeder.batches`` -> ``make_batch`` ->
``Trainer.put_batch`` -> ``Trainer.step`` -> the loss read back.  The
window then repeats that path for at least ``--seconds`` and up to the end
of a whole number of passes over the store (the feeder wraps around it), so
that every window trains on the same rows, however the seed orders them.

Afterwards the program's state is freed and the plain reference
(``bench/reference/decoder.py``) trains the same first steps from the same
seed on the same rows, packed by the plain packer.  Compared, each against
its limit: every fed row is a row of the corpus; each step's loss; the
norm of the first gradient as the optimizer holds it, per leaf; and the
norm of each leaf's change over the first steps.
"""
from __future__ import annotations

import gc
import math
import os
import time
from functools import partial
from typing import Any, Dict, List, Tuple

import numpy as np

import corpus as corpus_mod
import harness
from reference import decoder as ref
from reference import packer as ref_pack

FIELDS = ref_pack.PLANES


def model_config(cfg: Dict[str, Any]):
    from repro.models.config import ModelConfig
    return ModelConfig(
        name=cfg["name"], family="dense",
        num_layers=cfg["num_hidden_layers"], d_model=cfg["hidden_size"],
        n_heads=cfg["num_attention_heads"],
        n_kv_heads=cfg["num_key_value_heads"], head_dim=cfg["head_dim"],
        d_ff=cfg["intermediate_size"], vocab_size=cfg["vocab_size"],
        pattern=("attn",), mlp_kind="swiglu",
        tied_embeddings=cfg["tie_word_embeddings"],
        rope_theta=cfg["rope_theta"], norm_eps=cfg["rms_norm_eps"],
        dtype=cfg["dtype"], param_dtype=cfg["param_dtype"])


def fill_store(work_dir: str, feed: Dict[str, Any], seed: int, shards: int):
    """Ingest ``shards`` corpus shards through the feed's plan."""
    from repro.core import DataStore
    from repro.core.streaming import StreamingRuntimeEngine
    from repro.data.feeder import build_lm_plan
    store = DataStore(os.path.join(work_dir, "store"), nodes=["n0"])
    ec = feed["erasure"]
    plan = build_lm_plan(store, seq_len=feed["seq_len"],
                         rows_per_block=feed["rows_per_block"], use_pallas=True,
                         erasure={"k": ec["k"], "m": ec["m"], "use_pallas": True})
    engine = StreamingRuntimeEngine(store, epoch_items=feed["epoch"]["items"],
                                    backend="thread")
    try:
        engine.run_stream(plan, iter([corpus_mod.shard_item(seed, i, feed["corpus"])
                                      for i in range(shards)]))
    finally:
        engine.close()
    return store


def row_key(planes: Dict[str, np.ndarray], r: int) -> bytes:
    return b"".join(np.ascontiguousarray(planes[p][r], np.int32).tobytes()
                    for p in FIELDS)


class Loop:
    """The window's path, one step per call, each call into the program in
    a span of its own."""

    def __init__(self, trainer, batches, seq_len: int, spans: harness.Spans):
        from repro.launch import train as program
        self.program = program
        self.trainer, self.batches = trainer, batches
        self.seq_len, self.spans = seq_len, spans
        self.hosts: List[Dict[str, np.ndarray]] = []   # every batch the step took

    def __call__(self, params, opt_state):
        sp = self.spans.span
        with sp("feeder.next"):
            raw = next(self.batches)
        with sp("make_batch"):
            host = self.program.make_batch(raw, self.seq_len)
        self.hosts.append(host)
        with sp("put_batch"):
            dev = self.trainer.put_batch(host)
        with sp("step.dispatch"):
            params, opt_state, metrics = self.trainer.step(params, opt_state, dev)
        with sp("loss.fetch"):
            loss = float(metrics["loss"])
        return params, opt_state, loss, raw


def _leaf_gaps(prog: np.ndarray, want: np.ndarray, keep: np.ndarray) -> np.ndarray:
    """Each leaf's gap between two per-leaf norms, against the larger of the
    reference leaf's norm and the median leaf's."""
    med = float(np.median(want[keep]))
    return np.abs(prog[keep] - want[keep]) / np.maximum(want[keep], med)


def compare(cfg: Dict[str, Any], prog: Dict[str, Any], want: Dict[str, Any]
            ) -> Dict[str, float]:
    """The numbers compared: the loss of each step; the first gradient by
    the worst leaf and by the median leaf (steady from seed to seed: the
    worst leaf alone does not tell an fp8 step from a sound one); each
    leaf's change by the worst leaf.  Leaves whose reference gradient is
    under ``ignore_leaf_below`` of the median leaf's move by round-off
    alone and are left out."""
    g_ref = want["grad_norms"]
    keep = g_ref >= cfg["limits"]["ignore_leaf_below"] * np.median(g_ref)
    grad = _leaf_gaps(prog["grad_norms"], g_ref, keep)
    return {
        "loss_gap": float(max(abs(a - b) / abs(b)
                              for a, b in zip(prog["losses"], want["losses"]))),
        "grad_gap": float(np.max(grad)),
        "grad_gap_median": float(np.median(grad)),
        "change_gap": float(np.max(_leaf_gaps(prog["change_norms"],
                                              want["change_norms"], keep))),
    }


def run_reference(cfg: Dict[str, Any], words: np.ndarray,
                  batches: List[Dict[str, np.ndarray]], quantize=None
                  ) -> Dict[str, Any]:
    """The reference's readings over ``batches`` from the seed's weights."""
    import jax
    import jax.numpy as jnp
    init = jax.jit(partial(ref.init_params, cfg))
    model = ref.Reference(cfg, quantize=quantize)
    losses, first, final = model.train(init(words), batches)
    start = init(words)
    change = ref.leaf_norms(jax.tree.map(
        lambda a, b: a.astype(jnp.float32) - b.astype(jnp.float32), final, start))
    return {"losses": losses, "grad_norms": first, "change_norms": change}


def setup(ctx: harness.Context) -> Dict[str, Any]:
    """Everything before the window: store, weights, step, the first steps
    and the program's readings of them."""
    import jax
    import jax.numpy as jnp
    from repro.data.feeder import BlockFeeder
    from repro.launch import train as program
    from repro.models.params import abstract_params
    cfg, traffic = ctx.cell.config, ctx.cell.traffic
    feed = harness.load_json(os.path.join(
        ctx.cell.root, "bench", "configs", cfg["feed_config"] + ".json"))
    S, B = feed["seq_len"], int(traffic["rows_per_chip"]) * ctx.cell.chips
    t = time.perf_counter()
    store = fill_store(ctx.work_dir, feed, ctx.seed, int(traffic["fill_shards"]))
    harness.log(f"[setup] store filled: {time.perf_counter() - t:.3f} s")
    t = time.perf_counter()
    trainer = program.make_trainer(model_config(cfg), program.build_mesh("1x1"),
                           global_batch=B, seq_len=S, lr=cfg["optimizer"]["lr"])
    want = jax.tree.map(lambda d: (d.shape, str(d.dtype)),
                        abstract_params(trainer.pdefs))
    have = jax.tree.map(lambda s: (s[0], s[1]), ref.param_shapes(cfg),
                        is_leaf=lambda x: isinstance(x, tuple) and len(x) == 3)
    if want != have:
        raise RuntimeError(f"the program's parameters {want} are not the "
                           f"configuration's {have}")
    words = ref.seed_words(ctx.seed)
    params = jax.jit(partial(ref.init_params, cfg),
                     out_shardings=trainer.params_sharding)(words)
    opt_state = jax.jit(trainer.init_opt,
                        out_shardings=trainer.opt_sharding)(params)
    feeder = BlockFeeder(store, batch_rows=B, seed=ctx.seed)
    loop = Loop(trainer, feeder.batches(1 << 40), S, ctx.spans)
    b1 = cfg["optimizer"]["b1"]
    grad_norms = jax.jit(lambda mu: [jnp.sqrt(jnp.sum(jnp.square(x / (1 - b1))))
                                     for x in jax.tree.leaves(mu)])
    change = jax.jit(lambda p, w: [
        jnp.sqrt(jnp.sum(jnp.square(a.astype(jnp.float32) - b.astype(jnp.float32))))
        for a, b in zip(jax.tree.leaves(p),
                        jax.tree.leaves(ref.init_params(cfg, w)))])
    losses, fed = [], []
    prog: Dict[str, Any] = {}
    for i in range(int(traffic["check_steps"])):
        params, opt_state, loss, raw = loop(params, opt_state)
        losses.append(loss)
        fed.append(raw)
        if i == 0:
            prog["grad_norms"] = np.array([float(x) for x in
                                           grad_norms(opt_state["mu"])])
    prog["change_norms"] = np.array([float(x) for x in change(params, words)])
    prog["losses"] = losses
    harness.log(f"[setup] weights, step and {len(losses)} first steps: "
                f"{time.perf_counter() - t:.3f} s")
    rows = sum(len(ref_pack.plan_rows(corpus_mod.shard_docs(ctx.seed, i, feed["corpus"]), S))
               for i in range(int(traffic["fill_shards"])))
    cycle = math.lcm(rows, B) // B
    if cycle > 64:
        raise ValueError(f"a pass over the store is {rows} rows, {cycle} steps "
                         f"of {B}: choose fill_shards to make whole batches")
    return {"store": store, "feed": feed, "trainer": trainer, "loop": loop,
            "cycle_steps": cycle,
            "params": params, "opt_state": opt_state, "words": words,
            "fed": fed, "prog": prog, "batch": B, "seq_len": S}


def reference_rows(feed: Dict[str, Any], seed: int, shards: int
                   ) -> Dict[bytes, Dict[str, np.ndarray]]:
    """Every row of the filled shards, packed by the plain packer."""
    rows: Dict[bytes, Dict[str, np.ndarray]] = {}
    for i in range(shards):
        planes = ref_pack.pack(corpus_mod.shard_docs(seed, i, feed["corpus"]),
                               feed["seq_len"])
        for r in range(len(planes["tokens"])):
            rows[row_key(planes, r)] = {p: planes[p][r] for p in FIELDS}
    return rows


def batch_differing(host: Dict[str, np.ndarray],
                    planes: Dict[str, np.ndarray]) -> int:
    """Values of the batch the program's step took that differ from the
    batch the reference makes of the same rows: tokens, next-token labels
    (-1 where no loss), segment ids and positions."""
    labels, valid = ref.targets(planes["tokens"], planes["loss_mask"],
                                planes["segment_ids"])
    want = {"tokens": planes["tokens"], "labels": np.where(valid, labels, -1),
            "segments": planes["segment_ids"], "positions": planes["positions"]}
    return int(sum(np.count_nonzero(np.asarray(host[f]) != want[f])
                   for f in want))


def check_fed(rows, fed: List[Dict[str, np.ndarray]]
              ) -> Tuple[int, List[Dict[str, np.ndarray]]]:
    """Fed rows that are no row of the corpus, and the reference's own rows
    for each fed batch (where every row is found)."""
    missing = 0
    batches = []
    for raw in fed:
        found = []
        for r in range(len(raw["tokens"])):
            hit = rows.get(row_key(raw, r))
            missing += hit is None
            found.append(hit)
        if all(h is not None for h in found):
            batches.append({p: np.stack([h[p] for h in found]) for p in FIELDS})
    return missing, batches


def run(ctx: harness.Context) -> harness.Outcome:
    cfg, traffic = ctx.cell.config, ctx.cell.traffic
    t_setup = time.perf_counter()
    st = setup(ctx)
    setup_s = time.perf_counter() - t_setup
    loop, params, opt_state = st["loop"], st["params"], st["opt_state"]
    st["hosts"] = loop.hosts
    record: Dict[str, Any] = {"config": cfg, "chips": ctx.cell.chips,
                              "spans": ctx.spans}
    tokens = attempted = failed = 0
    window_fed: List[Dict[str, np.ndarray]] = []
    compiles = harness.CompileCounter()
    with harness.traced(ctx, record), compiles.counting():
        with ctx.spans.span("window"):
            t0 = time.perf_counter()
            while True:
                params, opt_state, loss, raw = loop(params, opt_state)
                attempted += 1
                failed += not np.isfinite(loss)
                tokens += int(np.sum(raw["loss_mask"]))
                window_fed.append(raw)
                elapsed = time.perf_counter() - t0
                if elapsed >= ctx.seconds and attempted % st["cycle_steps"] == 0:
                    break
    harness.log(f"[window] steps={attempted} seconds={elapsed:.4f} "
                f"compiles inside the window: {compiles.count}")
    peak = harness.memory_peak_bytes(ctx.devices)
    del params, opt_state, loop, st["params"], st["opt_state"], st["loop"]
    gc.collect()

    rows = reference_rows(st["feed"], ctx.seed, int(traffic["fill_shards"]))
    missing, batches = check_fed(rows, st["fed"] + window_fed)
    hosts = st["hosts"]
    differing = (sum(batch_differing(h, b) for h, b in zip(hosts, batches))
                 if missing == 0 else int(np.sum([h["tokens"].size for h in hosts])))
    checks = [harness.Check("fed_rows_not_in_corpus", missing, 0),
              harness.Check("batch_values_differing", differing, 0)]
    first = batches[:len(st["fed"])] if missing == 0 else []
    if len(first) == len(st["fed"]):
        t = time.perf_counter()
        want = run_reference(cfg, st["words"], first)
        harness.log(f"[check] reference: {time.perf_counter() - t:.3f} s")
        for name, value in compare(cfg, st["prog"], want).items():
            checks.append(harness.Check(name, value, cfg["limits"][name]))
        harness.log(f"[check] program losses {st['prog']['losses']} "
                    f"reference {want['losses']}")
    from costs import decoder_step
    record.update({
        "host_window_s": elapsed, "steps": attempted,
        "flops_per_step": decoder_step.flops_per_step(cfg, st["batch"],
                                                      st["seq_len"]),
        "compiles_in_window": compiles.count,
    })
    return harness.Outcome(
        setup_s=setup_s,
        end_to_end={"train_tokens_per_s": tokens / elapsed},
        attempted=attempted, failed=failed, checks=checks,
        memory_peak_bytes=peak, record=record)
