"""Fed training traffic for a latent-attention expert model on one chip's
share: the production train step of ``launch.train`` on 8k rows that
``BlockFeeder`` reads from a store the LM ingest plan filled.

The path and the check are ``drivers/train.py``'s, whose store filling,
window loop and comparisons this driver imports: set-up fills the store
with ``fill_shards`` shards through the configuration's own feed
(``feed``: both ingest kernels on), makes the weights from the seed on the
device, builds the step (``launch.train.make_trainer``) and drives it
through ``check_steps`` steps along the window's path; the window repeats
that path for at least ``--seconds`` and to the end of a whole pass over
the store.  The step's routing counters are read back with its loss.

Afterwards the plain reference (``bench/reference/mla_moe.py``) trains the
same first steps from the same seed on the same rows.  Compared, each
against its limit in the configuration: every fed row is a corpus row and
every batch is the reference's; each step's loss; the first gradient per
leaf, by the worst and the median leaf; each leaf's change over the first
steps; the router biases after them (entries differing from the
reference's); and the assignments to held experts left uncomputed over
every step (the layer is dropless: 0).
"""
from __future__ import annotations

import gc
import math
import time
from functools import partial
from typing import Any, Dict, List

import numpy as np

import corpus as corpus_mod
import harness
from reference import decoder
from reference import mla_moe as ref
from reference import packer as ref_pack

fed = harness.load_module(harness.bench_file(harness.ROOT, "drivers", "train"),
                          "driver_train")

#: published settings the program implements one way only
FIXED = {"scoring_func": "sigmoid", "topk_method": "noaux_tc", "n_group": 1,
         "topk_group": 1, "norm_topk_prob": True, "hidden_act": "silu",
         "moe_layer_freq": 1, "attention_bias": False,
         "tie_word_embeddings": False, "q_lora_rank": None}
COUNTERS = ("moe_computed", "moe_max_load", "moe_dropped")


def model_config(cfg: Dict[str, Any]):
    """The program's configuration of this chip's share (fails at once on
    a program without latent attention or held-expert layers)."""
    from repro.models.config import MLAConfig, ModelConfig, MoEConfig
    for key, want in FIXED.items():
        if cfg[key] != want:
            raise ValueError(f"{key}={cfg[key]!r}: the program implements {want!r}")
    dep, bal = cfg["deployment"], cfg["balance"]
    lead = cfg["first_k_dense_replace"]
    return ModelConfig(
        name=cfg["name"], family="moe",
        num_layers=cfg["num_hidden_layers"], d_model=cfg["hidden_size"],
        n_heads=cfg["num_attention_heads"],
        n_kv_heads=cfg["num_key_value_heads"],
        head_dim=cfg["qk_nope_head_dim"] + cfg["qk_rope_head_dim"],
        d_ff=cfg["intermediate_size"], vocab_size=cfg["vocab_size"],
        leading=("attn",) * lead,
        pattern=("attn",), mlp_kind="moe",
        mla=MLAConfig(q_lora_rank=0, kv_lora_rank=cfg["kv_lora_rank"],
                      qk_nope_head_dim=cfg["qk_nope_head_dim"],
                      qk_rope_head_dim=cfg["qk_rope_head_dim"],
                      v_head_dim=cfg["v_head_dim"]),
        moe=MoEConfig(num_experts=dep["n_routed_experts"],
                      top_k=cfg["num_experts_per_tok"],
                      d_ff_expert=cfg["moe_intermediate_size"],
                      num_shared_experts=cfg["n_shared_experts"],
                      router="sigmoid",
                      routed_scaling=cfg["routed_scaling_factor"],
                      dispatch="dropless",
                      held_first=dep["experts_held_first"],
                      held_count=cfg["n_routed_experts"],
                      aux_weight=bal["seq_aux_alpha"],
                      bias_rate=bal["bias_update_speed"]),
        tied_embeddings=False, rope_theta=float(cfg["rope_theta"]),
        norm_eps=cfg["rms_norm_eps"], remat_loss=True,
        dtype=cfg["dtype"], param_dtype=cfg["param_dtype"])


class Loop(fed.Loop):
    """``drivers/train.Loop``, reading the step's routing counters back
    with its loss."""

    def __init__(self, *a, **kw):
        super().__init__(*a, **kw)
        self.counters: List[Dict[str, float]] = []

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
            self.counters.append({k: float(metrics[k]) for k in COUNTERS})
        return params, opt_state, loss, raw


def bias_leaves(tree) -> List[np.ndarray]:
    import jax
    return [np.asarray(x, np.float32) for k, x in
            jax.tree_util.tree_flatten_with_path(tree)[0]
            if jax.tree_util.keystr(k).endswith("['router_bias']")]


def setup(ctx: harness.Context) -> Dict[str, Any]:
    """Everything before the window: store, weights, step, the first steps
    and the program's readings of them."""
    import jax
    import jax.numpy as jnp
    from repro.data.feeder import BlockFeeder
    from repro.launch import train as program
    from repro.models.params import abstract_params
    cfg, traffic = ctx.cell.config, ctx.cell.traffic
    mcfg = model_config(cfg)
    feed = cfg["feed"]
    S, B = feed["seq_len"], int(traffic["rows_per_chip"]) * ctx.cell.chips
    shards = int(traffic["fill_shards"])
    rows = sum(len(ref_pack.plan_rows(corpus_mod.shard_docs(ctx.seed, i, feed["corpus"]), S))
               for i in range(shards))
    cycle = math.lcm(rows, B) // B
    if cycle > 64:
        raise ValueError(f"a pass over the store is {rows} rows, {cycle} steps "
                         f"of {B}: choose fill_shards to make whole batches")
    t = time.perf_counter()
    store = fed.fill_store(ctx.work_dir, feed, ctx.seed, shards)
    harness.log(f"[setup] store filled: {time.perf_counter() - t:.3f} s")
    t = time.perf_counter()
    trainer = program.make_trainer(mcfg, program.build_mesh("1x1"),
                                   global_batch=B, seq_len=S,
                                   lr=cfg["optimizer"]["lr"])
    want = jax.tree.map(lambda d: (d.shape, str(d.dtype)),
                        abstract_params(trainer.pdefs))
    have = jax.tree.map(lambda s: (s[0], s[1]), ref.param_shapes(cfg),
                        is_leaf=decoder._is_spec)
    if want != have:
        raise RuntimeError(f"the program's parameters {want} are not the "
                           f"configuration's {have}")
    words = decoder.seed_words(ctx.seed)
    params = jax.jit(partial(ref.init_params, cfg),
                     out_shardings=trainer.params_sharding)(words)
    # the one set of initial weights that both the changes and the
    # reference start from (weights drawn again in another program may
    # round a few entries the other way, and at this learning rate a step
    # moves an entry by less than one bf16 rounding step)
    start = jax.device_get(params)
    opt_state = jax.jit(trainer.init_opt,
                        out_shardings=trainer.opt_sharding)(params)
    feeder = BlockFeeder(store, batch_rows=B, seed=ctx.seed)
    loop = Loop(trainer, feeder.batches(1 << 40), S, ctx.spans)
    b1 = cfg["optimizer"]["b1"]
    grad_norms = jax.jit(lambda mu: [jnp.sqrt(jnp.sum(jnp.square(x / (1 - b1))))
                                     for x in jax.tree.leaves(mu)])
    change = jax.jit(lambda p, s: [
        jnp.sqrt(jnp.sum(jnp.square(a.astype(jnp.float32) - b.astype(jnp.float32))))
        for a, b in zip(jax.tree.leaves(p), jax.tree.leaves(s))])
    losses, first_rows = [], []
    prog: Dict[str, Any] = {}
    for i in range(int(traffic["check_steps"])):
        params, opt_state, loss, raw = loop(params, opt_state)
        losses.append(loss)
        first_rows.append(raw)
        if i == 0:
            prog["grad_norms"] = np.array([float(x) for x in
                                           grad_norms(opt_state["mu"])])
    prog["change_norms"] = np.array([float(x) for x in change(params, start)])
    prog["losses"] = losses
    prog["biases"] = bias_leaves(params)
    harness.log(f"[setup] weights, step and {len(losses)} first steps: "
                f"{time.perf_counter() - t:.3f} s; routing {loop.counters}")
    return {"store": store, "feed": feed, "trainer": trainer, "loop": loop,
            "cycle_steps": cycle, "params": params, "opt_state": opt_state,
            "start": start, "fed": first_rows, "prog": prog, "batch": B,
            "seq_len": S}


def run_reference(cfg: Dict[str, Any], start, batches: List[Dict[str, np.ndarray]],
                  quantize=None) -> Dict[str, Any]:
    """The reference's readings over ``batches`` from the initial weights
    ``start`` (host arrays)."""
    import jax
    import jax.numpy as jnp
    model = ref.Reference(cfg, quantize=quantize)
    losses, first, final = model.train(jax.device_put(start), batches)
    change = decoder.leaf_norms(jax.tree.map(
        lambda a, b: a.astype(jnp.float32) - b.astype(jnp.float32),
        final, jax.device_put(start)))
    return {"losses": losses, "grad_norms": first, "change_norms": change,
            "biases": bias_leaves(final)}


def compare(cfg: Dict[str, Any], prog: Dict[str, Any], want: Dict[str, Any]
            ) -> Dict[str, float]:
    """``drivers/train.compare``, and the router biases after the first
    steps: entries that differ from the reference's."""
    out = fed.compare(cfg, prog, want)
    out["bias_entries_differing"] = float(sum(
        np.count_nonzero(a != b) for a, b in zip(prog["biases"], want["biases"])))
    return out


def run(ctx: harness.Context) -> harness.Outcome:
    cfg, traffic = ctx.cell.config, ctx.cell.traffic
    t_setup = time.perf_counter()
    st = setup(ctx)
    setup_s = time.perf_counter() - t_setup
    loop, params, opt_state = st["loop"], st["params"], st["opt_state"]
    n_check = len(loop.counters)
    record: Dict[str, Any] = {"config": cfg, "chips": ctx.cell.chips,
                              "spans": ctx.spans, "batch": st["batch"],
                              "seq_len": st["seq_len"]}
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
    counters = loop.counters
    hosts = loop.hosts
    del params, opt_state, loop, st["params"], st["opt_state"], st["loop"]
    gc.collect()

    rows = fed.reference_rows(st["feed"], ctx.seed, int(traffic["fill_shards"]))
    missing, batches = fed.check_fed(rows, st["fed"] + window_fed)
    differing = (sum(fed.batch_differing(h, b) for h, b in zip(hosts, batches))
                 if missing == 0 else int(np.sum([h["tokens"].size for h in hosts])))
    dropped = sum(c["moe_dropped"] for c in counters)
    checks = [harness.Check("fed_rows_not_in_corpus", missing, 0),
              harness.Check("batch_values_differing", differing, 0),
              harness.Check("tokens_dropped", dropped, 0)]
    first = batches[:len(st["fed"])] if missing == 0 else []
    if len(first) == len(st["fed"]):
        t = time.perf_counter()
        want = run_reference(cfg, st["start"], first)
        harness.log(f"[check] reference: {time.perf_counter() - t:.3f} s")
        for name, value in compare(cfg, st["prog"], want).items():
            checks.append(harness.Check(name, value, cfg["limits"][name]))
        harness.log(f"[check] program losses {st['prog']['losses']} "
                    f"reference {want['losses']}")
    from costs import mla_moe_step
    window_counters = counters[n_check:]
    record.update({
        "host_window_s": elapsed, "steps": attempted,
        "flops_per_step": mla_moe_step.flops_per_step(cfg, st["batch"],
                                                      st["seq_len"]),
        "moe_computed": [c["moe_computed"] for c in window_counters],
        "moe_max_load": [c["moe_max_load"] for c in window_counters],
        "compiles_in_window": compiles.count,
    })
    harness.log(f"[window] routing: assignments computed here per step "
                f"{record['moe_computed']}, largest held expert's load "
                f"{record['moe_max_load']}, dropped {dropped}")
    return harness.Outcome(
        setup_s=setup_s,
        end_to_end={"train_tokens_per_s": tokens / elapsed},
        attempted=attempted, failed=failed, checks=checks,
        memory_peak_bytes=peak, record=record)
