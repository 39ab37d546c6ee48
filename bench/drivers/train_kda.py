"""Fed training traffic for the Kimi-Linear chip share (KDA and latent
attention, then dense or expert FFNs): the production train step of
``launch.train`` on 8k rows that ``BlockFeeder`` reads from a store the LM
ingest plan filled.

The path, the window and the checks are ``drivers/train_moe.py``'s: its
``setup``, ``run_reference`` and ``compare`` run from a copy of that
module given this configuration's program configuration
(``model_config``), its plain reference (``bench/reference/kda_mla_moe.py``)
and one more counter read back with each loss, the state resets the KDA
kernels' segment masks made (``kda_resets``).  Checked, each against its
limit in the configuration: every fed row is a corpus row and every batch
is the reference's; each step's loss; the first gradient by the worst and
the median leaf; each leaf's change over the first steps; the router
biases after them; no assignment to a held expert left uncomputed; and,
over every step, the resets the KDA kernels made against the segment
starts of the rows they were fed (one per start after a row's first
token, per KDA layer).  The step's operations are
``bench/costs/kda_mla_moe_step.py``'s.
"""
from __future__ import annotations

import gc
import time
from typing import Any, Dict, List

import numpy as np

import harness
from costs import kda_mla_moe_step
from reference import kda_mla_moe as ref

_moe = harness.load_module(harness.bench_file(harness.ROOT, "drivers", "train_moe"),
                           "driver_train_moe_kda")
fed = _moe.fed

#: published settings the program implements one way only
FIXED = {"moe_router_activation_func": "sigmoid", "moe_renormalize": True,
         "num_expert_group": 1, "topk_group": 1, "hidden_act": "silu",
         "moe_layer_freq": 1, "mla_use_nope": True, "q_lora_rank": None,
         "tie_word_embeddings": False}


def model_config(cfg: Dict[str, Any]):
    """The program's configuration of this chip's share (fails at once on
    a program without KDA blocks)."""
    from repro.models.config import KDAConfig, MLAConfig, ModelConfig, MoEConfig
    for key, want in FIXED.items():
        if cfg[key] != want:
            raise ValueError(f"{key}={cfg[key]!r}: the program implements {want!r}")
    dep, bal, lin = cfg["deployment"], cfg["balance"], cfg["linear_attn_config"]
    kinds = tuple(ref.layer_kinds(cfg))
    lead = cfg["first_k_dense_replace"]
    return ModelConfig(
        name=cfg["name"], family="moe",
        num_layers=cfg["num_hidden_layers"], d_model=cfg["hidden_size"],
        n_heads=cfg["num_attention_heads"],
        n_kv_heads=cfg["num_key_value_heads"],
        head_dim=cfg["qk_nope_head_dim"] + cfg["qk_rope_head_dim"],
        d_ff=cfg["intermediate_size"], vocab_size=cfg["vocab_size"],
        leading=kinds[:lead], pattern=(), remainder=kinds[lead:],
        mlp_kind="moe",
        mla=MLAConfig(q_lora_rank=0, kv_lora_rank=cfg["kv_lora_rank"],
                      qk_nope_head_dim=cfg["qk_nope_head_dim"],
                      qk_rope_head_dim=cfg["qk_rope_head_dim"],
                      v_head_dim=cfg["v_head_dim"], rope=False),
        kda=KDAConfig(n_heads=lin["num_heads"], head_dim=lin["head_dim"],
                      conv_size=lin["short_conv_kernel_size"],
                      gate_rank=lin["head_dim"]),
        moe=MoEConfig(num_experts=dep["num_experts"],
                      top_k=cfg["num_experts_per_token"],
                      d_ff_expert=cfg["moe_intermediate_size"],
                      num_shared_experts=cfg["num_shared_experts"],
                      router="sigmoid",
                      routed_scaling=cfg["routed_scaling_factor"],
                      dispatch="dropless",
                      held_first=dep["experts_held_first"],
                      held_count=cfg["num_experts"],
                      aux_weight=bal["seq_aux_alpha"],
                      bias_rate=bal["bias_update_speed"]),
        tied_embeddings=False, norm_eps=cfg["rms_norm_eps"], remat_loss=True,
        dtype=cfg["dtype"], param_dtype=cfg["param_dtype"])


# train_moe's setup reads these three names of its module
for _name, _value in (("model_config", model_config), ("ref", ref),
                      ("COUNTERS", _moe.COUNTERS + ("kda_resets",))):
    if not hasattr(_moe, _name):
        raise ImportError(f"drivers/train_moe.py has no {_name!r} to set")
    setattr(_moe, _name, _value)
setup, run_reference, compare = _moe.setup, _moe.run_reference, _moe.compare


def expected_resets(cfg: Dict[str, Any], segments: List[np.ndarray]) -> int:
    """Segment starts after a row's first token, over the batches'
    ``segments`` planes, times the KDA layers."""
    starts = sum(int(np.count_nonzero(s[:, 1:] != s[:, :-1])) for s in segments)
    return starts * ref.layer_kinds(cfg).count("kda")


def run(ctx: harness.Context) -> harness.Outcome:
    """``train_moe.run``'s window and checks, with this model's step count
    and the resets check."""
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
    counters, hosts = loop.counters, loop.hosts
    del params, opt_state, loop, st["params"], st["opt_state"], st["loop"]
    gc.collect()

    rows = fed.reference_rows(st["feed"], ctx.seed, int(traffic["fill_shards"]))
    missing, batches = fed.check_fed(rows, st["fed"] + window_fed)
    differing = (sum(fed.batch_differing(h, b) for h, b in zip(hosts, batches))
                 if missing == 0 else int(np.sum([h["tokens"].size for h in hosts])))
    made = sum(c["kda_resets"] for c in counters)
    want = expected_resets(cfg, [h["segments"] for h in hosts])
    checks = [harness.Check("fed_rows_not_in_corpus", missing, 0),
              harness.Check("batch_values_differing", differing, 0),
              harness.Check("tokens_dropped", sum(c["moe_dropped"] for c in counters), 0),
              harness.Check("kda_resets_missing", abs(made - want), 0)]
    first = batches[:len(st["fed"])] if missing == 0 else []
    if len(first) == len(st["fed"]):
        t = time.perf_counter()
        want_ref = run_reference(cfg, st["start"], first)
        harness.log(f"[check] reference: {time.perf_counter() - t:.3f} s")
        for name, value in compare(cfg, st["prog"], want_ref).items():
            checks.append(harness.Check(name, value, cfg["limits"][name]))
        harness.log(f"[check] program losses {st['prog']['losses']} "
                    f"reference {want_ref['losses']}")
    window_counters = counters[n_check:]
    record.update({
        "host_window_s": elapsed, "steps": attempted,
        "flops_per_step": kda_mla_moe_step.flops_per_step(cfg, st["batch"],
                                                          st["seq_len"]),
        "moe_computed": [c["moe_computed"] for c in window_counters],
        "moe_max_load": [c["moe_max_load"] for c in window_counters],
        "kda_resets": [c["kda_resets"] for c in counters],
        "compiles_in_window": compiles.count,
    })
    harness.log(f"[window] routing: assignments computed here per step "
                f"{record['moe_computed']}, largest held expert's load "
                f"{record['moe_max_load']}; KDA state resets {made:.0f} made, "
                f"{want} segment starts")
    return harness.Outcome(
        setup_s=setup_s,
        end_to_end={"train_tokens_per_s": tokens / elapsed},
        attempted=attempted, failed=failed, checks=checks,
        memory_peak_bytes=peak, record=record)
