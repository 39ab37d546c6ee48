"""CPU sizes of the cells added beside ``bench/tests/tiny.py``'s: its
cut-down copy of the benchmark looks each configuration and driver up by
name, so every configuration and driver needs its entry there."""
import os
import sys

BENCH = os.path.dirname(os.path.abspath(__file__))
for _p in (BENCH, os.path.join(BENCH, "tests")):
    if _p not in sys.path:
        sys.path.insert(0, _p)

import tiny  # noqa: E402

tiny.CONFIG.setdefault("moonlight-16b-a3b.ep8", {
    "hidden_size": 64, "intermediate_size": 96, "num_attention_heads": 4,
    "num_key_value_heads": 4, "kv_lora_rank": 32, "qk_nope_head_dim": 16,
    "qk_rope_head_dim": 8, "v_head_dim": 16, "moe_intermediate_size": 32,
    "n_routed_experts": 2, "num_experts_per_tok": 3, "num_hidden_layers": 3,
    "vocab_size": 256, "dtype": "float32", "param_dtype": "float32",
    "initializer_range": 0.02,
    "deployment": {"n_routed_experts": 8, "experts_held_first": 2},
    "feed": {"seq_len": 64, "vocab_size": 256,
             "corpus": {"shard_tokens": 1024, "vocab_size": 256,
                        "doc_len_median": 20, "doc_len_cap": 500}}})
tiny.TRAFFIC.setdefault("train_moe", {"fill_shards": 2})
