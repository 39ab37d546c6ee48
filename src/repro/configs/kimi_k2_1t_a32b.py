"""kimi-k2-1t-a32b [moe] — trillion-param MoE of the DeepSeek-V3 family.

Published shape (hf:moonshotai/Kimi-K2-Instruct config.json): 61 layers,
``first_k_dense_replace`` 1 (a leading dense layer of width 18432), d_model
7168, 64 heads of multi-head latent attention (``q_lora_rank`` 1536,
``kv_lora_rank`` 512, qk heads of 128 + 64 rope, v heads of 128), 384
routed experts of width 2048, 8 per token, 1 shared expert, sigmoid scores
with bias-corrected (noaux_tc) selection, ``routed_scaling_factor`` 2.827,
vocab 163840, ``rope_theta`` 50000.  The blocks are those of
``moonlight_16b_a3b`` (see its docstring for the equations); YaRN rope
scaling is not modelled.

At ~1.03 T total / ~33 B active params this is the arch that forces the
1000+-node posture: Adafactor (factored optimizer state), 16-way expert
parallelism (384/16 = 24 experts per shard), FSDP over the data axis, and
the capacity dispatch that partitions over the mesh.
"""
from ..models.config import MLAConfig, ModelConfig, MoEConfig

CONFIG = ModelConfig(
    name="kimi-k2-1t-a32b",
    family="moe",
    num_layers=61,
    d_model=7168,
    n_heads=64, n_kv_heads=64, head_dim=192,
    d_ff=18432,                     # the leading dense layer
    vocab_size=163840,
    leading=("attn",),
    pattern=("attn",),
    mlp_kind="moe",
    mla=MLAConfig(q_lora_rank=1536, kv_lora_rank=512, qk_nope_head_dim=128,
                  qk_rope_head_dim=64, v_head_dim=128),
    moe=MoEConfig(num_experts=384, top_k=8, d_ff_expert=2048,
                  capacity_factor=1.25, num_shared_experts=1,
                  router="sigmoid", routed_scaling=2.827,
                  aux_weight=1e-4, bias_rate=1e-3),
    rope_theta=50000.0,
    optimizer="adafactor",
    remat_policy="save_layer_inputs",
)

SMOKE = CONFIG.replace(
    name="kimi-smoke", num_layers=3, d_model=64,
    n_heads=4, n_kv_heads=4, head_dim=24, d_ff=96, vocab_size=256,
    mla=MLAConfig(q_lora_rank=32, kv_lora_rank=32, qk_nope_head_dim=16,
                  qk_rope_head_dim=8, v_head_dim=16),
    # capacity for every token at this size, so that the full forward and
    # the one-token decode route alike (no overflow drops)
    moe=MoEConfig(num_experts=8, top_k=2, d_ff_expert=32, capacity_factor=4.0,
                  num_shared_experts=1, router="sigmoid",
                  routed_scaling=2.827, aux_weight=1e-4, bias_rate=1e-3),
    dtype="float32", param_dtype="float32",
)
