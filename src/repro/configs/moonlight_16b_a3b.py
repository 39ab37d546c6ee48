"""moonlight-16b-a3b [moe] — Moonshot's DeepSeek-V3-family model:
multi-head latent attention, one leading dense layer, then expert layers
of 64 sigmoid-routed experts (6 per token) and 2 shared experts.

Source: https://huggingface.co/moonshotai/Moonlight-16B-A3B (config.json,
``model_type`` deepseek_v3): 27 layers, ``first_k_dense_replace`` 1, hidden
2048, vocab 163840 untied, 16 heads, ``q_lora_rank`` null, ``kv_lora_rank``
512, ``qk_nope_head_dim`` 128, ``qk_rope_head_dim`` 64, ``v_head_dim`` 128,
``rope_theta`` 50000 (no rope scaling), dense ``intermediate_size`` 11264,
``moe_intermediate_size`` 1408, ``n_routed_experts`` 64,
``num_experts_per_tok`` 6, ``n_shared_experts`` 2, ``scoring_func``
sigmoid, ``topk_method`` noaux_tc, ``norm_topk_prob`` true,
``routed_scaling_factor`` 2.446, ``n_group`` = ``topk_group`` = 1 (no group
limit), ``rms_norm_eps`` 1e-5, ``max_position_embeddings`` 8192.

Layer equations (pre-norm residual blocks, x <- x + Attn(RMSNorm(x)),
x <- x + FFN(RMSNorm(x)), a final RMSNorm, untied output head):

- MLA.  q = x W_q (2048 -> 16 x 192), split into q_nope (128) and q_pe
  (64).  [c, k_pe] = x W_kva (2048 -> 512 + 64); c = RMSNorm_kv(c).
  [k_nope, v] = c W_kvb (512 -> 16 x (128 + 128)).  Rope on q_pe and on
  k_pe at the token's position, k_pe shared by every head.  q = [q_nope,
  q_pe], k = [k_nope, k_pe]; causal softmax over the row's own segment at
  scale 192^-0.5, times v, then W_o (16 x 128 -> 2048).
- Leading layer: a dense SwiGLU of width 11264,
  (silu(x W_gate) * (x W_up)) W_down.
- MoE layers.  s = sigmoid(x W_r) in float32 (64 scores).  Selection: the
  top 6 of s + b, b the balancing bias.  Weights w_i = 2.446 s_i /
  sum_top6 s (the unbiased scores).  out = sum_{i in top6, i held}
  w_i SwiGLU_i(x) + SwiGLU_shared(x), each routed expert of width 1408,
  the shared experts one SwiGLU of width 2 x 1408 = 2816.
- Balance (DeepSeek-V3, arXiv:2412.19437 §2.1.2).  After each step, per
  layer, b_i += gamma sign(mean load - load_i) (loads: tokens that chose
  expert i in the step).  Loss += alpha sum_i f_i P_i per packed row, f_i =
  (E / (K T)) #(tokens choosing i), P_i = mean over the row's tokens of
  s_i / sum_j s_j, averaged over rows.  gamma = 0.001 and alpha = 1e-4
  (the report's values; not in config.json).

Departures: RMSNorm is the repo's x * rsqrt(mean(x^2) + eps) * (1 + g)
with g starting at 0 (HF: a weight starting at 1, the same function);
rope pairs half-split dimensions (HF stores them interleaved: under random
weights the two differ by a fixed permutation of the rope columns).

``CONFIG`` is the published model (every expert held); ``SMOKE`` a CPU
size of the same blocks.  The benchmark's chip share (5 layers, 8 of the
64 experts, 20,480 vocabulary rows) is stated in
``bench/configs/moonlight-16b-a3b.ep8.json``.
"""
from ..models.config import MLAConfig, ModelConfig, MoEConfig

CONFIG = ModelConfig(
    name="moonlight-16b-a3b",
    family="moe",
    num_layers=27,
    d_model=2048,
    n_heads=16, n_kv_heads=16, head_dim=192,
    d_ff=11264,                     # the leading dense layer
    vocab_size=163840,
    leading=("attn",),
    pattern=("attn",),
    mlp_kind="moe",
    mla=MLAConfig(q_lora_rank=0, kv_lora_rank=512, qk_nope_head_dim=128,
                  qk_rope_head_dim=64, v_head_dim=128),
    moe=MoEConfig(num_experts=64, top_k=6, d_ff_expert=1408,
                  num_shared_experts=2, router="sigmoid",
                  routed_scaling=2.446, dispatch="dropless",
                  aux_weight=1e-4, bias_rate=1e-3),
    rope_theta=50000.0,
    norm_eps=1e-5,
    remat_policy="save_layer_inputs",
    remat_loss=True,
)

SMOKE = CONFIG.replace(
    name="moonlight-smoke", num_layers=3, d_model=64,
    n_heads=4, n_kv_heads=4, head_dim=24, d_ff=96, vocab_size=256,
    mla=MLAConfig(q_lora_rank=0, kv_lora_rank=32, qk_nope_head_dim=16,
                  qk_rope_head_dim=8, v_head_dim=16),
    moe=MoEConfig(num_experts=8, top_k=3, d_ff_expert=32,
                  num_shared_experts=2, router="sigmoid",
                  routed_scaling=2.446, dispatch="dropless",
                  aux_weight=1e-4, bias_rate=1e-3),
    dtype="float32", param_dtype="float32",
)
