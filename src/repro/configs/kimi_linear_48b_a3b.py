"""kimi-linear-48b-a3b [moe] — Moonshot's hybrid linear-attention model:
Kimi Delta Attention (KDA) and multi-head latent attention without
rotation, 3 : 1, one leading dense layer, then expert layers of 256
sigmoid-routed experts (8 per token) and 1 shared expert.

Source: https://huggingface.co/moonshotai/Kimi-Linear-48B-A3B-Instruct
(config.json, ``model_type`` kimi_linear): 27 layers, hidden 2304, vocab
163840 untied; ``linear_attn_config``: KDA at layers 1-3, 5-7, ..., 25-26,
full attention at 4, 8, ..., 24, 27 (1-indexed), 32 KDA heads of 128,
``short_conv_kernel_size`` 4; MLA with ``mla_use_nope`` true: 32 heads,
``q_lora_rank`` null, ``kv_lora_rank`` 512, ``qk_nope_head_dim`` 128,
``qk_rope_head_dim`` 64, ``v_head_dim`` 128; ``first_k_dense_replace`` 1
(dense ``intermediate_size`` 9216), ``num_experts`` 256 of
``moe_intermediate_size`` 1024, ``num_experts_per_token`` 8,
``num_shared_experts`` 1, ``moe_router_activation_func`` sigmoid,
``moe_renormalize`` true, ``routed_scaling_factor`` 2.446,
``num_expert_group`` = ``topk_group`` = 1, ``rms_norm_eps`` 1e-5.

Layer equations (pre-norm residual blocks, a final RMSNorm, an untied
output head):

- KDA layers: ``models/kda.py`` (the Kimi Linear report,
  arXiv:2510.26692): q, k, v through a causal depthwise conv of width 4
  and SiLU; q and k L2-normalised, q scaled by 128^-1/2; beta =
  sigmoid(x W_beta) per head; log a = -exp(A_log) softplus(x W_f_a W_f_b +
  dt_bias) per key channel (W_f_a of rank 128); the per-head recurrence
  S_t = (I - beta k k^T) Diag(a) S_{t-1} + beta k v^T, o_t = S_t^T q_t,
  reset at every packed document; the head output RMS-normalised, times
  sigmoid(x W_g_a W_g_b + g_bias) (rank 128), then W_o.
- MLA layers: as ``moonlight_16b_a3b``'s, with no rotation of the 64-wide
  shared key part (``mla_use_nope``).
- Leading layer: a dense SwiGLU of width 9216.  Expert layers: the
  sigmoid router with its balancing bias over 256 experts, 8 chosen,
  weights renormalised and times 2.446, plus one shared SwiGLU of 1024.

Departures: RMSNorm is the repo's x * rsqrt(mean(x^2) + eps) * (1 + g)
with g starting at 0 (the gated output norm too); A_log and dt_bias start
at 0 here (the benchmark draws them as its configuration file states).

``CONFIG`` is the published model (every expert held); ``SMOKE`` a CPU
size of the same blocks.  The benchmark's chip share (layers 1-5, 8 of the
256 experts, 20,480 vocabulary rows) is stated in
``bench/configs/kimi-linear-48b-a3b.ep32.json``.
"""
from ..models.config import KDAConfig, MLAConfig, ModelConfig, MoEConfig

_FULL = (4, 8, 12, 16, 20, 24, 27)

CONFIG = ModelConfig(
    name="kimi-linear-48b-a3b",
    family="moe",
    num_layers=27,
    d_model=2304,
    n_heads=32, n_kv_heads=32, head_dim=192,
    d_ff=9216,                      # the leading dense layer
    vocab_size=163840,
    leading=("kda",),
    pattern=(),
    remainder=tuple("attn" if i in _FULL else "kda" for i in range(2, 28)),
    mlp_kind="moe",
    mla=MLAConfig(q_lora_rank=0, kv_lora_rank=512, qk_nope_head_dim=128,
                  qk_rope_head_dim=64, v_head_dim=128, rope=False),
    kda=KDAConfig(n_heads=32, head_dim=128, conv_size=4, gate_rank=128),
    moe=MoEConfig(num_experts=256, top_k=8, d_ff_expert=1024,
                  num_shared_experts=1, router="sigmoid",
                  routed_scaling=2.446, dispatch="dropless",
                  aux_weight=1e-4, bias_rate=1e-3),
    norm_eps=1e-5,
    remat_policy="save_layer_inputs",
    remat_loss=True,
)

SMOKE = CONFIG.replace(
    name="kimi-linear-smoke", num_layers=5, d_model=64,
    n_heads=4, n_kv_heads=4, head_dim=24, d_ff=96, vocab_size=256,
    remainder=("kda", "kda", "attn", "kda"),
    mla=MLAConfig(q_lora_rank=0, kv_lora_rank=32, qk_nope_head_dim=16,
                  qk_rope_head_dim=8, v_head_dim=16, rope=False),
    kda=KDAConfig(n_heads=4, head_dim=16, conv_size=4, gate_rank=16),
    moe=MoEConfig(num_experts=8, top_k=3, d_ff_expert=32,
                  num_shared_experts=1, router="sigmoid",
                  routed_scaling=2.446, dispatch="dropless",
                  aux_weight=1e-4, bias_rate=1e-3),
    dtype="float32", param_dtype="float32",
)
