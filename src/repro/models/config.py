"""Model configuration of the decoder the benchmark trains.

One definition covers the registered architectures: unrolled leading
layers, then a scanned pattern of layers, then unrolled ``remainder``
layers.  A layer's mixer is causal self-attention, grouped-query or latent
(``mla``), or Kimi Delta Attention (``kda``, a delta-rule recurrence whose
state resets at every packed document), followed by a SwiGLU or
mixture-of-experts feed-forward.
"""
from __future__ import annotations

import dataclasses
from dataclasses import dataclass
from typing import Any, Optional, Tuple

import jax.numpy as jnp

#: block kinds, by mixer: causal self-attention (grouped-query, or latent
#: where ``mla`` is set), or Kimi Delta Attention (``kda``); then the MLP
BLOCK_KINDS = ("attn", "kda")
#: MLP kinds: a dense SwiGLU of width d_ff, or the mixture of experts
MLP_KINDS = ("swiglu", "moe")
#: the MLP kind of the leading (dense) layers
LEADING_MLP = "swiglu"


@dataclass(frozen=True)
class MoEConfig:
    num_experts: int = 8
    top_k: int = 2
    d_ff_expert: int = 2048
    capacity_factor: float = 1.25
    # how many experts live on each model shard (num_experts % shard == 0 to
    # use expert parallelism; otherwise experts are replicated and d_ff is TP)
    expert_parallel: bool = True
    num_shared_experts: int = 0     # always-on experts beside the routed ones
    router_dtype: str = "float32"
    # router: "softmax" (top-k of softmax probabilities, renormalised) or
    # "sigmoid" (DeepSeek-V3 noaux_tc: top-k of sigmoid scores + a balancing
    # bias that the gradient does not move; weights from the unbiased
    # scores, renormalised, times ``routed_scaling``)
    router: str = "softmax"
    routed_scaling: float = 1.0
    # dispatch: "capacity" (GShard one-hot einsums, drops overflow) or
    # "dropless" (sort by expert, grouped matmuls over the held experts)
    dispatch: str = "capacity"
    # the experts this layer holds: [held_first, held_first + held_count);
    # held_count 0 holds all of them.  The router always spans num_experts.
    held_first: int = 0
    held_count: int = 0
    # weight of the balance loss in the total loss (the Switch loss for the
    # softmax router, the sequence-wise loss for the sigmoid router)
    aux_weight: float = 0.01
    # balancing-bias step: b_i += bias_rate * sign(mean load - load_i)
    bias_rate: float = 0.0

    @property
    def held(self) -> int:
        return self.held_count or self.num_experts


@dataclass(frozen=True)
class MLAConfig:
    """Multi-head latent attention (DeepSeek-V2/V3): queries through an
    optional low-rank path, keys and values from one normalised latent per
    token, a rotary part of the key shared by all heads."""
    q_lora_rank: int = 0          # 0: queries projected from x directly
    kv_lora_rank: int = 512
    qk_nope_head_dim: int = 128
    qk_rope_head_dim: int = 64
    v_head_dim: int = 128
    # rotate the qk_rope_head_dim part of q and k (False: Kimi-Linear's
    # ``mla_use_nope``, the part is kept and shared but not rotated)
    rope: bool = True

    @property
    def qk_head_dim(self) -> int:
        return self.qk_nope_head_dim + self.qk_rope_head_dim


@dataclass(frozen=True)
class KDAConfig:
    """Kimi Delta Attention (the Kimi Linear report, arXiv:2510.26692):
    per head a (head_dim, head_dim) state updated by the delta rule with a
    decay per key channel, q, k and v through a causal depthwise short
    convolution and SiLU, the decay and the output gate through rank
    ``gate_rank`` projections."""
    n_heads: int = 32
    head_dim: int = 128           # d_k = d_v
    conv_size: int = 4
    gate_rank: int = 128


@dataclass(frozen=True)
class ModelConfig:
    name: str = "model"
    family: str = "dense"         # dense | moe (read by nothing)
    num_layers: int = 4
    d_model: int = 512
    n_heads: int = 8
    n_kv_heads: int = 8
    head_dim: int = 0             # 0 -> d_model // n_heads
    d_ff: int = 2048
    vocab_size: int = 32000

    # stack structure: leading layers are unrolled before the scan (their
    # MLP is a dense ``LEADING_MLP`` of width d_ff, as the first dense
    # layers of an expert model are), pattern is scanned `pattern_repeats`
    # times, remainder layers are unrolled after the scan.  pattern of
    # ("attn",) with repeats=num_layers is the homogeneous case.
    pattern: Tuple[str, ...] = ("attn",)
    remainder: Tuple[str, ...] = ()
    leading: Tuple[str, ...] = ()

    mlp_kind: str = "swiglu"      # swiglu | moe

    moe: Optional[MoEConfig] = None
    mla: Optional[MLAConfig] = None
    kda: Optional[KDAConfig] = None

    rope_theta: float = 10000.0
    norm_eps: float = 1e-6
    tied_embeddings: bool = False

    dtype: str = "bfloat16"        # activations/params compute dtype
    param_dtype: str = "bfloat16"
    attn_chunk: int = 1024         # KV chunk for chunked attention
    # recompute each loss chunk's logits in the backward instead of keeping
    # every chunk's (B, chunk, V) float32 logits for it: long rows
    remat_loss: bool = False

    # distribution
    optimizer: str = "adamw"       # adamw | adafactor
    # layer checkpoint: nothing | save_layer_inputs | dots | dots_no_batch;
    # save_layer_inputs keeps the scan carry's layer inputs and the attention
    # kernel's output and log-sum-exp, and recomputes the rest of the layer
    remat_policy: str = "save_layer_inputs"

    def __post_init__(self) -> None:
        if self.head_dim == 0:
            object.__setattr__(self, "head_dim", self.d_model // max(1, self.n_heads))
        for kind in self.leading + self.pattern + self.remainder:
            if kind not in BLOCK_KINDS:
                raise ValueError(f"{self.name}: unknown block kind {kind!r}; "
                                 f"have {BLOCK_KINDS}")
            if kind == "kda" and self.kda is None:
                raise ValueError(f"{self.name}: a kda block needs ``kda``")
        if self.mlp_kind not in MLP_KINDS:
            raise ValueError(f"{self.name}: unknown mlp kind "
                             f"{self.mlp_kind!r}; have {MLP_KINDS}")
        # pattern bookkeeping
        total_pat = len(self.pattern)
        if total_pat and self._scanned_layers % total_pat:
            raise ValueError(
                f"{self.name}: num_layers={self.num_layers} minus leading "
                f"{len(self.leading)} and remainder {len(self.remainder)} not "
                f"divisible by pattern {self.pattern}")

    @property
    def _scanned_layers(self) -> int:
        return self.num_layers - len(self.leading) - len(self.remainder)

    @property
    def pattern_repeats(self) -> int:
        if not self.pattern:
            return 0
        return self._scanned_layers // len(self.pattern)

    def layer_mlps(self) -> Tuple[str, ...]:
        """The MLP kind of every layer, in stack order."""
        rest = len(self.pattern) * self.pattern_repeats + len(self.remainder)
        return (LEADING_MLP,) * len(self.leading) + (self.mlp_kind,) * rest

    def layer_kinds(self) -> Tuple[str, ...]:
        """The block kind of every layer, in stack order."""
        return (self.leading + self.pattern * self.pattern_repeats
                + self.remainder)

    @property
    def activation_dtype(self):
        return jnp.dtype(self.dtype)

    def replace(self, **kw: Any) -> "ModelConfig":
        return dataclasses.replace(self, **kw)

    # ------------------------------------------------------------- size audit
    def param_count(self) -> int:
        """Analytic parameter count."""
        D, V = self.d_model, self.vocab_size
        total = V * D  # embedding
        if not self.tied_embeddings:
            total += V * D
        for kind, mlp_kind in zip(self.layer_kinds(), self.layer_mlps()):
            mixer = self._kda_params() if kind == "kda" else self._attn_params()
            total += mixer + self._mlp_params(mlp_kind) + 2 * D
        total += D  # final norm
        return total

    def active_param_count(self) -> int:
        """Active params per token (= param_count for dense; MoE counts top_k
        + shared experts only)."""
        if self.moe is None:
            return self.param_count()
        m = self.moe
        inactive = (m.held - m.top_k) * 3 * self.d_model * m.d_ff_expert
        return self.param_count() - self.layer_mlps().count("moe") * inactive

    def _attn_params(self) -> int:
        D, H, KV, hd = self.d_model, self.n_heads, self.n_kv_heads, self.head_dim
        if self.mla is None:
            return D * H * hd + 2 * D * KV * hd + H * hd * D
        a = self.mla
        if a.q_lora_rank:
            q = D * a.q_lora_rank + a.q_lora_rank + a.q_lora_rank * H * a.qk_head_dim
        else:
            q = D * H * a.qk_head_dim
        kv = (D * (a.kv_lora_rank + a.qk_rope_head_dim) + a.kv_lora_rank
              + a.kv_lora_rank * H * (a.qk_nope_head_dim + a.v_head_dim))
        return q + kv + H * a.v_head_dim * D

    def _kda_params(self) -> int:
        a, D = self.kda, self.d_model
        HK = a.n_heads * a.head_dim
        proj = 3 * D * HK + HK * D                     # q, k, v, o
        conv = 3 * a.conv_size * HK
        gates = 2 * (D * a.gate_rank + a.gate_rank * HK) + D * a.n_heads
        return proj + conv + gates + a.n_heads + 2 * HK + a.head_dim

    def _mlp_params(self, mlp_kind: str) -> int:
        if mlp_kind == "swiglu":
            return 3 * self.d_model * self.d_ff
        m = self.moe or MoEConfig()
        full = 3 * self.d_model * m.d_ff_expert
        router = self.d_model * m.num_experts
        if m.router == "sigmoid":
            router += m.num_experts         # the balancing bias
        return m.held * full + m.num_shared_experts * full + router
