"""Model configuration for the flexible decoder family.

One parameterized definition covers the registered architectures: block
*patterns* (unrolled leading layers + scanned super-blocks + unrolled
remainder) express heterogeneous stacks (RG-LRU/attn interleave, cross-attn
every Nth layer, a leading dense layer before the expert layers); mixer and
MLP kinds select attention (grouped-query or latent) / SSD / RG-LRU and
dense / MoE feed-forwards.
"""
from __future__ import annotations

import dataclasses
from dataclasses import dataclass, field
from typing import Any, Dict, Optional, Tuple

import jax.numpy as jnp

# block kinds: what the mixer is
#   attn   — causal self attention (GQA; window=None -> full)
#   swa    — sliding-window attention (window tokens)
#   local  — local attention (alias of swa; recurrentgemma naming)
#   ssd    — Mamba-2 state-space duality mixer (no separate MLP unless d_ff>0)
#   rec    — RG-LRU recurrent block
#   cross  — cross-attention to encoder/vision embeddings (+ self mlp)


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
    num_shared_experts: int = 0     # kimi-k2 has 1 shared expert
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

    @property
    def qk_head_dim(self) -> int:
        return self.qk_nope_head_dim + self.qk_rope_head_dim


@dataclass(frozen=True)
class SSMConfig:
    state_dim: int = 128          # N
    head_dim: int = 64            # P
    num_heads: int = 0            # derived: d_inner / head_dim if 0
    num_groups: int = 1           # G (B/C projections shared per group)
    expand: int = 2               # d_inner = expand * d_model
    conv_width: int = 4
    chunk_size: int = 256         # SSD chunk length


@dataclass(frozen=True)
class RGLRUConfig:
    lru_width: int = 0            # 0 -> d_model
    conv_width: int = 4
    c_exponent: float = 8.0       # a = a_param^(c * r)


@dataclass(frozen=True)
class ModelConfig:
    name: str = "model"
    family: str = "dense"         # dense | moe | ssm | hybrid | vlm | audio
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

    mlp_kind: str = "swiglu"      # swiglu | geglu | gelu | moe | none
    window: Optional[int] = None  # SWA/local attention window
    cross_attn_kv_len: int = 0    # vlm: number of vision tokens (stub frontend)

    moe: Optional[MoEConfig] = None
    mla: Optional[MLAConfig] = None
    ssm: Optional[SSMConfig] = None
    rglru: Optional[RGLRUConfig] = None

    rope_theta: float = 10000.0
    norm_eps: float = 1e-6
    tied_embeddings: bool = False
    embed_scale: bool = False      # gemma-style sqrt(d_model) scaling
    logit_softcap: float = 0.0

    dtype: str = "bfloat16"        # activations/params compute dtype
    param_dtype: str = "bfloat16"
    attn_impl: str = "chunked"     # chunked | naive
    attn_chunk: int = 1024         # KV chunk for chunked attention
    # dtype of materialized attention logits/probs tiles.  fp32 (default) is
    # the training-safe choice; bf16 halves the dominant S×chunk HBM traffic
    # on serve paths (stats m/l stay fp32 — only the tiles are rounded).
    attn_logits_dtype: str = "float32"
    # dry-run cost path: unroll every lax.scan so XLA cost analysis (which
    # counts while-loop bodies once) sees the full per-step work
    unroll_scans: bool = False
    # recompute each loss chunk's logits in the backward instead of keeping
    # every chunk's (B, chunk, V) float32 logits for it: long rows
    remat_loss: bool = False

    # distribution
    optimizer: str = "adamw"       # adamw | adafactor (1T-scale)
    # layer checkpoint: nothing | save_layer_inputs | dots | dots_no_batch;
    # save_layer_inputs keeps the scan carry's layer inputs and the attention
    # kernel's output and log-sum-exp, and recomputes the rest of the layer
    remat_policy: str = "save_layer_inputs"
    sharding_overrides: Dict[str, Any] = field(default_factory=dict, hash=False)

    # serving
    max_cache_len: int = 32768

    def __post_init__(self) -> None:
        if self.head_dim == 0:
            object.__setattr__(self, "head_dim", self.d_model // max(1, self.n_heads))
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

    def unrolled(self) -> "ModelConfig":
        """Equivalent config with every layer after the leading ones unrolled
        (pattern -> remainder).  Used by the dry-run cost path: XLA cost
        analysis counts while-loop bodies once, so per-step FLOPs are only
        correct on unrolled graphs."""
        layers = tuple(self.pattern) * self.pattern_repeats + tuple(self.remainder)
        return self.replace(pattern=(), remainder=layers)

    def layer_kinds(self) -> Tuple[Tuple[str, str], ...]:
        """(block kind, MLP kind) of every layer, in stack order."""
        rest = tuple(self.pattern) * self.pattern_repeats + tuple(self.remainder)
        return (tuple((k, LEADING_MLP) for k in self.leading)
                + tuple((k, self.mlp_kind) for k in rest))

    @property
    def q_per_kv(self) -> int:
        return self.n_heads // max(1, self.n_kv_heads)

    @property
    def activation_dtype(self):
        return jnp.dtype(self.dtype)

    def replace(self, **kw: Any) -> "ModelConfig":
        return dataclasses.replace(self, **kw)

    # ------------------------------------------------------------- size audit
    def param_count(self) -> int:
        """Analytic parameter count (drives MODEL_FLOPS = 6·N·D in §Roofline)."""
        D, V = self.d_model, self.vocab_size
        total = V * D  # embedding
        if not self.tied_embeddings:
            total += V * D
        for kind, mlp_kind in self.layer_kinds():
            total += self._block_params(kind, mlp_kind)
        total += D  # final norm
        return total

    def active_param_count(self) -> int:
        """Active params per token (= param_count for dense; MoE counts top_k
        + shared experts only)."""
        if self.moe is None:
            return self.param_count()
        D = self.d_model
        m = self.moe
        full_expert = 3 * D * m.d_ff_expert
        inactive = (m.held - m.top_k) * full_expert
        n_moe_layers = sum(1 for k, mk in self.layer_kinds()
                           if k in ("attn", "swa", "local", "cross") and mk == "moe")
        return self.param_count() - n_moe_layers * inactive

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

    def _block_params(self, kind: str, mlp_kind: str) -> int:
        D = self.d_model
        norms = 2 * D
        if kind in ("attn", "swa", "local"):
            return self._attn_params() + self._mlp_params(mlp_kind) + norms
        if kind == "cross":
            return self._attn_params() + self._mlp_params(mlp_kind) + norms + D
        if kind == "ssd":
            s = self.ssm or SSMConfig()
            d_in = s.expand * D
            nh = s.num_heads or d_in // s.head_dim
            # in_proj covers [z, x, B, C, dt]: 2*d_in + 2*G*N + nh
            zxbcdt = 2 * d_in + 2 * s.num_groups * s.state_dim + nh
            return D * zxbcdt + d_in * D + s.conv_width * (
                d_in + 2 * s.num_groups * s.state_dim) + 3 * nh + D
        if kind == "rec":
            r = self.rglru or RGLRUConfig()
            W = r.lru_width or D
            rec = 2 * D * W + W * D + r.conv_width * W + 2 * W * W + 2 * W
            return rec + self._mlp_params(mlp_kind) + norms
        raise ValueError(f"unknown block kind {kind!r}")

    def _mlp_params(self, mlp_kind: str) -> int:
        D, F = self.d_model, self.d_ff
        if mlp_kind in ("swiglu", "geglu"):
            return 3 * D * F
        if mlp_kind == "gelu":
            return 2 * D * F
        if mlp_kind == "moe":
            m = self.moe or MoEConfig()
            full = 3 * self.d_model * m.d_ff_expert
            router = self.d_model * m.num_experts
            if m.router == "sigmoid":
                router += m.num_experts         # the balancing bias
            return m.held * full + m.num_shared_experts * full + router
        if mlp_kind == "none":
            return 0
        raise ValueError(f"unknown mlp kind {mlp_kind!r}")
