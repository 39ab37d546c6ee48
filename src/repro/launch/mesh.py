"""Production mesh + sharding-rule derivation (DESIGN.md §5).

``make_production_mesh`` is a FUNCTION so importing this module never touches
jax device state.  Single pod = (16, 16) ("data", "model") — 256 chips; two
pods = (2, 16, 16) ("pod", "data", "model") — the pod axis extends data
parallelism across the DCN.

``sharding_rules`` maps logical parameter axes to mesh axes per arch:
  embed   -> data   (FSDP: params+optimizer sharded over the data axis;
                     gathers stay intra-pod on multi-pod meshes)
  ffn/heads/kv/vocab -> model  (TP)
  experts -> model  (EP) when num_experts divides the model axis, else the
                     expert dim is replicated and ffn stays TP (mixtral)
Divisibility is enforced per-parameter in ``param_specs`` (a 9-head dim never
shards 16 ways — it silently stays replicated, by design).
"""
from __future__ import annotations

from typing import Any, Dict, NamedTuple, Optional, Sequence

import jax
from jax.sharding import AxisType, Mesh, NamedSharding
from jax.sharding import PartitionSpec as P

from ..models.config import ModelConfig


class ChipPeaks(NamedTuple):
    """Published per-chip peaks for the roofline."""
    flops_bf16: float     # FLOP/s
    hbm_bw: float         # bytes/s
    ici_bw: float         # bytes/s per link


#: keyed by ``jax.Device.device_kind``.  Source: Google Cloud documentation,
#: "TPU v5e" (197 TFLOP/s bf16, HBM at 819 GB/s, 1,600 Gbit/s of
#: interconnect over 4 links).
CHIP_PEAKS: Dict[str, ChipPeaks] = {
    "TPU v5 lite": ChipPeaks(flops_bf16=197e12, hbm_bw=819e9, ici_bw=50e9),
}


def chip_peaks(device_kind: str) -> ChipPeaks:
    """Peaks of one chip of ``device_kind``; an unknown kind is an error."""
    try:
        return CHIP_PEAKS[device_kind]
    except KeyError:
        raise KeyError(f"no published peaks for device kind {device_kind!r}; "
                       f"known: {sorted(CHIP_PEAKS)}") from None


def make_mesh(shape: Sequence[int], axes: Sequence[str],
              devices: Optional[Sequence[Any]] = None) -> Mesh:
    """A mesh whose axes GSPMD partitions automatically (``jax.make_mesh``
    otherwise makes them explicit, which the sharding rules here are not
    written for)."""
    return jax.make_mesh(tuple(shape), tuple(axes), devices=devices,
                         axis_types=(AxisType.Auto,) * len(shape))


def make_production_mesh(*, multi_pod: bool = False) -> Mesh:
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return make_mesh(shape, axes)


def mesh_axis_sizes(mesh: Mesh) -> Dict[str, int]:
    return dict(zip(mesh.axis_names, mesh.devices.shape))


def batch_axes(mesh: Mesh, batch: int, include_model: bool = False
               ) -> Optional[Any]:
    """Longest ("pod","data"[,"model"]) prefix that divides ``batch``."""
    sizes = mesh_axis_sizes(mesh)
    names = ("pod", "data", "model") if include_model else ("pod", "data")
    cand = [a for a in names if a in sizes]
    kept, prod = [], 1
    for a in cand:
        prod *= sizes[a]
        if batch % prod == 0:
            kept.append(a)
        else:
            break
    if not kept:
        return None
    return tuple(kept) if len(kept) > 1 else kept[0]


def sharding_rules(cfg: ModelConfig, mesh: Mesh, *,
                   global_batch: int, dp: bool = False) -> Dict[str, Any]:
    sizes = mesh_axis_sizes(mesh)
    model_n = sizes.get("model", 1)
    ep_ok = (cfg.moe is not None and cfg.moe.num_experts % model_n == 0)
    kv_shardable = cfg.n_kv_heads > 0 and cfg.n_kv_heads % model_n == 0
    if dp:
        # pure data parallelism (+ ZeRO-3 FSDP over every mesh axis): the
        # right regime for models too small to shard — a 16-way TP of a
        # 1.4 B model replicates un-shardable attention 16x (musicgen:
        # mem term 61.8 s -> the model axis becomes extra batch instead)
        return {
            "embed": ("data", "model"), "ffn": None, "heads": None,
            "kv": None, "vocab": None, "experts": None, "layers": None,
            "cache_batch": batch_axes(mesh, global_batch, include_model=True),
            "cache_len": None,
        }
    rules: Dict[str, Any] = {
        "embed": "data",
        "ffn": "model",
        "heads": "model",
        "kv": "model",
        "vocab": "model",
        "experts": "model" if ep_ok else None,
        "layers": None,
        "cache_batch": batch_axes(mesh, global_batch),
        # flash-decoding-style cache sharding: when kv heads don't divide the
        # model axis, shard the cache LENGTH dim instead (partial softmax +
        # tiny all-reduce of the m/l stats, done by GSPMD automatically)
        "cache_len": None if kv_shardable else "model",
    }
    rules.update(cfg.sharding_overrides)
    return rules


def make_constrain(mesh: Mesh, cfg: ModelConfig, global_batch: int,
                   *, gather_weights: bool = False, seq_shard: bool = False,
                   seq_len: int = 0, dp: bool = False):
    """Activation sharding-constraint callback for the step builders.

    Without these pins, GSPMD sometimes replicates the batch dim through the
    loss (a tied embedding's FSDP-sharded contracting dim confuses the
    propagation — verified on gemma-7b: 85 full-batch f32 logits tensors).

    ``gather_weights`` additionally pins the *gathered* (FSDP-unsharded) form
    of each block weight at its use site — on serve paths GSPMD otherwise
    reshards the 32k-token residual stream (2.1 GB f32 transpose+copy per
    matmul, verified on llama prefill) instead of all-gathering the 134 MB
    weight."""
    sizes = mesh_axis_sizes(mesh)
    model_n = sizes.get("model", 1)
    # a mesh without a model axis (pure data parallelism) shards nothing on it
    tp_ok = "model" in sizes and not dp
    bax = batch_axes(mesh, global_batch, include_model=dp)
    vocab_ax = "model" if (tp_ok and cfg.vocab_size % model_n == 0) else None
    # sequence parallelism (long-prefill): residual stream sharded over the
    # model axis on the SEQ dim; per-layer weights are gathered instead of
    # activations all-reduced — 32k-token activations dwarf the weights.
    sp = tp_ok and seq_shard and seq_len > 0 and seq_len % model_n == 0
    seq_ax = "model" if sp else None

    def tp(dim: int):  # model axis only if the dim divides (and not used by SP)
        return "model" if (tp_ok and not sp and dim % model_n == 0) else None

    ep_ax = ("model" if (tp_ok and cfg.moe is not None
                         and cfg.moe.num_experts % model_n == 0) else None)
    weight_specs = {
        "w_q": P(None, tp(cfg.n_heads), None),
        "w_kv": P(None, tp(cfg.n_kv_heads), None),
        "w_o": P(tp(cfg.n_heads), None, None),
        "w_in": P(None, tp(cfg.d_ff) if cfg.d_ff else None),
        "w_out": P(tp(cfg.d_ff) if cfg.d_ff else None, None),
        # MoE: expert dim stays EP-sharded; embed/ffn dims gathered (in bf16,
        # at the use site — otherwise GSPMD gathers the f32 upcast: 2x bytes)
        "w_moe": P(ep_ax, None, None),
        "w_moe_out": P(ep_ax, None, None),
    }

    def constrain(name: str, x):
        if name == "moe_tokens":  # (n_groups, G, D) grouped token stream
            # NOTE: sharding n over (data, model) to force a2a dispatch was
            # tried and catastrophically refuted (54 s -> 3787 s: GSPMD falls
            # back to full rematerialization) — groups stay data-sharded.
            n_ax = bax if isinstance(bax, str) else None
            return jax.lax.with_sharding_constraint(
                x, NamedSharding(mesh, P(n_ax, None, None)))
        if name == "moe_ecd":   # (n_groups, E, C, D) dispatch intermediates
            return jax.lax.with_sharding_constraint(
                x, NamedSharding(mesh, P(bax if isinstance(bax, str) else None,
                                         ep_ax, None, None)))
        if name == "logits":
            return jax.lax.with_sharding_constraint(
                x, NamedSharding(mesh, P(bax, None, vocab_ax)))
        if name == "hidden":
            return jax.lax.with_sharding_constraint(
                x, NamedSharding(mesh, P(bax, seq_ax, None)))
        if name in weight_specs:
            if not (gather_weights or sp):
                return x
            return jax.lax.with_sharding_constraint(
                x, NamedSharding(mesh, weight_specs[name]))
        return x

    return constrain


def input_shardings(mesh: Mesh, specs: Dict[str, Any],
                    dp: bool = False) -> Dict[str, Any]:
    """NamedShardings for a batch dict: leading (batch) dim over pod+data
    (+model under pure DP)."""
    out = {}
    for k, v in specs.items():
        b = v.shape[0] if len(v.shape) else 1
        ax = batch_axes(mesh, b, include_model=dp)
        ndim = len(v.shape)
        out[k] = NamedSharding(mesh, P(*([ax] + [None] * (ndim - 1))) if ndim
                               else P())
    return out
