"""Production mesh + sharding-rule derivation (DESIGN.md §5).

``make_production_mesh`` is a FUNCTION so importing this module never touches
jax device state.  Single pod = (16, 16) ("data", "model") — 256 chips; two
pods = (2, 16, 16) ("pod", "data", "model") — the pod axis extends data
parallelism across the DCN.

``sharding_rules`` maps logical parameter axes to mesh axes:
  embed   -> data   (FSDP: params+optimizer sharded over the data axis;
                     gathers stay intra-pod on multi-pod meshes)
  ffn/heads/kv/vocab -> model  (TP)
  experts -> model  (EP) when num_experts divides the model axis, else the
                     expert dim is replicated and ffn stays TP
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


def batch_axes(mesh: Mesh, batch: int) -> Optional[Any]:
    """Longest ("pod","data") prefix that divides ``batch``."""
    sizes = mesh_axis_sizes(mesh)
    cand = [a for a in ("pod", "data") if a in sizes]
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


def sharding_rules(cfg: ModelConfig, mesh: Mesh) -> Dict[str, Any]:
    model_n = mesh_axis_sizes(mesh).get("model", 1)
    ep_ok = (cfg.moe is not None and cfg.moe.num_experts % model_n == 0)
    return {
        "embed": "data",
        "ffn": "model",
        "heads": "model",
        "kv": "model",
        "vocab": "model",
        "experts": "model" if ep_ok else None,
        "layers": None,
    }


def make_constrain(mesh: Mesh, cfg: ModelConfig, global_batch: int):
    """Activation sharding-constraint callback for the step builders.

    Without these pins, GSPMD sometimes replicates the batch dim through the
    loss (a tied embedding's FSDP-sharded contracting dim confuses the
    propagation)."""
    sizes = mesh_axis_sizes(mesh)
    model_n = sizes.get("model", 1)
    # a mesh without a model axis (pure data parallelism) shards nothing on it
    tp_ok = "model" in sizes
    bax = batch_axes(mesh, global_batch)
    vocab_ax = "model" if (tp_ok and cfg.vocab_size % model_n == 0) else None
    ep_ax = ("model" if (tp_ok and cfg.moe is not None
                         and cfg.moe.num_experts % model_n == 0) else None)

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
                x, NamedSharding(mesh, P(bax, None, None)))
        return x

    return constrain


def input_shardings(mesh: Mesh, specs: Dict[str, Any]) -> Dict[str, Any]:
    """NamedShardings for a batch dict: leading (batch) dim over pod+data."""
    out = {}
    for k, v in specs.items():
        b = v.shape[0] if len(v.shape) else 1
        ax = batch_axes(mesh, b)
        ndim = len(v.shape)
        out[k] = NamedSharding(mesh, P(*([ax] + [None] * (ndim - 1))) if ndim
                               else P())
    return out
