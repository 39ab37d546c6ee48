"""Multi-pod AOT dry-run: lower + compile every (arch × shape × mesh) cell.

For each cell this builds the exact step the production job would run
(train_step / prefill / serve_step), with parameters, optimizer state, and
decode caches as ShapeDtypeStructs (no allocation), jits it with the
production in/out shardings, and runs ``.lower().compile()``.  Success proves
the distribution config is coherent: every collective the partitioner needs
exists and every per-device buffer fits.

Outputs per cell (written to benchmarks/artifacts/dryrun/*.json):
  memory_analysis  — per-device argument/output/temp bytes (proves it fits)
  cost_analysis    — HLO FLOPs + bytes accessed (roofline compute/memory terms)
  collectives      — per-op-kind traffic parsed from the optimized HLO
                     (roofline collective term)

Usage:
  python -m repro.launch.dryrun --arch gemma-7b --shape train_4k
  python -m repro.launch.dryrun --all [--multi-pod] [--force]
"""
import argparse
import json
import os
import re
import sys
import time
import traceback
from typing import Any, Dict, Optional

#: the chip the dry-run pods are made of (a key of ``mesh.CHIP_PEAKS``)
TARGET_KIND = "TPU v5 lite"
HOST_DEVICES = 512


def force_host_devices(n: int = HOST_DEVICES) -> None:
    """Ask XLA's CPU backend for ``n`` devices to lay the pod meshes on.
    Appends to ``XLA_FLAGS`` rather than replacing it, and must run before
    JAX initializes a backend (it fixes the device count then)."""
    flag = f"--xla_force_host_platform_device_count={n}"
    flags = os.environ.get("XLA_FLAGS", "")
    if flag not in flags.split():
        os.environ["XLA_FLAGS"] = f"{flags} {flag}".strip()


ART_DIR = os.path.join(os.path.dirname(__file__), "..", "..", "..",
                       "benchmarks", "artifacts", "dryrun")


def _mesh_tag(multi_pod: bool) -> str:
    return "pod2x16x16" if multi_pod else "pod16x16"


def artifact_path(arch: str, shape: str, multi_pod: bool) -> str:
    os.makedirs(ART_DIR, exist_ok=True)
    return os.path.abspath(os.path.join(
        ART_DIR, f"{arch}__{shape}__{_mesh_tag(multi_pod)}.json"))


# --------------------------------------------------------------- collectives
_COLL_RE = re.compile(
    r"=\s*(?P<result>\([^)]*\)|[a-z0-9]+\[[0-9,]*\][^ ]*)\s+"
    r"(?P<op>all-reduce|all-gather|reduce-scatter|all-to-all|collective-permute)"
    r"(?:-start)?\(")
_RESULT_SHAPE_RE = re.compile(r"([a-z0-9]+)\[([0-9,]*)\]")
_GROUP_RE = re.compile(r"replica_groups=\{?\{([0-9, ]+)\}")
# iota format: replica_groups=[num_groups,group_size]<=[total](T(perm))?
_IOTA_GROUP_RE = re.compile(r"replica_groups=\[(\d+),(\d+)\]<=\[")
_DTYPE_BYTES = {"pred": 1, "s8": 1, "u8": 1, "s16": 2, "u16": 2, "bf16": 2,
                "f16": 2, "s32": 4, "u32": 4, "f32": 4, "s64": 8, "u64": 8,
                "f64": 8, "c64": 8, "c128": 16}


def parse_collectives(hlo_text: str) -> Dict[str, Any]:
    """Per-device collective traffic from optimized HLO (ring model):
      all-reduce: 2·R·(n-1)/n    all-gather: R·(n-1)/n  (R = result bytes)
      reduce-scatter: R·(n-1)    all-to-all: R·(n-1)/n  permute: R
    """
    per_kind_bytes: Dict[str, float] = {}
    per_kind_count: Dict[str, int] = {}
    total = 0.0
    for line in hlo_text.splitlines():
        m = _COLL_RE.search(line)
        if not m or "-done" in line:
            continue
        op = m.group("op")
        # result may be a tuple — sum every shape token inside it
        r = 0
        for dtype, dims in _RESULT_SHAPE_RE.findall(m.group("result")):
            dt = _DTYPE_BYTES.get(dtype)
            if dt is None:
                continue
            numel = 1
            for d in dims.split(","):
                if d.strip():
                    numel *= int(d)
            r += numel * dt
        if r == 0:
            continue
        g = _GROUP_RE.search(line)
        if g:
            n = len(g.group(1).split(","))
        else:
            gi = _IOTA_GROUP_RE.search(line)
            n = int(gi.group(2)) if gi else 2
        if n <= 1:
            continue
        if op == "all-reduce":
            traffic = 2.0 * r * (n - 1) / n
        elif op == "all-gather":
            traffic = r * (n - 1) / n
        elif op == "reduce-scatter":
            traffic = r * (n - 1)
        elif op == "all-to-all":
            traffic = r * (n - 1) / n
        else:  # collective-permute
            traffic = float(r)
        per_kind_bytes[op] = per_kind_bytes.get(op, 0.0) + traffic
        per_kind_count[op] = per_kind_count.get(op, 0) + 1
        total += traffic
    return {"total_bytes": total, "by_kind_bytes": per_kind_bytes,
            "by_kind_count": per_kind_count}


# ------------------------------------------------------------- memory model
# The CPU backend barely fuses, so raw "bytes accessed" counts every convert/
# broadcast/multiply as HBM traffic — a TPU fuses those chains into their
# producing/consuming matmuls.  This model walks the optimized HLO and counts
# operand+result bytes ONLY for ops that genuinely materialize on TPU:
_MATERIALIZING = (
    "dot", "convolution", "fusion", "reduce", "reduce-window", "sort",
    "transpose", "copy", "concatenate", "pad", "reverse", "gather", "scatter",
    "dynamic-slice", "dynamic-update-slice", "all-reduce", "all-gather",
    "reduce-scatter", "all-to-all", "collective-permute", "select-and-scatter",
)
_INSTR_RE = re.compile(
    r"=\s*(?:\([^)]*\)|[a-z0-9]+\[[0-9,]*\][^ ]*)\s+([a-z0-9-]+)(?:-start)?\(")
_SHAPE_RE = re.compile(r"([a-z0-9]+)\[([0-9,]*)\]")


def _shape_bytes(dtype: str, dims: str) -> int:
    dt = _DTYPE_BYTES.get(dtype)
    if dt is None:
        return 0
    n = 1
    for d in dims.split(","):
        if d.strip():
            n *= int(d)
    return n * dt


def tpu_memory_bytes(hlo_text: str) -> float:
    """Approximate per-device HBM traffic: sum of operand+result bytes over
    materializing ops (elementwise/convert/broadcast/bitcast assumed fused).

    Only ENTRY-computation instructions count: ops inside fusion bodies are
    VMEM/register-resident on TPU (counting them quadruple-billed the
    attention tiles — the fusion call site already carries its operand and
    result bytes)."""
    total = 0.0
    in_entry = False
    for line in hlo_text.splitlines():
        stripped = line.strip()
        if stripped.startswith("ENTRY "):
            in_entry = True
            continue
        if in_entry and stripped == "}":
            in_entry = False
            continue
        if not in_entry:
            continue
        m = _INSTR_RE.search(line)
        if not m:
            continue
        op = m.group(1)
        if op not in _MATERIALIZING or "-done" in line:
            continue
        # result + operand shapes all appear as dtype[dims] tokens in the line
        for dtype, dims in _SHAPE_RE.findall(line):
            total += _shape_bytes(dtype, dims)
    return total


# ------------------------------------------------------------------ the cell
def _build_lowered(cfg, shape: str, mesh, *, grad_accum: int, loss_chunk: int,
                   sp: bool = False, dp: bool = False):
    """Build the jitted step for one cfg/shape/mesh and return lowered."""
    import jax
    import jax.numpy as jnp
    from jax.sharding import NamedSharding
    from jax.sharding import PartitionSpec as P

    from ..configs import SHAPES, cache_len_for, input_specs
    from ..models.model import cache_defs, model_defs
    from ..models.params import abstract_params, param_specs
    from ..training.optim import opt_state_defs
    from ..training.steps import make_prefill_step, make_serve_step, make_train_step
    from .mesh import (input_shardings, make_constrain, mesh_axis_sizes,
                       sharding_rules)

    spec = SHAPES[shape]
    rules = sharding_rules(cfg, mesh, global_batch=spec.global_batch, dp=dp)
    sizes = mesh_axis_sizes(mesh)
    named = lambda t: jax.tree.map(lambda s: NamedSharding(mesh, s), t,
                                   is_leaf=lambda x: isinstance(x, P))
    pdefs = model_defs(cfg)
    pshard = named(param_specs(pdefs, rules, sizes))
    pabs = abstract_params(pdefs)
    bspecs = input_specs(cfg, shape)
    bshard = input_shardings(mesh, bspecs, dp=dp)

    if spec.kind == "train":
        odefs = opt_state_defs(cfg.optimizer, pdefs)
        oshard = named(param_specs(odefs, rules, sizes))
        oabs = abstract_params(odefs)
        step = make_train_step(cfg, loss_chunk=loss_chunk, grad_accum=grad_accum,
                               constrain=make_constrain(mesh, cfg,
                                                        spec.global_batch,
                                                        gather_weights=True,
                                                        seq_shard=sp,
                                                        seq_len=spec.seq_len,
                                                        dp=dp),
                               grad_shardings=pshard)
        jitted = jax.jit(step, in_shardings=(pshard, oshard, bshard),
                         out_shardings=(pshard, oshard, None),
                         donate_argnums=(0, 1))
        return jitted.lower(pabs, oabs, bspecs)
    if spec.kind == "prefill":
        cdefs = cache_defs(cfg, spec.global_batch, cache_len_for(cfg, shape))
        cshard = named(param_specs(cdefs, rules, sizes))
        step = make_prefill_step(
            cfg, cache_len_for(cfg, shape),
            constrain=make_constrain(mesh, cfg, spec.global_batch,
                                     gather_weights=True, seq_shard=sp,
                                     seq_len=spec.seq_len, dp=dp))
        jitted = jax.jit(step, in_shardings=(pshard, bshard),
                         out_shardings=(None, cshard))
        return jitted.lower(pabs, bspecs)
    # decode
    cdefs = cache_defs(cfg, spec.global_batch, cache_len_for(cfg, shape))
    cshard = named(param_specs(cdefs, rules, sizes))
    cabs = abstract_params(cdefs)
    step = make_serve_step(cfg, constrain=make_constrain(
        mesh, cfg, spec.global_batch, gather_weights=True, dp=dp))
    jitted = jax.jit(step, in_shardings=(pshard, cshard, bshard["tokens"], None),
                     out_shardings=(None, None, cshard),
                     donate_argnums=(1,))
    pos = jax.ShapeDtypeStruct((), jnp.int32)
    return jitted.lower(pabs, cabs, bspecs["tokens"], pos)


def _costs_of(compiled) -> Dict[str, Any]:
    cost = compiled.cost_analysis()
    if isinstance(cost, (list, tuple)):
        cost = cost[0]
    text = compiled.as_text()
    coll = parse_collectives(text)
    return {"flops": float(cost.get("flops", 0.0)),
            "bytes": tpu_memory_bytes(text),
            "bytes_raw": float(cost.get("bytes accessed", 0.0)),
            "coll": coll}


def _extrapolate(c1: Dict[str, Any], c2: Dict[str, Any], R: int) -> Dict[str, Any]:
    """XLA cost analysis counts while-loop bodies ONCE regardless of trip
    count (verified), so per-step costs are reconstructed from two reduced
    depths: cost(R) = cost(1) + (cost(2) - cost(1)) * (R - 1).  Everything
    per-layer (block compute, per-layer collectives, stacked-param optimizer
    work) is linear in R; everything else (embed, loss, step overhead) sits
    in the intercept."""
    lin = lambda a, b: a + (b - a) * (R - 1)
    kinds = set(c1["coll"]["by_kind_bytes"]) | set(c2["coll"]["by_kind_bytes"])
    coll_bytes = {k: lin(c1["coll"]["by_kind_bytes"].get(k, 0.0),
                         c2["coll"]["by_kind_bytes"].get(k, 0.0)) for k in kinds}
    coll_count = {k: round(lin(c1["coll"]["by_kind_count"].get(k, 0),
                               c2["coll"]["by_kind_count"].get(k, 0))) for k in kinds}
    return {"flops": lin(c1["flops"], c2["flops"]),
            "bytes": lin(c1["bytes"], c2["bytes"]),
            "bytes_raw": lin(c1["bytes_raw"], c2["bytes_raw"]),
            "coll": {"total_bytes": sum(coll_bytes.values()),
                     "by_kind_bytes": coll_bytes,
                     "by_kind_count": coll_count}}


def run_cell(arch: str, shape: str, *, multi_pod: bool,
             grad_accum: int = 1, loss_chunk: int = 1024,
             overrides: Optional[Dict[str, Any]] = None,
             sp: bool = False, dp: bool = False) -> Dict[str, Any]:
    from ..configs import SHAPES, get_config, shape_applicable
    from .mesh import chip_peaks, make_production_mesh

    cfg = get_config(arch)
    if overrides:
        cfg = cfg.replace(**overrides)
    if not shape_applicable(cfg, shape):
        return {"arch": arch, "shape": shape, "skipped": True,
                "reason": "full-attention arch: 500k dense KV cache is the "
                          "quadratic wall (DESIGN.md §4)"}
    spec = SHAPES[shape]
    mesh = make_production_mesh(multi_pod=multi_pod)
    n_chips = mesh.devices.size
    # auto pure-DP (EXPERIMENTS.md §Perf cell 1): sub-3B models whose heads
    # don't divide the model axis replicate attention under TP — the model
    # axis is worth more as extra data parallelism (42x on musicgen train)
    model_n = dict(zip(mesh.axis_names, mesh.devices.shape)).get("model", 1)
    if (not dp and cfg.n_heads > 0 and cfg.n_heads % model_n != 0
            and cfg.param_count() < 3e9
            and spec.global_batch % mesh.devices.size == 0):
        dp = True

    # ---- full-config compile: proves sharding coherence + memory fit
    t0 = time.time()
    lowered = _build_lowered(cfg, shape, mesh, grad_accum=grad_accum,
                             loss_chunk=loss_chunk, sp=sp, dp=dp)
    t_lower = time.time() - t0
    t0 = time.time()
    compiled = lowered.compile()
    t_compile = time.time() - t0
    mem = compiled.memory_analysis()

    # ---- cost terms via unrolled depth extrapolation.  XLA cost analysis
    # counts while-loop bodies ONCE (verified), so the production graph
    # (scanned layers, scanned KV chunks, scanned loss chunks) undercounts.
    # Cost variants therefore unroll everything scanned: layers moved to the
    # unrolled remainder, naive (scan-free) attention, single-chunk loss —
    # all FLOP-equivalent to the production graph — at depths r=1,2, then
    # extrapolate linearly to the full depth.
    P_len, rem = len(cfg.pattern), len(cfg.remainder)
    R = cfg.pattern_repeats
    costs = []
    for r in (1, 2):
        cfg_r = cfg.replace(num_layers=P_len * r + rem).unrolled().replace(
            unroll_scans=True)
        low_r = _build_lowered(cfg_r, shape, mesh, grad_accum=1,
                               loss_chunk=loss_chunk, sp=sp, dp=dp)
        costs.append(_costs_of(low_r.compile()))
    cost_full = _extrapolate(costs[0], costs[1], max(R, 1) if P_len else 1)
    coll = cost_full["coll"]
    flops_dev = cost_full["flops"]
    bytes_dev = cost_full["bytes"]
    # roofline terms (seconds, per device = per step for SPMD)
    peaks = chip_peaks(TARGET_KIND)
    compute_s = flops_dev / peaks.flops_bf16
    memory_s = bytes_dev / peaks.hbm_bw
    collective_s = coll["total_bytes"] / peaks.ici_bw

    # useful-FLOPs model (6·N_active·tokens for train, 2·N_active·tokens fwd)
    n_active = cfg.active_param_count()
    tokens = spec.global_batch * (spec.seq_len if spec.kind != "decode" else 1)
    mult = 6 if spec.kind == "train" else 2
    model_flops = mult * n_active * tokens
    hlo_flops_global = flops_dev * n_chips

    art = {
        "arch": arch, "shape": shape, "mesh": _mesh_tag(multi_pod),
        "n_chips": n_chips, "skipped": False,
        "grad_accum": grad_accum, "loss_chunk": loss_chunk,
        "overrides": overrides or {}, "seq_parallel": sp, "pure_dp": dp,
        "t_lower_s": round(t_lower, 1), "t_compile_s": round(t_compile, 1),
        "memory_analysis": {
            "argument_bytes": mem.argument_size_in_bytes,
            "output_bytes": mem.output_size_in_bytes,
            "temp_bytes": mem.temp_size_in_bytes,
            "peak_bytes": (mem.argument_size_in_bytes + mem.temp_size_in_bytes
                           + mem.generated_code_size_in_bytes),
            "fits_16gb": (mem.argument_size_in_bytes + mem.temp_size_in_bytes)
                          < 16 * 1024**3,
        },
        "cost_analysis": {"flops_per_device": flops_dev,
                          "bytes_per_device": bytes_dev,
                          "bytes_per_device_unfused": cost_full["bytes_raw"]},
        "collectives": coll,
        "roofline": {
            "compute_s": compute_s, "memory_s": memory_s,
            "collective_s": collective_s,
            "dominant": max(
                [("compute", compute_s), ("memory", memory_s),
                 ("collective", collective_s)], key=lambda kv: kv[1])[0],
            "model_flops": model_flops,
            "hlo_flops_global": hlo_flops_global,
            "useful_ratio": model_flops / max(hlo_flops_global, 1.0),
            "roofline_fraction": (min(compute_s / max(
                max(compute_s, memory_s, collective_s), 1e-30), 1.0)),
        },
    }
    return art


def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch")
    ap.add_argument("--shape")
    ap.add_argument("--multi-pod", action="store_true")
    ap.add_argument("--all", action="store_true")
    ap.add_argument("--both-meshes", action="store_true")
    ap.add_argument("--force", action="store_true")
    ap.add_argument("--grad-accum", type=int, default=1)
    ap.add_argument("--loss-chunk", type=int, default=1024)
    ap.add_argument("--overrides", type=str, default=None,
                    help="JSON dict of ModelConfig overrides (perf experiments)")
    args = ap.parse_args()
    force_host_devices()

    from ..configs import all_cells
    cells = (all_cells() if args.all else [(args.arch, args.shape)])
    meshes = [False, True] if args.both_meshes else [args.multi_pod]
    overrides = json.loads(args.overrides) if args.overrides else None

    failures = 0
    for arch, shape in cells:
        for mp in meshes:
            path = artifact_path(arch, shape, mp)
            if os.path.exists(path) and not args.force:
                print(f"[skip] {arch} {shape} {_mesh_tag(mp)} (cached)")
                continue
            print(f"[cell] {arch} {shape} {_mesh_tag(mp)} ...", flush=True)
            try:
                art = run_cell(arch, shape, multi_pod=mp,
                               grad_accum=args.grad_accum,
                               loss_chunk=args.loss_chunk,
                               overrides=overrides)
            except Exception:
                failures += 1
                print(f"[FAIL] {arch} {shape} {_mesh_tag(mp)}")
                traceback.print_exc()
                continue
            with open(path, "w") as f:
                json.dump(art, f, indent=1)
            if art.get("skipped"):
                print(f"[skip-cell] {arch} {shape}: {art['reason']}")
            else:
                r = art["roofline"]
                print(f"[ok] {arch} {shape} {_mesh_tag(mp)} "
                      f"compile={art['t_compile_s']}s "
                      f"compute={r['compute_s']*1e3:.1f}ms "
                      f"mem={r['memory_s']*1e3:.1f}ms "
                      f"coll={r['collective_s']*1e3:.1f}ms "
                      f"dom={r['dominant']} useful={r['useful_ratio']:.2f}",
                      flush=True)
    return 1 if failures else 0


if __name__ == "__main__":
    sys.exit(main())
