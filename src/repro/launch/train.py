"""End-to-end training driver: ingest -> feed -> pjit train -> checkpoint.

This is the production entry point; the same code path scales from the CPU
smoke configs (mesh 1x1) to the 256-chip pod (mesh 16x16) — only the mesh
and config change.  The data plane is INGESTBASE end to end:

  1. raw token documents are ingested once via the canonical LM plan
     (parse -> pack into device-shaped blocks -> serialize -> store),
  2. the BlockFeeder replays ingested blocks as train batches through
     ingestion-aware access (filterReplica("serialize","packed") +
     splitByKey over feeder tasks + projection pushdown),
  3. the train loop jits the step with production shardings, checkpoints
     asynchronously, and restores elastically (a checkpoint written on one
     mesh restores onto another).

Usage (CPU example — also examples/train_smollm.py):
  python -m repro.launch.train --arch smollm-135m --smoke --steps 200
"""
import argparse
import time
from collections import Counter
from dataclasses import dataclass
from typing import Any, Callable, Dict

import jax
import numpy as np

from .. import tracing

BATCH_FIELDS = ("tokens", "labels", "segments", "positions")


def build_mesh(spec: str):
    from .mesh import make_mesh, make_production_mesh
    if spec == "production":
        return make_production_mesh()
    if spec == "multipod":
        return make_production_mesh(multi_pod=True)
    shape = tuple(int(x) for x in spec.split("x"))
    return make_mesh(shape, ("data", "model")[:len(shape)])


def make_batch(raw, seq_len: int, pad_id: int = 0):
    """BlockFeeder fields -> model batch (next-token labels from tokens)."""
    with tracing.span("ib.train.make_batch"):
        toks = raw["tokens"].astype(np.int32)
        seg = raw["segment_ids"].astype(np.int32)
        pos = raw["positions"].astype(np.int32)
        mask = raw["loss_mask"].astype(np.int32)
        labels = np.concatenate([toks[:, 1:], np.full((toks.shape[0], 1), -1,
                                                      np.int32)], axis=1)
        # don't predict across packing boundaries
        labels = np.where((seg == np.concatenate(
            [seg[:, 1:], np.zeros((seg.shape[0], 1), np.int32)], axis=1))
            & (mask > 0), labels, -1)
        return {"tokens": toks, "labels": labels, "segments": seg,
                "positions": pos}


@dataclass
class Trainer:
    """The production train step for one mesh, with its shardings:
    parameters and optimizer state by the sharding rules, the batch split
    over the data axis."""
    step: Callable            # jitted (params, opt_state, batch) -> (..., metrics)
    init_opt: Callable
    pdefs: Any
    odefs: Any
    params_sharding: Any
    opt_sharding: Any
    batch_sharding: Dict[str, Any]

    def init_state(self, seed: int = 0):
        """Random parameters from ``seed`` and fresh optimizer state, both
        placed by their shardings."""
        from ..models.params import init_params
        params = init_params(jax.random.PRNGKey(seed), self.pdefs)
        params = jax.tree.map(jax.device_put, params, self.params_sharding)
        opt_state = jax.jit(self.init_opt,
                            out_shardings=self.opt_sharding)(params)
        return params, opt_state

    def put_batch(self, batch: Dict[str, np.ndarray]) -> Dict[str, jax.Array]:
        """A host batch -> device arrays laid out by ``batch_sharding``."""
        with tracing.span("ib.train.put_batch"):
            return {k: jax.device_put(batch[k], self.batch_sharding[k])
                    for k in BATCH_FIELDS}


def make_trainer(cfg, mesh, *, global_batch: int, seq_len: int, lr: float,
                 loss_chunk: int = 1024, grad_accum: int = 1) -> Trainer:
    """jit the train step for ``mesh`` with production shardings."""
    from jax.sharding import NamedSharding
    from jax.sharding import PartitionSpec as P

    from ..models.model import model_defs
    from ..models.params import param_specs
    from ..training.optim import make_optimizer, opt_state_defs
    from ..training.steps import make_train_step
    from .mesh import (input_shardings, make_constrain, mesh_axis_sizes,
                       sharding_rules)

    rules = sharding_rules(cfg, mesh, global_batch=global_batch)
    sizes = mesh_axis_sizes(mesh)
    named = lambda t: jax.tree.map(lambda s: NamedSharding(mesh, s), t,
                                   is_leaf=lambda x: isinstance(x, P))
    pdefs = model_defs(cfg)
    pshard = named(param_specs(pdefs, rules, sizes))
    odefs = opt_state_defs(cfg.optimizer, pdefs)
    oshard = named(param_specs(odefs, rules, sizes))
    spec = jax.ShapeDtypeStruct((global_batch, seq_len), np.int32)
    bshard = input_shardings(mesh, {k: spec for k in BATCH_FIELDS})

    step_fn = make_train_step(
        cfg, loss_chunk=min(loss_chunk, seq_len), grad_accum=grad_accum,
        optimizer_kw={"lr": lr},
        constrain=make_constrain(mesh, cfg, global_batch),
        grad_shardings=pshard)
    jitted = jax.jit(step_fn, in_shardings=(pshard, oshard, bshard),
                     out_shardings=(pshard, oshard, None),
                     donate_argnums=(0, 1))
    init_opt, _, _ = make_optimizer(cfg.optimizer, lr=lr)
    return Trainer(jitted, init_opt, pdefs, odefs, pshard, oshard, bshard)


def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="smollm-135m")
    ap.add_argument("--smoke", action="store_true",
                    help="use the reduced same-family config (CPU)")
    ap.add_argument("--steps", type=int, default=100)
    ap.add_argument("--batch", type=int, default=8)
    ap.add_argument("--seq-len", type=int, default=256)
    ap.add_argument("--mesh", default="1x1",
                    help='"RxC", "production" (16x16) or "multipod"')
    ap.add_argument("--lr", type=float, default=3e-3)
    ap.add_argument("--docs", type=int, default=2000)
    ap.add_argument("--ckpt-dir", default="/tmp/repro_ckpt")
    ap.add_argument("--ckpt-every", type=int, default=50)
    ap.add_argument("--resume", action="store_true")
    ap.add_argument("--data-dir", default="/tmp/repro_corpus")
    ap.add_argument("--log-every", type=int, default=10)
    ap.add_argument("--grad-accum", type=int, default=1)
    args = ap.parse_args()

    from ..configs import get_config, get_smoke
    from ..core import DataStore
    from ..data.feeder import BlockFeeder, ingest_corpus
    from ..data.generators import gen_token_documents
    from ..models.model import ATTENTION_PATHS, ATTENTION_RESIDUALS
    from ..models.params import abstract_params
    from ..training.checkpoint import CheckpointManager
    from .compile_cache import enable_compile_cache

    enable_compile_cache()
    cfg = get_smoke(args.arch) if args.smoke else get_config(args.arch)
    mesh = build_mesh(args.mesh)
    print(f"[train] arch={cfg.name} params={cfg.param_count()/1e6:.1f}M "
          f"mesh={dict(zip(mesh.axis_names, mesh.devices.shape))}")

    # ------------------------------------------------------ 1. ingest corpus
    store = DataStore(args.data_dir, nodes=["n0", "n1", "n2", "n3"])
    if not store.blocks():
        docs = gen_token_documents(args.docs, vocab=cfg.vocab_size,
                                   max_len=args.seq_len)
        rep = ingest_corpus(docs, store, seq_len=args.seq_len,
                            rows_per_block=max(8, args.batch))
        print(f"[ingest] stages={rep.stage_items} wall={rep.wall_time_s:.2f}s")

    # ------------------------------------------------------ 2. feeder
    feeder = BlockFeeder(store, num_tasks=1, task=0, batch_rows=args.batch)
    print(f"[feed] {len(feeder)} packed blocks available")

    # ------------------------------------------------------ 3. jit the step
    trainer = make_trainer(cfg, mesh, global_batch=args.batch,
                           seq_len=args.seq_len, lr=args.lr,
                           grad_accum=args.grad_accum)

    # ------------------------------------------------------ 4. init / restore
    ckpt = CheckpointManager(args.ckpt_dir, keep=3, async_write=True)
    start = 0
    if args.resume and ckpt.latest_step() is not None:
        start = ckpt.latest_step()
        pabs = abstract_params(trainer.pdefs)
        oabs = abstract_params(trainer.odefs)
        params = ckpt.restore(start, {"params": pabs})["params"]
        params = jax.tree.map(jax.device_put, params, trainer.params_sharding)
        opt_state = ckpt.restore(start, {"opt": oabs})["opt"]
        opt_state = jax.tree.map(jax.device_put, opt_state,
                                 trainer.opt_sharding)
        feeder.step = start
        print(f"[restore] resumed from step {start} (elastic across meshes)")
    else:
        params, opt_state = trainer.init_state(seed=0)

    # ------------------------------------------------------ 5. train loop
    t0 = time.time()
    losses = []
    traced = Counter(ATTENTION_PATHS)
    traced_residuals = Counter(ATTENTION_RESIDUALS)
    for i, raw in enumerate(feeder.batches(args.steps)):
        batch = trainer.put_batch(make_batch(raw, args.seq_len))
        params, opt_state, metrics = trainer.step(params, opt_state, batch)
        step = start + i + 1
        if i == 0:   # the first call traced the step
            print(f"[train] attention calls traced by path: "
                  f"{dict(ATTENTION_PATHS - traced)}, kernel residuals: "
                  f"{dict(ATTENTION_RESIDUALS - traced_residuals)}")
        losses.append(float(metrics["loss"]))
        if step % args.log_every == 0:
            dt = (time.time() - t0) / (i + 1)
            print(f"[step {step:5d}] loss={losses[-1]:.4f} "
                  f"xent={float(metrics['xent']):.4f} "
                  f"gnorm={float(metrics['grad_norm']):.3f} "
                  f"{dt*1e3:.0f} ms/step", flush=True)
        if step % args.ckpt_every == 0:
            ckpt.save(step, {"params": params, "opt": opt_state})
    ckpt.save(start + args.steps, {"params": params, "opt": opt_state},
              blocking=True)
    print(f"[done] {args.steps} steps; loss {losses[0]:.3f} -> {losses[-1]:.3f}")
    return 0 if losses[-1] < losses[0] else 1


if __name__ == "__main__":
    raise SystemExit(main())
