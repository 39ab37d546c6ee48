"""Faults and controls planted under a run, so that the correctness check
can be seen to fail.

Each plant patches the program under test where the timed path calls it,
for as long as its context lasts; the harness and the drivers run
unchanged on top.  The check must come out false under every one:

- ``control`` -- the step a later change might be tempted by: ingest
  writes RS(10, m - 1) parity where the configuration states m parity
  blocks (a guarantee broken); for training the control is the reference
  computed in fp8 in the program's place (``bench/control.py``);
- ``state_unchanged`` -- the step returns its state unchanged;
- ``half_batch`` -- half of each batch is left out, the mean taken over the
  rest;
- ``token_altered`` -- a token is altered where it is produced.

The exchange between chips is not planted: no cell of this benchmark runs
on more than one chip.
"""
from __future__ import annotations

import dataclasses
from contextlib import ExitStack, contextmanager
from typing import Any, Iterator

INGEST = ("control", "state_unchanged", "half_batch", "token_altered")
TRAIN = ("state_unchanged", "half_batch", "token_altered")


@contextmanager
def patched(obj: Any, name: str, value: Any) -> Iterator[None]:
    old = getattr(obj, name)
    setattr(obj, name, value)
    try:
        yield
    finally:
        setattr(obj, name, old)


def _ingest(name: str, stack: ExitStack) -> None:
    from repro.core.ops_format import PackOp
    from repro.data import feeder
    from repro.kernels import ops
    if name == "control":
        build = feeder.build_lm_plan

        def fewer_parity(store, **kw):
            ec = dict(kw["erasure"])
            ec["m"] -= 1
            return build(store, **dict(kw, erasure=ec))
        stack.enter_context(patched(feeder, "build_lm_plan", fewer_parity))
    elif name == "state_unchanged":
        stack.enter_context(patched(PackOp, "process_batch",
                                    lambda self, items: []))
    elif name == "half_batch":
        batch = PackOp.process_batch
        stack.enter_context(patched(
            PackOp, "process_batch",
            lambda self, items: batch(self, list(items)[:len(items) // 2])))
    elif name == "token_altered":
        pack = ops.pack_tokens

        def altered(*a, **kw):
            toks, mask, pos = pack(*a, **kw)
            return toks.at[:, 0].add(1), mask, pos
        stack.enter_context(patched(ops, "pack_tokens", altered))
    else:
        raise KeyError(f"no ingest plant {name!r}")


def _train(name: str, stack: ExitStack) -> None:
    import jax
    from repro.launch import train as program
    if name == "state_unchanged":
        make = program.make_trainer

        def unchanged(*a, **kw):
            t = make(*a, **kw)
            inner = t.step
            return dataclasses.replace(t, step=jax.jit(
                lambda p, o, b: (p, o, inner(p, o, b)[2])))
        stack.enter_context(patched(program, "make_trainer", unchanged))
    elif name in ("half_batch", "token_altered"):
        make_batch = program.make_batch

        def broken(raw, seq_len, **kw):
            out = make_batch(raw, seq_len, **kw)
            if name == "half_batch":
                out["labels"] = out["labels"].copy()
                out["labels"][len(out["labels"]) // 2:] = -1
            else:
                out["tokens"] = out["tokens"].copy()
                out["tokens"][0] ^= 1
            return out
        stack.enter_context(patched(program, "make_batch", broken))
    else:
        raise KeyError(f"no train plant {name!r}")


@contextmanager
def plant(driver: str, name: str) -> Iterator[None]:
    """Plant fault ``name`` under a driver (``ingest`` or ``train``)."""
    with ExitStack() as stack:
        {"ingest": _ingest, "train": _train}[driver](name, stack)
        yield
