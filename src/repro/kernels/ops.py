"""Jit'd public wrappers for the Pallas kernels.

``interpret`` defaults to the backend: the real kernels on a TPU, the Pallas
interpreter on the CPU (tests).  Any other backend is an error — a run that
lost its chip must not pass in interpret mode.

Where each kernel runs: the ingest plan's pack and erasure operators take
``pack_tokens`` and ``gf256_matmul`` when built with ``use_pallas``; the
train step's self-attention takes ``flash_attention`` on a TPU wherever the
kernel applies (``models.model._attention_path``); the dropless expert layer
(``models.moe.moe_dropless``) takes ``grouped_matmul`` on every backend.
"""
from __future__ import annotations

from functools import partial

import jax

from .flash_attention import flash_attention as _flash
from .gf256_matmul import gf256_matmul as _gf256
from .grouped_matmul import grouped_matmul as _gmm
from .pack_tokens import pack_tokens as _pack


def bucket(n: int, minimum: int = 128) -> int:
    """Round ``n`` up to one of four sizes per octave (at most 25% padding).
    Host callers pad a kernel's varying dimension to it, so a stream of
    batches of every size compiles a handful of shapes, not one per batch."""
    n = max(n, minimum)
    step = 1 << max(0, n.bit_length() - 3)
    return -(-n // step) * step


def _default_interpret() -> bool:
    backend = jax.default_backend()
    if backend == "tpu":
        return False
    if backend == "cpu":
        return True
    raise RuntimeError(f"Pallas kernels run on a TPU, or interpreted on the "
                       f"CPU; the default backend is {backend!r}")


@partial(jax.jit, static_argnames=("block_n", "interpret"))
def gf256_matmul(code, data, *, block_n: int = 2048, interpret: bool = None):
    if interpret is None:
        interpret = _default_interpret()
    return _gf256(code, data, block_n=block_n, interpret=interpret)


@partial(jax.jit, static_argnames=("interpret",))
def flash_attention(q, k, v, seg, *, interpret: bool = None):
    if interpret is None:
        interpret = _default_interpret()
    return _flash(q, k, v, seg, interpret=interpret)


def grouped_matmul(lhs, rhs, group_sizes, *, interpret: bool = None):
    if interpret is None:
        interpret = _default_interpret()
    return _gmm(lhs, rhs, group_sizes, interpret=interpret)


@partial(jax.jit, static_argnames=("seq_len", "pad_id", "interpret"))
def pack_tokens(flat_tokens, starts, lens, seq_len: int, *, pad_id: int = 0,
                interpret: bool = None):
    if interpret is None:
        interpret = _default_interpret()
    return _pack(flat_tokens, starts, lens, seq_len, pad_id=pad_id,
                 interpret=interpret)
