"""Grouped matrix product for the dropless expert layer, on the TPU.

``out[rows of group g] = lhs[rows of group g] @ rhs[g]``: rows sorted by
group, ``group_sizes[g]`` rows each, group 0 first.  Rows past
``sum(group_sizes)`` belong to no group; the kernel does not visit them and
their output is undefined (the caller masks them with ``where``, never a
product, so that whatever the buffer held cannot leak).

Built on JAX's megablox kernels (``jax.experimental.pallas.ops.tpu.
megablox``): ``gmm`` forward, and for the backward ``gmm`` against the
transposed weights (the input's gradient) and ``tgmm`` (the weights'
gradient, one ``lhs^T @ grad`` per group).  The kernels walk only the row
tiles that hold a group's rows, so their work follows the routed load, not
the static bound of the buffer.  Accumulation is float32; the output takes
the input's dtype.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
from jax.experimental.pallas.ops.tpu.megablox import ops as megablox

# (rows, contraction, output) tile; a dimension smaller than its tile is
# taken whole
TILING = (512, 512, 512)


def grouped_matmul(lhs: jax.Array, rhs: jax.Array, group_sizes: jax.Array, *,
                   interpret: bool = False) -> jax.Array:
    """lhs (m, k), rhs (groups, k, n), group_sizes (groups,) int32 ->
    (m, n) in ``lhs.dtype``.  ``m`` is padded to a whole number of row
    tiles inside; the padding belongs to no group."""
    m, k = lhs.shape
    n = rhs.shape[2]
    tm = min(TILING[0], -(-m // 8) * 8)
    pad = -m % tm
    if pad:
        lhs = jnp.pad(lhs, ((0, pad), (0, 0)))
    tiling = (tm, min(TILING[1], k), min(TILING[2], n))
    out = megablox.gmm(lhs, rhs, group_sizes.astype(jnp.int32), lhs.dtype,
                       tiling, None, None, False, interpret)
    return out[:m] if pad else out
