"""Pallas TPU kernel: pack ragged documents into fixed-shape training rows.

Serialize/pack is the paper's hottest CPU ingest operator (Sec. VI-A runs it
multi-threaded).  On TPU the same transform is a tiled gather: given the flat
token stream and a (row -> [start, len)) table produced by the packer's
first-fit pass, emit the (R, S) packed token matrix plus the valid-mask and
position planes, with padding masked.

Layout: grid = (R_pad // ROWS,).  ``starts``/``lens`` are scalar-prefetched
into SMEM.  The flat stream stays in HBM as a (1, T) array; each grid step
DMAs, for each of its ROWS rows, the lane-aligned window
``[start - start % 128, +W)`` into a VMEM scratch tile, rotates it left by
``start % 128`` and keeps the first Sp lanes (Sp = S rounded up to 128
lanes, W = Sp + 128; the wrapper cuts the rows back to S).  VMEM use is
therefore independent of the corpus size.  Output blocks are (ROWS, Sp): a
whole (8, 128) sublane tile per lane column.

(A row's documents are contiguous in the flat stream by construction — the
packer writes them that way — so one window per row suffices.)
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

ROWS = 8    # rows per grid step: the int32 sublane count of one vreg tile
LANES = 128  # HBM slices along the lane dim must start on a lane tile


def _kernel(starts_ref, lens_ref, toks_hbm, out_ref, seg_ref, pos_ref,
            buf, sem, *, S: int, W: int, pad_id: int):
    base = pl.program_id(0) * ROWS
    copies = []
    for i in range(ROWS):
        start = starts_ref[base + i]
        aligned = pl.multiple_of(start - start % LANES, LANES)
        c = pltpu.make_async_copy(toks_hbm.at[:, pl.ds(aligned, W)],
                                  buf.at[i], sem.at[i])
        c.start()
        copies.append(c)
    Sp = out_ref.shape[1]
    col = jax.lax.broadcasted_iota(jnp.int32, (1, Sp), 1)
    for i, c in enumerate(copies):
        c.wait()
        shift = starts_ref[base + i] % LANES
        # rotate left by shift: the row then starts at lane 0
        window = pltpu.roll(buf[i], (W - shift) % W, axis=1)[:, :Sp]
        valid = (col < lens_ref[base + i]) & (col < S)
        out_ref[pl.ds(i, 1), :] = jnp.where(valid, window, pad_id)
        seg_ref[pl.ds(i, 1), :] = valid.astype(jnp.int32)
        pos_ref[pl.ds(i, 1), :] = jnp.where(valid, col, 0)


def pack_tokens(flat_tokens: jax.Array, starts: jax.Array, lens: jax.Array,
                seq_len: int, *, pad_id: int = 0, interpret: bool = False):
    """flat_tokens (T,) int32; starts/lens (R,) int32 -> (tokens, valid, pos)
    each (R, seq_len) int32."""
    R = starts.shape[0]
    Rp = -(-R // ROWS) * ROWS
    Sp = -(-seq_len // LANES) * LANES   # lane-dense rows, cut to seq_len
    W = Sp + LANES
    T = flat_tokens.shape[0]
    # over-read pad: an aligned window may run up to W past the last token
    toks = jnp.pad(flat_tokens.astype(jnp.int32),
                   (0, -(-T // LANES) * LANES - T + W)).reshape(1, -1)
    # padding rows read an empty window at 0 and come out all pad
    starts = jnp.pad(starts.astype(jnp.int32), (0, Rp - R))
    lens = jnp.pad(lens.astype(jnp.int32), (0, Rp - R))
    row_block = pl.BlockSpec((ROWS, Sp), lambda r, *_: (r, 0))
    out, seg, pos = pl.pallas_call(
        functools.partial(_kernel, S=seq_len, W=W, pad_id=pad_id),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=2,
            grid=(Rp // ROWS,),
            in_specs=[pl.BlockSpec(memory_space=pl.ANY)],
            out_specs=[row_block] * 3,
            scratch_shapes=[pltpu.VMEM((ROWS, 1, W), jnp.int32),
                            pltpu.SemaphoreType.DMA((ROWS,))],
        ),
        out_shape=[jax.ShapeDtypeStruct((Rp, Sp), jnp.int32)] * 3,
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel",)),
        interpret=interpret,
    )(starts, lens, toks)
    return out[:R, :seq_len], seg[:R, :seq_len], pos[:R, :seq_len]
