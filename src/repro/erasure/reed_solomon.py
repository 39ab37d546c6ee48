"""Systematic Reed-Solomon (k data + m parity) over GF(2^8).

Encoding:  parity = C @ data        (C: m×k Cauchy matrix, data: k×L bytes)
Recovery:  any k surviving rows of [I; C] are invertible — solve for the
           missing data rows, then recompute missing parity rows.
"""
from __future__ import annotations

from typing import Dict, List, Optional, Sequence, Tuple

import numpy as np

from .. import tracing
from .gf256 import GF256


class ReedSolomon:
    def __init__(self, k: int, m: int, use_pallas: bool = False) -> None:
        if k < 1 or m < 1:
            raise ValueError("need k >= 1 data and m >= 1 parity blocks")
        self.k, self.m = k, m
        self.C = GF256.cauchy_matrix(m, k)  # (m, k)
        self.use_pallas = use_pallas
        self._pallas_matmul = None
        if use_pallas:
            from ..kernels import ops as gf_ops  # lazy: jax import
            self._pallas_matmul = gf_ops.gf256_matmul

    # ------------------------------------------------------------------ encode
    def _matmul(self, A: np.ndarray, B: np.ndarray) -> np.ndarray:
        if self._pallas_matmul is not None:
            return np.asarray(self._pallas_matmul(A, B))
        return GF256.matmul(A, B)

    def encode(self, data: np.ndarray) -> np.ndarray:
        """data: (k, L) uint8 -> parity (m, L) uint8."""
        data = np.asarray(data, dtype=np.uint8)
        if data.shape[0] != self.k:
            raise ValueError(f"expected {self.k} data rows, got {data.shape[0]}")
        return self._matmul(self.C, data)

    def encode_payloads(self, payloads: Sequence[bytes]) -> Tuple[np.ndarray, int]:
        """Encode variable-length payloads: zero-pad to the max length (and to a
        multiple of 128 for kernel tile alignment); missing trailing blocks of a
        partial stripe are virtual zero blocks.  Returns (parity (m, L), L)."""
        L = max((len(p) for p in payloads), default=1)
        L = max(1, -(-L // 128) * 128)
        data = np.zeros((self.k, L), dtype=np.uint8)
        for i, p in enumerate(payloads):
            data[i, : len(p)] = np.frombuffer(p, dtype=np.uint8)
        return self.encode(data), L

    @staticmethod
    def stripe_pad(payloads: Sequence) -> int:
        """The padded stripe length ``encode_payloads`` would use — per-stripe
        max payload length rounded up to a multiple of 128."""
        L = max((len(p) for p in payloads), default=1)
        return max(1, -(-L // 128) * 128)

    def encode_payload_batch(
            self, stripes: Sequence[Sequence[np.ndarray]]
            ) -> List[Tuple[np.ndarray, int]]:
        """Batch twin of ``encode_payloads``: encode S stripes in one pass.

        ``stripes`` holds uint8 payload views (one inner list per stripe, up
        to ``k`` rows each; short stripes encode virtual zero blocks).  The S
        stripes share one stacked parity accumulator ``(m, sum L_s)`` — the
        numpy path XOR-accumulates constant-product table gathers straight
        from the payload buffers (no staged ``(k, S*L)`` matrix), the Pallas
        path stages the stacked matrix once and runs ``gf256_matmul`` over
        all stripes in a single kernel launch.  Per-stripe results are
        byte-identical to ``encode_payloads`` (same per-stripe padding), so
        the scalar path stays the correctness oracle.

        Returns ``[(parity (m, L_s) view, L_s), ...]``; the views alias the
        shared accumulator.
        """
        Ls = [self.stripe_pad(ps) for ps in stripes]
        offs = [0]
        for L in Ls:
            offs.append(offs[-1] + L)
        total = offs[-1]
        if self._pallas_matmul is not None:
            # bucketed width: the kernel compiles for a few shapes, not one
            # per batch; the zero columns encode to parity nobody reads
            from ..kernels.ops import bucket
            data = np.zeros((self.k, bucket(total)), dtype=np.uint8)
            for si, ps in enumerate(stripes):
                o = offs[si]
                for j, p in enumerate(ps):
                    data[j, o:o + len(p)] = p
            from ..core.items import as_device_array  # lazy: jax import
            # the span holds the copy in, the launch, the device work and
            # the copy back
            with tracing.span("ib.kernel.gf256_matmul"):
                parity = np.asarray(
                    self._pallas_matmul(self.C, as_device_array(data)))
        else:
            parity = np.zeros((self.m, total), dtype=np.uint8)
            for si, ps in enumerate(stripes):
                o = offs[si]
                for j, p in enumerate(ps):
                    for i in range(self.m):
                        GF256.xor_mul_into(parity[i, o:], int(self.C[i, j]), p)
        return [(parity[:, offs[s]:offs[s] + Ls[s]], Ls[s])
                for s in range(len(stripes))]

    # ------------------------------------------------------------------ decode
    def reconstruct(self, shards: Dict[int, np.ndarray]) -> np.ndarray:
        """Rebuild the full (k, L) data matrix from any >= k surviving shards.

        ``shards`` maps stripe position -> row bytes; positions 0..k-1 are data
        rows, k..k+m-1 are parity rows.
        """
        if len(shards) < self.k:
            raise ValueError(f"need at least {self.k} shards, have {len(shards)}")
        L = len(next(iter(shards.values())))
        G = np.concatenate([np.eye(self.k, dtype=np.uint8), self.C], axis=0)  # (k+m, k)
        pos = sorted(shards)[: self.k]
        A = G[pos]                                  # (k, k) rows we actually have
        Y = np.stack([np.frombuffer(np.asarray(shards[p], dtype=np.uint8).tobytes(),
                                    dtype=np.uint8) for p in pos])  # (k, L)
        A_inv = GF256.mat_inv(A)
        return self._matmul(A_inv, Y)               # (k, L) original data rows

    def recover_block(self, missing_pos: int, shards: Dict[int, np.ndarray]) -> np.ndarray:
        """Recover one missing stripe row (data or parity) from survivors."""
        data = self.reconstruct(shards)
        if missing_pos < self.k:
            return data[missing_pos]
        return self.encode(data)[missing_pos - self.k]
