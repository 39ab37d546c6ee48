"""Numpy oracles for the ingest kernels (the exact-match targets)."""
from __future__ import annotations

import numpy as np

from ..erasure.gf256 import GF256


# ---------------------------------------------------------------- gf256
def gf256_matmul_ref(code: np.ndarray, data: np.ndarray) -> np.ndarray:
    """Table-based GF(2^8) matmul oracle.  code (P,K), data (K,N) uint8."""
    P, K = code.shape
    N = data.shape[1]
    out = np.zeros((P, N), np.uint8)
    for p in range(P):
        acc = np.zeros(N, np.uint8)
        for k in range(K):
            acc ^= GF256.mul(np.full(N, code[p, k], np.uint8), data[k])
        out[p] = acc
    return out


# ------------------------------------------------------------ pack tokens
def pack_tokens_ref(flat_tokens: np.ndarray, starts: np.ndarray,
                    lens: np.ndarray, seq_len: int, *, pad_id: int = 0):
    R = len(starts)
    toks = np.full((R, seq_len), pad_id, np.int32)
    seg = np.zeros((R, seq_len), np.int32)
    pos = np.zeros((R, seq_len), np.int32)
    for r in range(R):
        ln = min(int(lens[r]), seq_len)
        toks[r, :ln] = flat_tokens[int(starts[r]):int(starts[r]) + ln]
        seg[r, :ln] = 1
        pos[r, :ln] = np.arange(ln)
    return toks, seg, pos
