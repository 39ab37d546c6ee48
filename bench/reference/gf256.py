"""Plain Reed-Solomon parity over GF(2^8), written from the definition.

The field is GF(2)[x] / (x^8 + x^4 + x^3 + x + 1) (0x11B, the AES
polynomial), generated by 3.  A systematic RS(k, m) stripe of k data rows
gets m parity rows ``parity = C @ data``, with the Cauchy matrix
``C[i, j] = 1 / (x_i + y_j)`` over ``x_i = k + i`` and ``y_j = j``.  The
tables below are built here, from the polynomial, and nothing is taken
from the program under test.
"""
from __future__ import annotations

import numpy as np

POLY = 0x11B
GENERATOR = 3


def _tables():
    exp = [0] * 510
    log = [0] * 256
    x = 1
    for i in range(255):
        exp[i] = x
        log[x] = i
        doubled = x << 1
        if doubled & 0x100:
            doubled ^= POLY
        x = doubled ^ x              # x * 3 = x * 2 + x
    for i in range(255, 510):
        exp[i] = exp[i - 255]
    return exp, log


EXP, LOG = _tables()


def mul(a: int, b: int) -> int:
    if a == 0 or b == 0:
        return 0
    return EXP[LOG[a] + LOG[b]]


def inv(a: int) -> int:
    if a == 0:
        raise ZeroDivisionError("0 has no inverse in GF(2^8)")
    return EXP[255 - LOG[a]]


def cauchy(m: int, k: int) -> np.ndarray:
    """(m, k) Cauchy code matrix of a systematic RS(k, m) code."""
    return np.array([[inv((k + i) ^ j) for j in range(k)] for i in range(m)],
                    dtype=np.uint8)


#: MUL_TABLE[c, b] = c * b
MUL_TABLE = np.array([[mul(c, b) for b in range(256)] for c in range(256)],
                     dtype=np.uint8)


def encode(code: np.ndarray, data: np.ndarray) -> np.ndarray:
    """``code`` (m, k) uint8, ``data`` (k, L) uint8 -> parity (m, L) uint8."""
    m, k = code.shape
    parity = np.zeros((m, data.shape[1]), np.uint8)
    for i in range(m):
        for j in range(k):
            c = int(code[i, j])
            if c:
                parity[i] ^= MUL_TABLE[c][data[j]]
    return parity


def stripe_parity(payloads, k: int, m: int) -> np.ndarray:
    """Parity of one stripe of up to ``k`` data payloads (bytes), each
    zero-padded to the stripe's longest payload rounded up to 128 bytes;
    missing trailing members are all-zero rows."""
    width = max(len(p) for p in payloads)
    width = -(-width // 128) * 128
    data = np.zeros((k, width), np.uint8)
    for j, p in enumerate(payloads):
        data[j, :len(p)] = np.frombuffer(p, np.uint8)
    return encode(cauchy(m, k), data)
