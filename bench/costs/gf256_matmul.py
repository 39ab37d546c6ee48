"""Work of one ``gf256_matmul`` call, from its true (unpadded) sizes.

RS(k, m) over ``columns`` stripe columns reads k data bytes and writes m
parity bytes per column: (k + m) * columns bytes.  This counts the
algorithm's bytes, not the kernel's working set (it widens each byte to an
int32 lane), so a kernel that keeps bytes packed is judged on the same work.
The GF(2^8) multiply-adds (m * k per column) have no published peak on the
chip, so the roofline here is the memory bound alone.
"""
from __future__ import annotations


def bytes_moved(k: int, m: int, columns: int) -> int:
    return (k + m) * columns


def gf_macs(k: int, m: int, columns: int) -> int:
    return m * k * columns
