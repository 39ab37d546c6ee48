"""Work of one ``pack_tokens`` call, from its true (unpadded) sizes.

The call reads the tokens it places (4 bytes each) and the row table
(start and length, 4 bytes each per row), and writes three (rows, seq_len)
int32 planes: tokens, valid mask and positions.  Padding added to reach a
bucketed shape is not counted: it is work the algorithm does not need.
There are no arithmetic operations to speak of, so bytes bound it.
"""
from __future__ import annotations


def bytes_moved(rows: int, tokens: int, seq_len: int) -> int:
    return 4 * tokens + 8 * rows + 3 * 4 * rows * seq_len


def flops(rows: int, tokens: int, seq_len: int) -> int:
    return 0
