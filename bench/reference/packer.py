"""Plain packer of token documents into fixed-length training rows.

Documents are taken in order.  A document longer than a row is cut into
pieces of at most ``seq_len`` tokens.  Each piece goes into the current row
if it fits; otherwise the row is closed and the piece starts a new one (a
row that becomes exactly full is closed at once).  Within a row, pieces are
numbered 1, 2, ... (``segment_ids``), each piece's positions count from 0,
``loss_mask`` is 1 on tokens, and padding is ``pad_id`` with mask,
position and segment 0.  Rows are grouped into blocks of
``rows_per_block`` rows, the last block of a shard holding the rest.
"""
from __future__ import annotations

from typing import Dict, List, Sequence

import numpy as np

PLANES = ("tokens", "loss_mask", "positions", "segment_ids")


def plan_rows(docs: Sequence[np.ndarray], seq_len: int) -> List[List[np.ndarray]]:
    """The pieces of each row, in order."""
    rows: List[List[np.ndarray]] = []
    cur: List[np.ndarray] = []
    fill = 0
    for doc in docs:
        for off in range(0, len(doc), seq_len):
            piece = doc[off:off + seq_len]
            if cur and fill + len(piece) > seq_len:
                rows.append(cur)
                cur, fill = [], 0
            cur.append(piece)
            fill += len(piece)
            if fill == seq_len:
                rows.append(cur)
                cur, fill = [], 0
    if cur:
        rows.append(cur)
    return rows


def pack(docs: Sequence[np.ndarray], seq_len: int,
         pad_id: int = 0) -> Dict[str, np.ndarray]:
    """All four (rows, seq_len) int32 planes of one shard."""
    rows = plan_rows(docs, seq_len)
    out = {p: np.zeros((len(rows), seq_len), np.int32) for p in PLANES}
    out["tokens"][:] = pad_id
    for r, pieces in enumerate(rows):
        fill = 0
        for seg, piece in enumerate(pieces, start=1):
            n = len(piece)
            out["tokens"][r, fill:fill + n] = piece
            out["loss_mask"][r, fill:fill + n] = 1
            out["positions"][r, fill:fill + n] = np.arange(n)
            out["segment_ids"][r, fill:fill + n] = seg
            fill += n
    return out


def blocks(planes: Dict[str, np.ndarray],
           rows_per_block: int) -> List[Dict[str, np.ndarray]]:
    n = len(planes["tokens"])
    return [{p: v[i:i + rows_per_block] for p, v in planes.items()}
            for i in range(0, n, rows_per_block)]
