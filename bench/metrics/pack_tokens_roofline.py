"""``pack_tokens`` share of its memory roofline in the traced window (%).

Device time: every op of the jitted ``kernels.ops.pack_tokens`` program,
the Mosaic kernel and whatever XLA runs around it.  Bytes: the call's
useful bytes from its true rows and tokens (``bench/costs/pack_tokens.py``).
"""
import os
import sys

sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import _kernels  # noqa: E402
from costs import pack_tokens as cost  # noqa: E402


def read(rec):
    return _kernels.roofline_pct(
        rec, "jit_pack_tokens",
        lambda e: cost.bytes_moved(e["rows"], e["tokens"], rec["seq_len"]))
