"""``gf256_matmul`` share of its memory roofline in the traced window (%).

Device time: every op of the jitted ``kernels.ops.gf256_matmul`` program,
with the casts and padding XLA runs around the kernel.  Bytes: (k + m)
times the unpadded stripe columns (``bench/costs/gf256_matmul.py``).
"""
import os
import sys

sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import _kernels  # noqa: E402
from costs import gf256_matmul as cost  # noqa: E402


def read(rec):
    return _kernels.roofline_pct(
        rec, "jit_gf256_matmul",
        lambda e: cost.bytes_moved(rec["k"], rec["m"], e["parity_cols"]))
