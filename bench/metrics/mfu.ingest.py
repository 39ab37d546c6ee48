"""Whole-window share of the chip's peak in an ingest cell (%).

The least time both ingest kernels' useful work needs at the chip's peak
(bytes over peak HBM bandwidth; neither has FLOPs to count), summed over
the traced window, over the window's length.  It bounds every kernel's
roofline gain: a kernel taken off the path falls silent in its own
roofline metric but not here.
"""
import os
import sys

sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import _kernels  # noqa: E402
import peaks  # noqa: E402
from costs import gf256_matmul, pack_tokens  # noqa: E402


def read(rec):
    if rec.get("trace") is None:
        return None
    S, k, m = rec["seq_len"], rec["k"], rec["m"]
    work = [_kernels.traced_work(rec, "jit_pack_tokens",
                                 lambda e: pack_tokens.bytes_moved(
                                     e["rows"], e["tokens"], S)),
            _kernels.traced_work(rec, "jit_gf256_matmul",
                                 lambda e: gf256_matmul.bytes_moved(
                                     k, m, e["parity_cols"]))]
    useful = sum(w[0] for w in work if w is not None)
    if useful <= 0:
        return None
    bw = peaks.peaks(rec["device"]["kind"]).hbm_bytes_per_s
    return 100.0 * (useful / bw) / rec["window_s"]
