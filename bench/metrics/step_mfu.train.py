"""Model FLOP/s utilization of the fed train step over the window (%):
steps x the model's FLOPs per step (``bench/costs/decoder_step.py``: 6 N
plus causal attention per token slot, no recompute) over the window's host
time x chips x the chip's bf16 peak."""
import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import peaks  # noqa: E402


def read(rec):
    if not rec.get("steps") or not rec.get("host_window_s"):
        return None
    peak = peaks.peaks(rec["device"]["kind"]).flops_bf16
    return 100.0 * rec["steps"] * rec["flops_per_step"] / (
        rec["host_window_s"] * rec["chips"] * peak)
