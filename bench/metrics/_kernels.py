"""Shared reduction for the ingest kernels' metrics: the device time of a
jitted kernel's executions in the traced window, matched to the epochs that
made them.

Each epoch runs each ingest kernel once (one launch per node over the
epoch's batch, and the benchmark runs one node), and the trace runs until
the stream has drained, so the n executions in the window belong to the
last n epochs.
"""
from __future__ import annotations

import os
import sys
from typing import Any, Callable, Dict, Optional, Tuple

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import devtrace  # noqa: E402
import peaks  # noqa: E402


def traced_work(rec: Dict[str, Any], module: str,
                bytes_of: Callable[[Dict[str, Any]], float]
                ) -> Optional[Tuple[float, float]]:
    """(useful bytes, device seconds) of ``module``'s executions in the
    traced window, or None where it did not run there."""
    t = rec.get("trace")
    if t is None:
        return None
    lo, hi = rec["trace_window"]
    calls = devtrace.module_calls(t.device_modules[rec["trace_planes"][0]],
                                  module, lo, hi)
    epochs = sorted(rec["epochs"], key=lambda e: e["epoch"])
    if not calls or len(calls) > len(epochs):
        return None
    mine = epochs[-len(calls):]
    seconds = sum(c.end - c.start for c in calls)
    return sum(bytes_of(e) for e in mine), seconds


def roofline_pct(rec: Dict[str, Any], module: str,
                 bytes_of: Callable[[Dict[str, Any]], float]) -> Optional[float]:
    """Share of the memory roofline: least time at peak HBM bandwidth over
    the measured device time, in %."""
    work = traced_work(rec, module, bytes_of)
    if work is None or work[1] <= 0:
        return None
    bw = peaks.peaks(rec["device"]["kind"]).hbm_bytes_per_s
    return 100.0 * (work[0] / bw) / work[1]
