"""Shared reduction for the train step's kernel metrics: the device time
of the traced window's operations whose names start with a kernel's name,
and their count."""
from __future__ import annotations

import os
import sys
from typing import Any, Dict, Optional, Sequence, Tuple

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import peaks  # noqa: E402


def kernel_events(rec: Dict[str, Any], prefixes: Sequence[str]
                  ) -> Dict[str, Tuple[int, float]]:
    """{prefix: (events, device seconds)} of the operations in the traced
    window named ``<prefix>...`` on the first traced chip; empty where no
    trace was taken."""
    t = rec.get("trace")
    if t is None:
        return {}
    lo, hi = rec["trace_window"]
    out: Dict[str, Tuple[int, float]] = {}
    for e in t.device_ops[rec["trace_planes"][0]]:
        if e.start < lo or e.end > hi:
            continue
        name = e.name.lstrip("%")
        for p in prefixes:
            if name.startswith(p):
                n, s = out.get(p, (0, 0.0))
                out[p] = (n + 1, s + e.end - e.start)
                break
    return out


def roofline_pct(rec: Dict[str, Any], flops: float, seconds: float
                 ) -> Optional[float]:
    """Share of the bf16 compute roofline: least time at peak over the
    measured device time, in %."""
    if seconds <= 0 or flops <= 0:
        return None
    return 100.0 * flops / peaks.peaks(rec["device"]["kind"]).flops_bf16 / seconds
