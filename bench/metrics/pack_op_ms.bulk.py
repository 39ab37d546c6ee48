"""Host time of the pack operator per epoch of the bulk ingest cell (ms):
self time of the ``ib.op.PackOp`` spans (first-fit planning, positions and
segment ids, block assembly; the kernel call is its child and left out),
median over the window's whole epochs."""
import os
import sys

sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))

import _spans  # noqa: E402


def read(rec):
    return _spans.epoch_median(rec, lambda ep: ep.self_ms("ib.op.PackOp"))
