"""The streaming engine's own time per epoch of the bulk ingest cell (ms):
self time of the ``ib.epoch`` span, that is the epoch's time outside every
operator and the store commit (hand-offs between threads, beginning the
epoch, liveness probes), median over the window's whole epochs."""
import os
import sys

sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))

import _spans  # noqa: E402


def read(rec):
    return _spans.epoch_median(rec, lambda ep: ep.self_ms("ib.epoch"))
