"""Block write rate of the bulk ingest cell (MB/s): the payload bytes the
upload operator wrote (data and parity, its ``bytes`` attr) over the time
of its ``ib.op.UploadOp`` spans, per epoch, median over the window's whole
epochs."""
import os
import sys

sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))

import _spans  # noqa: E402


def _rate(ep):
    ms = ep.total_ms("ib.op.UploadOp")
    if not ms:
        return None
    return ep.attr("ib.op.UploadOp", "bytes") / 1e6 / (ms / 1e3)


def read(rec):
    return _spans.epoch_median(rec, _rate)
