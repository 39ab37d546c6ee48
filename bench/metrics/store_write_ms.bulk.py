"""Store write time per epoch of the bulk ingest cell (ms): the upload
operator's block writes (``ib.op.UploadOp``) and the epoch's commit
(``ib.store.commit``), median over the window's whole epochs."""
import os
import sys

sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))

import _spans  # noqa: E402


def read(rec):
    return _spans.epoch_median(
        rec, lambda ep: ep.total_ms("ib.op.UploadOp", "ib.store.commit"))
