"""Share of the training window the program's feed path holds the host
(%): its ``ib.feeder.batch``, ``ib.train.make_batch`` and
``ib.train.put_batch`` spans inside the window, over the window."""
import os
import sys

sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))

import _spans  # noqa: E402

FEED = ("ib.feeder.batch", "ib.train.make_batch", "ib.train.put_batch")


def read(rec):
    return _spans.window_share(rec, FEED)
