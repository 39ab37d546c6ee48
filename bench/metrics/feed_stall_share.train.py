"""Share of the training window the host spends getting the next batch
placed (%): the benchmark's spans around ``BlockFeeder.batches`` (next),
``make_batch`` and ``Trainer.put_batch``, over the window's host time."""

FEED = ("feeder.next", "make_batch", "put_batch")


def read(rec):
    spans, window = rec.get("spans"), rec.get("host_window_s")
    if spans is None or not window:
        return None
    bounds = [(s, e) for n, s, e in spans.records if n == "window"]
    if not bounds:
        return None
    lo, hi = bounds[-1]
    return 100.0 * spans.total(FEED, lo, hi) / (hi - lo)
