"""Median commit latency of the epochs that committed inside the window of
the bulk ingest cell (ms): the engine's own host clock from epoch cut to
commit (``EpochReport.commit_latency_s``)."""
import statistics


def read(rec):
    mine = set(rec.get("window_epochs", ()))
    vals = [e["commit_latency_s"] for e in rec.get("epochs", ())
            if e["epoch"] in mine and e["commit_latency_s"] is not None]
    return 1000.0 * statistics.median(vals) if vals else None
