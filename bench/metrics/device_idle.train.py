"""Share of the traced training window in which no operation ran on the
device (%): 1 - union of the device's op intervals / window."""


def read(rec):
    if rec.get("trace") is None or rec["window_s"] <= 0:
        return None
    return 100.0 * (1.0 - rec["busy_s"] / rec["window_s"])
