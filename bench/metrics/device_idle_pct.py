"""Share, in %, of the traced window in which no program ran on the
device: 100 * (1 - busy union / window)."""


def read(rec):
    t = rec.traced
    if t is None or t.trace["window_s"] <= 0:
        return None
    return 100.0 * (1.0 - t.trace["busy_s"] / t.trace["window_s"])
