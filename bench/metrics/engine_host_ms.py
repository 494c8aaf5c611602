"""Host time, in ms, of one word-count job: the mean over the traced
window's ``bench.execute`` spans of each span's length less the device's
busy time inside it."""


def _busy_inside(busy, s, e):
    return sum(max(min(b1, e) - max(b0, s), 0.0) for b0, b1 in busy)


def read(rec):
    t = rec.traced
    if t is None:
        return None
    jobs = [(s, e) for name, s, e in t.trace["spans"]
            if name == "bench.execute"]
    if not jobs:
        return None
    host = [(e - s) - _busy_inside(t.trace["busy"], s, e) for s, e in jobs]
    return sum(host) / len(host) * 1e3
