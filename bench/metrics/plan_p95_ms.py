"""95th percentile, in ms, of every request due in the open loop's window,
each timed from when it was due to when its plan was back on the host."""
from bench.record import percentile


def read(rec):
    lat = [op["done"] - op["due"] for op in rec.ops if "due" in op]
    return percentile(lat, 95) * 1e3 if lat else None
