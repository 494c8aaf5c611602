"""95th percentile, in ms, of the time each request of the open loop
waited in the benchmark's queue: from when it was due to when the call
that served it began."""
from bench.record import percentile


def read(rec):
    wait = [op["start"] - op["due"] for op in rec.ops
            if "due" in op and "start" in op]
    return percentile(wait, 95) * 1e3 if wait else None
