"""Share, in %, of its roofline that the ``segment_sum`` reduce kernel
reached in the traced window.

The work counted is what the reduction needs, not what the kernel does:
each call reads N int32 segment ids and N x D float32 values and writes
S x D float32 sums (:func:`needed_bytes`), with no floating-point work worth
a bound, so the roofline is bytes over the chip's HBM bandwidth.  The
time is the summed device time of the kernel's jitted program.
"""

KERNEL = "segment_sum"


def needed_bytes(rows: int, segments: int, width: int) -> int:
    return 4 * rows + 4 * rows * width + 4 * segments * width


def read(rec):
    t = rec.traced
    if t is None or t.peaks is None:
        return None
    dev = sum(s for name, (s, _) in t.trace["modules"].items()
              if KERNEL in name)
    calls = [c for c in t.reducer_calls if c["path"] == "pallas"]
    if dev <= 0 or not calls:
        return None
    need = sum(needed_bytes(c["rows"], c["segments"], c["width"])
               for c in calls)
    return 100.0 * need / t.peaks["hbm_bytes_per_s"] / dev
