"""What one measured window leaves for the metric readers.

A load client fills a :class:`Record` while it measures: one entry in ``ops``
per operation due in the window (a plan request or a job) and one in
``dispatches`` per call into the system.  Times are seconds on the host's
``perf_counter`` clock.  A traced run adds ``traced``, the record of a
short second window run under the profiler, which carries ``trace``, the
reduction of :mod:`bench.trace`, and ``peaks``, the chip's row of
:mod:`bench.peaks`.
"""
from __future__ import annotations

import dataclasses
import math
from typing import Optional

__all__ = ["Record", "percentile"]


def percentile(values, q: float) -> float:
    """The nearest-rank ``q``-th percentile of all ``values``: the
    smallest value at or above which lie ``100 - q`` percent of them."""
    v = sorted(values)
    if not v:
        raise ValueError("percentile of no values")
    return v[max(math.ceil(q / 100.0 * len(v)), 1) - 1]


@dataclasses.dataclass
class Record:
    #: seconds from the window's start to the end of its last operation
    window_s: float = 0.0
    #: per operation: ``due`` (open loops) or ``sent``, ``start`` (its
    #: dispatch began), ``done``, ``kind`` and ``work`` (records, plans)
    ops: list = dataclasses.field(default_factory=list)
    #: per call into the system: ``kind``, ``n`` requests, ``start``, ``end``
    dispatches: list = dataclasses.field(default_factory=list)
    #: per reducer call: ``rows``, ``segments``, ``width``, ``path``
    reducer_calls: list = dataclasses.field(default_factory=list)
    #: requests still queued when the open loop's last arrival was due
    backlog_at_end: int = 0
    #: ... and when half of the window had passed
    backlog_at_half: int = 0
    #: seconds from the process's start to the window's start
    setup_s: float = 0.0
    traced: Optional["Record"] = None
    trace: Optional[dict] = None
    peaks: Optional[dict] = None
