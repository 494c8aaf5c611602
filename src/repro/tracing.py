"""Spans and counters at the program's layer boundaries.

A span (:func:`span`) times one stage of a call.  It is a
``jax.profiler.TraceAnnotation`` of the same name, so any profiler trace
(the benchmark's, or an operator's in TensorBoard or Perfetto) shows it on
the device trace's clock beside the device's ``XLA Modules``.  While a
profiler session collects, or inside :func:`recording`, the span is also
kept in memory: name, start and end (``time.perf_counter_ns``), its own id,
its parent's id and its root's id, which every span of one call shares,
and its attrs.  Otherwise :func:`span` returns a shared no-op after one
check.

Counters (:func:`count`) are process-wide and always on.  A span that
names counters stores, as attrs, how much each grew between its start and
its end, so counts are read at the same boundary as the time.

Recorded spans wait in a bounded ring (:data:`RING`) until read:
:func:`spans` lists them, :func:`clear` empties the ring and
:func:`counters` / :func:`reset` read and zero counters.  The profiler's
trace is the exporter; nothing here writes a file.

    with tracing.recording():
        svc.plan_many(platforms)
    for s in tracing.spans():
        print(s.name, s.duration_ns, s.attrs)
"""
from __future__ import annotations

import collections
import contextlib
import dataclasses
import itertools
import threading
import time
from typing import Dict, List, Optional

import jax

__all__ = ["RING", "Span", "span", "count", "recording", "spans", "clear",
           "counters", "reset"]

#: most spans the ring holds; older ones are dropped first
RING = 8192

_ring: collections.deque = collections.deque(maxlen=RING)
_counts: Dict[str, int] = collections.defaultdict(int)
_lock = threading.Lock()
_ids = itertools.count(1)
_local = threading.local()
_forced = 0
_profiling = jax.profiler.TraceAnnotation.is_enabled


@dataclasses.dataclass(frozen=True)
class Span:
    name: str
    start_ns: int
    end_ns: int
    span_id: int
    parent_id: Optional[int]
    #: the id of the outermost span of the call; a root's own id
    root_id: int
    attrs: dict

    @property
    def duration_ns(self) -> int:
        return self.end_ns - self.start_ns


class _Recorder:
    __slots__ = ("name", "counted", "attrs", "before", "annotation",
                 "span_id", "parent_id", "root_id", "start_ns")

    def __init__(self, name: str, counted, attrs: dict):
        self.name, self.counted, self.attrs = name, counted, attrs

    def __enter__(self):
        stack = _stack()
        parent = stack[-1] if stack else None
        self.span_id = next(_ids)
        self.parent_id = parent.span_id if parent else None
        self.root_id = parent.root_id if parent else self.span_id
        self.before = [_counts.get(c, 0) for c in self.counted]
        stack.append(self)
        self.annotation = jax.profiler.TraceAnnotation(self.name)
        self.annotation.__enter__()
        self.start_ns = time.perf_counter_ns()
        return self

    def __exit__(self, *exc):
        end_ns = time.perf_counter_ns()
        self.annotation.__exit__(*exc)
        _stack().pop()
        attrs = dict(self.attrs)
        for c, b in zip(self.counted, self.before):
            attrs[c] = _counts.get(c, 0) - b
        _ring.append(Span(self.name, self.start_ns, end_ns, self.span_id,
                          self.parent_id, self.root_id, attrs))
        return False


_NOOP = contextlib.nullcontext()


def _stack() -> list:
    stack = getattr(_local, "stack", None)
    if stack is None:
        stack = _local.stack = []
    return stack


def span(name: str, counted=(), **attrs):
    """A context manager that times the block as span ``name``.
    ``counted`` names counters whose growth over the block becomes attrs;
    ``attrs`` are kept as given."""
    if not (_forced or _profiling()):
        return _NOOP
    return _Recorder(name, tuple(counted), attrs)


def count(name: str, n: int = 1) -> None:
    """Add ``n`` to the process-wide counter ``name``."""
    with _lock:
        _counts[name] += n


@contextlib.contextmanager
def recording():
    """Keep spans in memory inside the block, profiler or not."""
    global _forced
    _forced += 1
    try:
        yield
    finally:
        _forced -= 1


def spans() -> List[Span]:
    """The spans in the ring, oldest first, each recorded at its end."""
    return list(_ring)


def clear() -> None:
    """Empty the ring."""
    _ring.clear()


def counters() -> Dict[str, int]:
    """Every counter's value."""
    with _lock:
        return dict(_counts)


def reset(*names: str) -> None:
    """Zero the counters ``names``."""
    with _lock:
        for name in names:
            _counts[name] = 0
