"""The program's own spans (``repro.tracing``), grouped by the call they
belong to.

The program keeps a span in memory only while a profiler session
collects, so in a run of the benchmark the spans it holds are those of the
traced window.  Readers of a program stage's time or count go through
:func:`per_root`, which gives nothing where the run was not traced, or
where the program records no such span (a program without
``repro.tracing``).
"""
from __future__ import annotations

__all__ = ["by_root", "per_root", "stage_ms"]


def _recorded() -> list:
    try:
        from repro import tracing
    except ImportError:
        return []
    return tracing.spans()


def by_root(spans, root: str) -> list:
    """``[(root span, [spans under it]), ...]`` for each span named
    ``root`` that began its call, in the order the calls ended."""
    under: dict = {}
    for s in spans:
        if s.span_id != s.root_id:
            under.setdefault(s.root_id, []).append(s)
    return [(s, under.get(s.span_id, [])) for s in spans
            if s.name == root and s.span_id == s.root_id]


def per_root(rec, root: str, value):
    """The mean over the traced window's ``root`` spans of
    ``value(root span, spans under it)``; ``None`` where the run was not
    traced or holds no such span."""
    if rec.traced is None:
        return None
    calls = by_root(_recorded(), root)
    if not calls:
        return None
    return sum(value(r, kids) for r, kids in calls) / len(calls)


def stage_ms(rec, root: str, stage: str):
    """Mean time, in ms, per ``root`` span of the ``stage`` spans under
    it."""
    return per_root(rec, root, lambda r, kids: sum(
        s.duration_ns for s in kids if s.name == stage) * 1e-6)
