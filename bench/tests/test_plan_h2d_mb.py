"""The reader of the bytes a planner call puts on the device
(``plan_h2d_mb``): by hand on made-up spans, against a program that counts
no bytes or records no spans, and on a small driven cell under
``repro.tracing.recording()``."""
import sys

import pytest
from tests_support import drive

from bench import manifest, program_spans
from bench.record import Record
from repro import tracing
from repro.tracing import Span

TRACED = Record(traced=Record())
METRICS = ["plan_h2d_mb.pl512", "plan_h2d_mb.closed"]


def _read(metric, rec=TRACED):
    return manifest.load_module(manifest.reader(metric)).read(rec)


def _root(sid, start_ms, **attrs):
    return Span("geoplan.plan_many", int(start_ms * 1e6),
                int((start_ms + 5) * 1e6), sid, None, sid,
                dict(requests=8, **attrs))


def _calls(*h2d_bytes):
    return [_root(i + 1, 10.0 * i, **{"plan.h2d": 10, "plan.d2h": 3,
                                      "plan.h2d_bytes": n})
            for i, n in enumerate(h2d_bytes)]


def test_the_mean_megabytes_per_call_by_hand(monkeypatch):
    spans = _calls(42_000_000, 44_000_000) + [
        # a stage of a call is not a call
        Span("geoplan.plan.prep", 0, 10, 9, 1, 1, {"plan.h2d_bytes": 7})]
    monkeypatch.setattr(program_spans, "_recorded", lambda: spans)
    assert [_read(m) for m in METRICS] == pytest.approx([43.0, 43.0])


def test_nothing_from_a_program_that_counts_no_bytes(monkeypatch):
    old = [_root(1, 0.0, **{"plan.h2d": 11, "plan.d2h": 3})]
    monkeypatch.setattr(program_spans, "_recorded", lambda: old)
    assert all(_read(m) is None for m in METRICS)
    # nor where only some calls carry the counter
    monkeypatch.setattr(program_spans, "_recorded",
                        lambda: old + _calls(1_000_000))
    assert all(_read(m) is None for m in METRICS)


def test_nothing_untraced_or_without_spans_or_tracing(monkeypatch):
    monkeypatch.setattr(program_spans, "_recorded", lambda: _calls(5))
    assert all(_read(m, Record()) is None for m in METRICS)
    monkeypatch.setattr(program_spans, "_recorded", lambda: [])
    assert all(_read(m) is None for m in METRICS)
    monkeypatch.undo()
    monkeypatch.setitem(sys.modules, "repro.tracing", None)
    assert all(_read(m) is None for m in METRICS)


def test_a_driven_planner_cell_under_recording(small_cell):
    cell = small_cell("planetlab8.plan_closed", pool=16)
    tracing.clear()
    with tracing.recording():
        drive(cell, seed=3_141_592_653, seconds=0.5)
    try:
        roots = program_spans.by_root(tracing.spans(), "geoplan.plan_many")
        assert roots
        want = sum(r.attrs["plan.h2d_bytes"] for r, _ in roots) / len(roots)
        assert _read("plan_h2d_mb.closed") == pytest.approx(want / 1e6)
        assert want > 0
    finally:
        tracing.clear()
