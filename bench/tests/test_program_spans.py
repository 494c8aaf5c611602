"""The readers of the program's own spans and counters
(``bench/program_spans.py`` and the ``plan_*`` and ``engine_*`` metrics):
by hand on made-up spans, against a program that records none, and on
small driven cells under ``repro.tracing.recording()``."""
import sys

import pytest
from tests_support import drive

from bench import manifest, program_spans
from bench.record import Record
from repro import tracing
from repro.tracing import Span

PLAN = ["plan_prep_ms", "plan_solve_ms", "plan_fetch_ms", "plan_price_ms"]
ENGINE = ["engine_push_ms", "engine_map_ms", "engine_shuffle_ms",
          "engine_reduce_ms"]
TRACED = Record(traced=Record())


def _read(metric, rec=TRACED):
    return manifest.load_module(manifest.reader(metric)).read(rec)


def _span(name, sid, root, start_ms, end_ms, parent=None, **attrs):
    return Span(name, int(start_ms * 1e6), int(end_ms * 1e6), sid,
                parent if parent is not None else (None if sid == root
                                                   else root), root, attrs)


def _plan_call(root, t0, stages_ms, requests):
    """One ``geoplan.plan_many`` call starting at ``t0`` ms whose four
    stages take ``stages_ms``; children first, as the ring holds them."""
    out, t = [], t0
    for k, (stage, ms) in enumerate(zip(
            ["prep", "solve", "fetch", "price"], stages_ms)):
        out.append(_span(f"geoplan.plan.{stage}", root + 1 + k, root, t,
                         t + ms))
        t += ms
    out.append(_span("geoplan.plan_many", root, root, t0, t + 0.5,
                     requests=requests, **{"plan.h2d": 9 + 2 * requests,
                                           "plan.d2h": 1 + 2 * requests}))
    return out


@pytest.fixture
def made_up(monkeypatch):
    spans = (_plan_call(1, 0.0, [6, 4, 2, 3], 2)
             + _plan_call(10, 20.0, [10, 6, 4, 5], 4)
             # a stage recorded outside any call: not counted
             + [_span("geoplan.plan.prep", 20, 20, 50.0, 99.0)]
             + [_span("geoplan.engine.push", 31, 30, 60.0, 61.5),
                _span("geoplan.engine.map", 32, 30, 61.5, 64.0),
                _span("geoplan.engine.shuffle", 33, 30, 64.0, 64.25),
                _span("geoplan.engine.reduce", 34, 30, 64.25, 65.0),
                _span("geoplan.execute", 30, 30, 60.0, 65.1)])
    monkeypatch.setattr(program_spans, "_recorded", lambda: spans)
    return spans


def test_stage_readers_by_hand(made_up):
    assert _read("plan_prep_ms.stream") == pytest.approx(8.0)
    assert _read("plan_solve_ms.closed") == pytest.approx(5.0)
    assert _read("plan_fetch_ms.stream") == pytest.approx(3.0)
    assert _read("plan_price_ms.closed") == pytest.approx(4.0)
    # (9 + 2*2 + 1 + 2*2 + 9 + 2*4 + 1 + 2*4) / 2 calls: 10 + 4 * 3
    assert _read("plan_transfers.stream") == pytest.approx(22.0)
    assert [_read(f"{m}.wc") for m in ENGINE] == pytest.approx(
        [1.5, 2.5, 0.25, 0.75])
    calls = program_spans.by_root(made_up, "geoplan.plan_many")
    assert [len(kids) for _, kids in calls] == [4, 4]


def test_no_trace_or_no_root_reads_nothing(made_up, monkeypatch):
    for m in PLAN + ["plan_transfers"] + ENGINE:
        assert _read(m, Record()) is None  # an untraced run
    monkeypatch.setattr(program_spans, "_recorded", lambda: made_up[:4])
    assert _read("plan_prep_ms.stream") is None  # stages without a root
    monkeypatch.setattr(program_spans, "_recorded", lambda: [])
    assert all(_read(m) is None for m in PLAN + ["plan_transfers"] + ENGINE)


def test_a_program_without_tracing_reads_nothing(monkeypatch):
    monkeypatch.setitem(sys.modules, "repro.tracing", None)
    assert program_spans._recorded() == []
    assert all(_read(m) is None for m in PLAN + ["plan_transfers"] + ENGINE)


def _stage_share(roots, stages):
    """The stages' summed mean over the mean root, per root span."""
    mean_root = sum(r.duration_ns for r, _ in roots) / len(roots) * 1e-6
    return sum(_read(m) for m in stages) / mean_root


def test_a_driven_planner_cell_under_recording(small_cell):
    cell = small_cell("planetlab8.plan_closed", pool=16)
    tracing.clear()
    with tracing.recording():
        _, rec = drive(cell, seed=2_718_281_828, seconds=0.5)
    try:
        roots = program_spans.by_root(tracing.spans(), "geoplan.plan_many")
        assert roots and all(r.attrs["requests"] == 8 for r, _ in roots)
        assert _read("plan_transfers.closed") == 10 + 4 * 8
        assert 0.5 < _stage_share(roots, PLAN) <= 1.0
        assert all(_read(f"{m}.closed") > 0 for m in PLAN)
    finally:
        tracing.clear()


def test_a_driven_wordcount_cell_under_recording(small_cell):
    cell = small_cell("planetlab8.wordcount")
    tracing.clear()
    with tracing.recording():
        _, rec = drive(cell, seed=3, seconds=0.5)
    try:
        roots = program_spans.by_root(tracing.spans(), "geoplan.execute")
        assert len(roots) >= len(rec.ops) > 0
        assert 0.5 < _stage_share(roots, ENGINE) <= 1.0
    finally:
        tracing.clear()
