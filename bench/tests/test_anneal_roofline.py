"""The anneal's roofline share (``bench/metrics/anneal_roofline.py``): its
byte count, a run with nothing to read, a made-up traced window, and the
cell that reports it, as ``python3 -m bench`` loads it."""
import pytest

from bench import manifest, peaks, program_spans
from bench.record import Record
from repro.tracing import Span

CELL = "planetlab512.plan_closed"
HBM = peaks.PEAKS["TPU v5 lite"]["hbm_bytes_per_s"]


def _reader():
    return manifest.load_module(manifest.reader("anneal_roofline.pl512"))


def _span(name, sid, root, **attrs):
    return Span(name, 0, 1, sid, None if sid == root else root, root, attrs)


def _traced(solve_s, calls):
    trace = {"modules": {"jit__solve_batch_many": (solve_s, calls),
                         "jit_other": (9.0, 1)}}
    return Record(traced=Record(trace=trace, peaks=peaks.PEAKS["TPU v5 lite"],
                                dispatches=[dict(kind="plan", n=8)] * calls))


def test_needed_bytes_of_the_cell_shape():
    # a call of 8 requests x 24 restarts at 512 x 512 push and 512 shuffle
    params = 8 * 24 * (512 * 512 + 512)
    assert params == 50_429_952
    assert _reader().needed_bytes(params, 500) == 24 * 50_429_952 * 500


def test_an_untraced_run_reads_nothing():
    assert _reader().read(Record()) is None


def test_a_made_up_traced_window(monkeypatch):
    params = 8 * 24 * (512 * 512 + 512)
    spans = []
    for root in (1, 10):  # two calls, each one solve among its stages
        spans += [_span("geoplan.plan.prep", root + 1, root),
                  _span("geoplan.plan.solve", root + 2, root, params=params,
                        steps=500, tau0_frac=0.01),
                  _span("geoplan.plan_many", root, root, requests=8)]
    monkeypatch.setattr(program_spans, "_recorded", lambda: spans)
    # 2 calls in 2.0 s of solver device time: 1.0 s a call
    got = _reader().read(_traced(2.0, 2))
    assert got == pytest.approx(100.0 * 24 * params * 500 / HBM)
    assert 0 < got <= 100


def test_solve_spans_without_counts_read_nothing(monkeypatch):
    spans = [_span("geoplan.plan.solve", 2, 1),
             _span("geoplan.plan_many", 1, 1, requests=8)]
    monkeypatch.setattr(program_spans, "_recorded", lambda: spans)
    assert _reader().read(_traced(2.0, 1)) is None


def test_the_cell_loads_with_its_metrics():
    cell = manifest.load(CELL)
    assert cell.chips == 1 and cell.traffic["client"] == "planner"
    assert len(cell.config["platform"]["sites"]) == 512
    assert set(cell.metrics(False)) == {"plans_per_s", "setup_s"}
    traced = cell.metrics(True)
    assert set(traced) == {f"{m}.pl512" for m in (
        "plan_prep_ms", "plan_solve_ms", "plan_fetch_ms", "plan_price_ms",
        "solve_device_ms", "device_idle_pct", "anneal_roofline")}
    for entry, read in traced.values():
        assert callable(read) and entry["moves"] == "plans_per_s"
