"""The harness's own arithmetic: trace reduction, percentiles and rates,
seeded inputs, the batcher, lookup by name, the chip checks, and the
needed-bytes count of ``segment_sum``."""
import json
import re
from pathlib import Path

import numpy as np
import pytest

from bench import gen, manifest, peaks, trace
from bench.manifest import BENCH, ROOT
from bench.record import Record, percentile

DATA = Path(__file__).parent / "data"


def _metric(name):
    return manifest.load_module(manifest.reader(name))


def _row(plane, line, name, start_ms, dur_ms):
    return [plane, line, name, start_ms * 1e6, dur_ms * 1e6]


def test_trace_reduction_by_hand():
    dev, host = "/device:TPU:0", "/host:CPU"
    rows = [
        _row(host, "python3", "bench.window", 0, 100),
        _row(host, "python3", "bench.plan_many", 0, 40),
        _row(host, "python3", "bench.wait_arrival", 40, 60),
        _row(dev, trace.MODULES_LINE, "jit_fusion(12)", 10, 10),
        _row(dev, trace.MODULES_LINE, "jit_fusion(34)", 15, 10),  # union 10-25
        _row(dev, trace.MODULES_LINE, "jit__solve_batch_many(5)", 50, 5),
        _row(dev, trace.MODULES_LINE, "jit_late(6)", 120, 5),  # after it
    ]
    r = trace.reduce(rows)
    assert r["window_s"] == pytest.approx(0.1)
    assert r["busy_s"] == pytest.approx(0.02)
    assert dict(r["device_ops"]) == pytest.approx(
        {"jit_fusion": 0.02, "jit__solve_batch_many": 0.005})
    assert r["modules"]["jit_fusion"][1] == 2
    gaps = dict(r["idle_gaps"])
    # idle 0-10 and 25-40 under plan_many, 40-50 and 55-100 under the wait
    assert gaps["bench.plan_many"] == pytest.approx(0.025)
    assert gaps["bench.wait_arrival"] == pytest.approx(0.055)
    assert "host.other" not in gaps
    rec = Record(traced=Record(trace=r, dispatches=[
        dict(kind="plan", n=4, start=0.0, end=0.04)]))
    assert _metric("device_idle_pct.stream").read(rec) == pytest.approx(80.0)
    assert _metric("solve_device_ms.stream").read(rec) == pytest.approx(5.0)


def test_trace_reduction_of_a_recorded_chip_trace():
    rows = json.loads((DATA / "trace_rows.json").read_text())
    r = trace.reduce(rows)
    assert 0 < r["busy_s"] <= r["window_s"]
    idle = sum(s for _, s in r["idle_gaps"])
    assert idle == pytest.approx(r["window_s"] - r["busy_s"], rel=1e-6)
    assert any("_solve_batch_many" in m for m in r["modules"])
    assert any("segment_sum" in m for m in r["modules"])
    assert {n for n, _, _ in r["spans"]} >= {"bench.plan_many",
                                            "bench.execute"}


def test_p95_of_all_requests_from_their_due_times():
    # 20 requests due 10 ms apart, each done 1..20 ms after it was due,
    # though the loop only sent it at its dispatch
    ops = [dict(kind="plan", due=0.01 * i, start=0.01 * i + 0.0005,
                done=0.01 * i + 0.001 * (i + 1), answer=object())
           for i in range(20)]
    rec = Record(ops=ops, window_s=0.25)
    assert _metric("plan_p95_ms").read(rec) == pytest.approx(19.0)
    assert _metric("queue_wait_p95_ms.stream").read(rec) == pytest.approx(0.5)
    assert percentile(range(1, 101), 95) == 95
    assert percentile([7], 95) == 7
    # one stall shows: it is among the top 5 %
    rec.ops[-1]["done"] += 1.0
    assert _metric("plan_p95_ms").read(rec) == pytest.approx(19.0)
    rec.ops[-2]["done"] += 1.0
    assert _metric("plan_p95_ms").read(rec) > 1000


def test_rates_are_all_work_over_the_whole_window():
    ops = [dict(kind="plan", sent=0, done=1, answer=object())] * 30
    ops += [dict(kind="plan", sent=0, done=1, answer=None)] * 5  # raised
    assert _metric("plans_per_s").read(Record(ops=ops, window_s=12.5)) == 2.4
    jobs = [dict(kind="wordcount", work=10**7, answer=[])] * 3
    assert _metric("records_per_s").read(
        Record(ops=jobs, window_s=2.0)) == 1.5e7
    calls = [dict(kind="plan", n=n, start=0.0, end=0.01 * n)
             for n in (1, 2, 4, 8)]
    rec = Record(dispatches=calls)
    assert _metric("requests_per_dispatch.stream").read(rec) == 3.75
    assert _metric("dispatch_ms.stream").read(rec) == pytest.approx(37.5)


def test_seeded_inputs_are_deterministic():
    cell = manifest.load("planetlab8.plan_stream")
    p = cell.config["platform"]
    big = 3_000_000_017  # more than 31 bits

    def draw(seed):
        a = gen.planetlab(p, 1.0, gen.rng(seed, 1, 5))
        keys, values, words = gen.corpus(
            dict(docs=50, words_per_doc=20, vocab=100, zipf=1.4),
            gen.rng(seed, 11, 0))
        due = gen.arrivals(90.0, 0.5, 4, 7, gen.rng(seed, 3))
        return [a["B_sm"], a["C_r"], values, words, due]

    one, again, other = draw(big), draw(big), draw(big + 1)
    for x, y in zip(one, again):
        assert np.array_equal(x, y)
    assert not np.array_equal(one[0], other[0])
    assert not np.array_equal(one[4], other[4])
    # every seed offers the same arrivals in another order: 4 blocks of 45
    assert len(one[4]) == len(other[4]) == 180
    assert np.all(np.diff(one[4]) >= 0) and one[4][-1] < 2.0
    assert sorted(np.round(one[4] % 0.5, 9)) == sorted(np.round(other[4] % 0.5, 9))


def test_simple_plans_are_plans_and_price_above_an_annealed_one():
    from bench import reference

    a = gen.planetlab(manifest.load("planetlab8.plan_stream").config[
        "platform"], 1.0, gen.rng(9, 1, 0))
    plans = reference.simple_plans(a)
    assert len(plans) == 9
    for x, y in plans:
        assert np.allclose(x.sum(axis=1), 1) and np.isclose(y.sum(), 1)
        assert np.all(x >= 0) and np.all(y >= 0)
    # every node of the testbed is its own site: the local push is identity
    assert np.array_equal(plans[3][0], np.eye(8))
    # compute-proportional shuffle
    assert np.allclose(plans[1][1], a["C_r"] / a["C_r"].sum())


def test_a_split_metric_shares_its_quantity_reader():
    assert manifest.reader("dispatch_ms.stream") == \
        BENCH / "metrics" / "dispatch_ms.py"
    assert manifest.reader("segment_sum_roofline") == \
        BENCH / "metrics" / "segment_sum_roofline.py"


def test_power_of_two_batcher():
    planner = manifest.load_module(BENCH / "clients" / "planner.py")
    assert [planner._bucket(n, 8) for n in range(1, 13)] == \
        [1, 2, 2, 4, 4, 4, 4, 8, 8, 8, 8, 8]
    assert planner._bucket(5, 1) == 1


NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.-]{1,16}$")


def test_every_name_in_the_manifest_resolves():
    spec = json.loads((ROOT / "BENCHMARK.json").read_text())
    assert spec["paths"] == ["bench"]
    names = [c["name"] for c in spec["configs"]]
    for w in spec["workloads"]:
        cell = manifest.load(w["name"])
        assert w["config"] in names and cell.config["name"] == w["config"]
        assert hasattr(cell.client(), "Client")
        for traced in (False, True):
            metrics = cell.metrics(traced)
            assert metrics and all(callable(r) for _, r in metrics.values())
        assert "setup_s" in cell.metrics(False)
        assert NAME.match(w["name"]) and NAME.match(w["traffic"])
    for m in spec["end_to_end"] + spec["per_layer"]:
        assert NAME.match(m["name"]) and UNIT.match(m["unit"])
        assert manifest.reader(m["name"]).is_file()
    for c in spec["configs"]:
        assert (ROOT / c["file"]).is_file()
    e2e = {m["name"] for m in spec["end_to_end"]}
    assert all(m["moves"] in e2e for m in spec["per_layer"])
    assert all(0.01 <= m["bound"] <= 0.25 for m in spec["end_to_end"])
    for entry in spec["configs"] + spec["workloads"]:
        assert 1 <= len(entry["why"]) <= 200
    for w in spec["workloads"]:
        cell = manifest.load(w["name"])
        assert len(cell.end_to_end) >= 2 and cell.per_layer


def test_measurement_refuses_the_cpu(capsys):
    from bench import run

    code = run.main(["--workload", "planetlab8.plan_stream", "--seed", "1",
                     "--seconds", "1", "--trace", "0"])
    assert code == run.NO_CHIP
    assert capsys.readouterr().out == ""


def test_a_device_kind_missing_from_the_peaks_is_an_error():
    assert peaks.peaks("TPU v5 lite")["hbm_bytes_per_s"] == 819e9
    with pytest.raises(KeyError, match="TPU v99"):
        peaks.peaks("TPU v99")


def test_segment_sum_needed_bytes_and_roofline():
    m = _metric("segment_sum_roofline")
    # 1,000 int32 ids + 1,000 x 1 float32 values read, 10 x 1 sums written
    assert m.needed_bytes(1000, 10, 1) == 8040
    assert m.needed_bytes(1000, 10, 3) == 4000 + 12000 + 120
    calls = [dict(rows=1000, segments=10, width=1, path="pallas"),
             dict(rows=40, segments=5, width=1, path="reference")]
    traced = Record(reducer_calls=calls, peaks=peaks.peaks("TPU v5 lite"),
                    trace={"modules": {"jit_segment_sum": (1e-6, 1)}})
    assert m.read(Record(traced=traced)) == pytest.approx(
        100 * 8040 / 819e9 / 1e-6)
    traced.trace = {"modules": {}}
    assert m.read(Record(traced=traced)) is None  # nothing read: never 0
    assert m.read(Record()) is None
