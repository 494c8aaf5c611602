"""``repro.tracing``: spans and counters at the planner's and the engine's
stage boundaries, kept in memory only while a profiler session collects or
inside ``recording()``, and on the profiler trace's clock.

The solver runs at 4 restarts x 40 steps on 8-node PlanetLab platforms."""
import glob
import os

import jax
import numpy as np
import pytest

from repro import tracing
from repro.api import GeoJob
from repro.core import SolverService
from repro.core.platform import planetlab_platform
from repro.mapreduce.apps import word_count

STAGES = ("geoplan.plan.prep", "geoplan.plan.solve", "geoplan.plan.fetch",
          "geoplan.plan.price")
PHASES = ("geoplan.engine.push", "geoplan.engine.map",
          "geoplan.engine.shuffle", "geoplan.engine.reduce")


@pytest.fixture(scope="module")
def svc():
    return SolverService(n_restarts=4, steps=40)


@pytest.fixture
def ring():
    tracing.clear()
    yield
    tracing.clear()


def _plan(svc, b):
    platforms = [planetlab_platform(1, alpha=1.0, seed=s) for s in range(b)]
    return svc.plan_many(platforms, seeds=list(range(b)))


def _job():
    rng = np.random.default_rng(0)
    platform = planetlab_platform(1, alpha=1.0, seed=0)
    per_source = [(np.arange(200, dtype=np.int64),
                   rng.integers(0, 50, 200).astype(np.int64))
                  for _ in range(platform.nS)]
    return GeoJob(platform, word_count()).plan(mode="uniform"), per_source


def _calls(root):
    """``[(root span, [its children in start order])]``."""
    spans = tracing.spans()
    roots = [s for s in spans if s.name == root]
    return [(r, sorted((s for s in spans if s.parent_id == r.span_id),
                       key=lambda s: s.start_ns)) for r in roots]


def test_nothing_is_recorded_outside_a_session(svc, ring):
    _plan(svc, 1)  # compiles
    before = tracing.counters()
    _plan(svc, 1)
    assert tracing.spans() == []
    after = tracing.counters()
    assert after["plan.h2d"] - before["plan.h2d"] == 11
    assert after["plan.d2h"] - before["plan.d2h"] == 3
    with tracing.recording():
        pass
    _plan(svc, 1)
    assert tracing.spans() == []


@pytest.mark.parametrize("b", [1, 4])
def test_one_root_per_call_with_its_stages_and_transfers(svc, ring, b):
    _plan(svc, b)  # compiles
    tracing.clear()
    before = tracing.counters()
    with tracing.recording():
        results = _plan(svc, b)
    assert len(results) == b
    after = tracing.counters()
    (root, kids), = _calls("geoplan.plan_many")
    assert root.parent_id is None and root.root_id == root.span_id
    assert [k.name for k in kids] == list(STAGES)
    for k in kids:
        assert k.root_id == root.span_id
        assert root.start_ns <= k.start_ns <= k.end_ns <= root.end_ns
    assert all(a.end_ns <= z.start_ns for a, z in zip(kids, kids[1:]))
    assert root.attrs == {"requests": b, "plan.h2d": 9 + 2 * b,
                          "plan.d2h": 1 + 2 * b}
    assert after["plan.h2d"] - before["plan.h2d"] == 9 + 2 * b
    assert after["plan.d2h"] - before["plan.d2h"] == 1 + 2 * b
    assert len(tracing.spans()) == 5


def test_the_ring_keeps_the_newest_spans(ring):
    with tracing.recording():
        for i in range(tracing.RING + 10):
            with tracing.span("t.leaf", i=i):
                pass
    kept = tracing.spans()
    assert len(kept) == tracing.RING
    assert [s.attrs["i"] for s in kept[:2]] == [10, 11]
    assert kept[-1].attrs["i"] == tracing.RING + 9
    assert len({s.root_id for s in kept}) == tracing.RING


def test_a_span_stores_the_growth_of_the_counters_it_names(ring):
    with tracing.recording():
        with tracing.span("t.root", ("t.a", "t.b"), k="v"):
            tracing.count("t.a", 3)
            with tracing.span("t.child"):
                tracing.count("t.a")
    child, root = tracing.spans()
    assert root.attrs == {"k": "v", "t.a": 4, "t.b": 0}
    assert child.parent_id == root.span_id == child.root_id
    tracing.reset("t.a")
    assert tracing.counters()["t.a"] == 0


def test_a_job_records_its_four_phases(ring):
    job, per_source = _job()
    with tracing.recording():
        report = job.execute(per_source)
    assert sum(int(v.sum()) for _, v in report.outputs) == 200 * job.platform.nS
    (root, kids), = _calls("geoplan.execute")
    assert [k.name for k in kids] == list(PHASES)
    assert all(k.root_id == root.span_id for k in kids)
    assert sum(k.duration_ns for k in kids) <= root.duration_ns


def test_spans_in_the_profiler_trace_match_those_in_memory(svc, ring,
                                                          tmp_path):
    from jax.profiler import ProfileData

    _plan(svc, 2)  # compiles
    job, per_source = _job()
    job.execute(per_source)
    tracing.clear()
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    opts.host_tracer_level = 1
    jax.profiler.start_trace(str(tmp_path), profiler_options=opts)
    try:
        _plan(svc, 2)
        job.execute(per_source)
    finally:
        jax.profiler.stop_trace()
    memory = tracing.spans()
    assert {s.name for s in memory} == {"geoplan.plan_many", "geoplan.execute",
                                        *STAGES, *PHASES}
    path, = glob.glob(os.path.join(str(tmp_path), "plugins", "profile", "*",
                                   "*.xplane.pb"))
    traced = sorted(
        (ev.start_ns, ev.duration_ns, ev.name)
        for plane in ProfileData.from_file(path).planes
        for line in plane.lines for ev in line.events
        if ev.name.startswith("geoplan."))
    memory = sorted(memory, key=lambda s: s.start_ns)
    assert [n for _, _, n in traced] == [s.name for s in memory]
    offsets = []
    for (start, dur, _), s in zip(traced, memory):
        assert abs(dur - s.duration_ns) <= 50_000
        offsets.append(start - s.start_ns)
    assert max(offsets) - min(offsets) <= 50_000
