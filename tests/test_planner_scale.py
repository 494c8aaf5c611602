"""The fresh-plan anneal at PlanetLab's scale.

Its starting temperature follows the shape (``_anneal_tau0_frac``): the
8-node testbed keeps its schedule, and a wide platform starts colder.  At
128 sites of Table 1's law the planner's plans must beat the plans a
planner has without solving, priced by the benchmark's plain float64
reference (``bench.reference``), and the testbed's fixed start must not:
that is the fault the rule cures.  Whatever the anneal gives, the pick
never returns a plan priced above its un-annealed warm starts.
"""
import functools
import inspect
import json

import numpy as np
import pytest

import repro.core.optimize as opt
from bench import gen, reference
from bench.manifest import BENCH
from repro import tracing
from repro.core.platform import Platform, planetlab_platform

CONFIG = json.loads((BENCH / "configs" / "planetlab512.json").read_text())
SOLVER = CONFIG["solver"]
BARRIERS = CONFIG["barriers"]
SITES = 128
#: the fixed start fails on some requests and not on others: with the
#: random restarts drawn on the device, over seeds 1-8 its mean of the two
#: reads 0.74, 0.72, 0.85, 1.02, 0.46, 0.85, 0.88 and 1.09, the sized
#: start's 0.36-0.42.  On seeds 3, 4 and 8 it fails on both (0.82 and
#: 1.21 on seed 4)
SEED = 4


def _sites(n):
    """``n`` sites in planetlab512's continent split, 8:5:3."""
    us, eu = n * 8 // 16, n * 5 // 16
    return ([[f"US-{i}", "US"] for i in range(us)]
            + [[f"EU-{i}", "EU"] for i in range(eu)]
            + [[f"Asia-{i}", "Asia"] for i in range(n - us - eu)])


@functools.lru_cache(maxsize=None)
def _requests():
    p = dict(CONFIG["platform"], sites=_sites(SITES))
    out = []
    for i in range(2):
        g = gen.rng(SEED, 1, i)
        out.append(gen.planetlab(p, float(g.choice(CONFIG["alpha"])), g))
    return out


def _platform(a, i):
    return Platform(D=a["D"], B_sm=a["B_sm"], B_mr=a["B_mr"], C_m=a["C_m"],
                    C_r=a["C_r"], alpha=a["alpha"], cluster_s=a["cluster_s"],
                    cluster_m=a["cluster_m"], cluster_r=a["cluster_r"],
                    name=f"r{i}")


@functools.lru_cache(maxsize=None)
def _plans(start):
    """The two requests planned in one call at the cell's budget, the
    anneal starting where ``start`` says: ``sized`` (the rule) or
    ``fixed`` (the testbed's 0.3 at every size)."""
    rule = opt._anneal_tau0_frac
    if start == "fixed":
        opt._anneal_tau0_frac = lambda nS, nM, nR: 0.3
    try:
        return opt.optimize_plan_batch(
            [_platform(a, i) for i, a in enumerate(_requests())],
            SOLVER["mode"], barriers=tuple(BARRIERS),
            n_restarts=SOLVER["n_restarts"], steps=SOLVER["steps"],
            seeds=[0, 1])
    finally:
        opt._anneal_tau0_frac = rule


def _vs_simple(start):
    """Mean over the requests of a plan's reference makespan over the best
    simple plan's: the benchmark's ``plan_vs_simple``."""
    return float(np.mean([
        reference.makespan(a, r.plan.x, r.plan.y, BARRIERS)
        / min(reference.makespan(a, x, y, BARRIERS)
              for x, y in reference.simple_plans(a))
        for a, r in zip(_requests(), _plans(start))]))


def test_the_testbed_keeps_its_schedule(monkeypatch):
    assert opt._anneal_tau0_frac(8, 8, 8) == 0.3
    solver = inspect.signature(opt._solve_batch_many.__wrapped__).parameters
    assert solver["tau1_frac"].default == 1e-3
    calls, real = [], opt._solve_batch_many
    monkeypatch.setattr(opt, "_solve_batch_many", lambda *a, **k: (
        calls.append(k), real(*a, **k))[1])
    opt.optimize_plan(planetlab_platform(8, seed=3), n_restarts=4, steps=5)
    assert calls == [{"tau0_frac": 0.3}]


@pytest.mark.parametrize("axis", [0, 1, 2])
def test_the_start_never_warms_as_a_shape_grows(axis):
    sizes = [1, 2, 4, 8, 9, 16, 33, 64, 100, 128, 256, 480, 512, 1024]
    for base in [(8, 8, 8), (64, 64, 64), (480, 336, 180)]:
        taus = []
        for n in sizes:
            shape = list(base)
            shape[axis] = n
            taus.append(opt._anneal_tau0_frac(*shape))
        assert all(0 < t <= 0.3 for t in taus)
        assert all(b <= a for a, b in zip(taus, taus[1:])), (base, taus)


def test_a_sized_start_beats_the_simple_plans_at_128_sites():
    assert _vs_simple("sized") < 0.8


def test_the_fixed_start_fails_at_128_sites():
    assert _vs_simple("fixed") > 0.8


@pytest.mark.parametrize("start", ["sized", "fixed"])
def test_no_plan_is_priced_above_its_warm_starts(start):
    for i, (a, r) in enumerate(zip(_requests(), _plans(start))):
        p = _platform(a, i)
        lx, ly = opt._initial_logits(p, opt._WARM_STARTS, i)
        warm = min(reference.makespan(a, _softmax(x), _softmax(y), BARRIERS)
                   for x, y in zip(lx, ly))
        got = reference.makespan(a, r.plan.x, r.plan.y, BARRIERS)
        assert got <= warm * (1 + 1e-5)


def _softmax(logits):
    z = np.exp(logits - logits.max(axis=-1, keepdims=True))
    return z / z.sum(axis=-1, keepdims=True)


def test_the_solve_span_carries_the_anneals_size():
    platforms = [planetlab_platform(8, seed=s) for s in (1, 2)]
    tracing.clear()
    with tracing.recording():
        opt.optimize_plan_batch(platforms, n_restarts=4, steps=5,
                                seeds=[0, 1])
    try:
        solve, = [s for s in tracing.spans()
                  if s.name == "geoplan.plan.solve"]
    finally:
        tracing.clear()
    p = platforms[0]
    assert solve.attrs == dict(params=2 * 4 * (p.nS * p.nM + p.nR), steps=5,
                               tau0_frac=0.3)
