"""Planner-as-a-service throughput (ISSUE 7 / ROADMAP §1): the shape-keyed
solver-executable cache and its counters, batched-vs-sequential solve
equivalence, warm-started incremental replans (never modeled worse), the
measured solver-cost EMA behind ``swap_charge``, and the
``reactive_incremental`` online policy.

Compile-count assertions use *unique* static step budgets per test (jit
executables are process-wide, so a budget another test also uses would
make the first solve here a warm hit)."""
import itertools

import numpy as np
import pytest

from repro.api import GeoJob, GeoSchedule, OnlineConfig
from repro.core import (
    SolverService,
    SolveTimeEMA,
    get_online_config,
    get_online_policy,
    local_push_plan,
    optimize_plan,
    optimize_plan_batch,
    replan,
    replan_batch,
    reset_solver_cache_stats,
    solver_cache_stats,
    uniform_plan,
)
from repro.core.makespan import BARRIERS_GGL, CostModel, JobProgress
from repro.core.platform import CapacityTrace, Substrate, planetlab_platform
from repro.core.simulate import SimConfig


def _snap():
    return solver_cache_stats()


def _delta(before, after=None):
    after = after if after is not None else _snap()
    return {k: after[k] - before[k] for k in after}


def _platform(n=2, alpha=1.0, seed=0):
    return planetlab_platform(n, alpha=alpha, seed=seed)


def _small_platform(name="svc_small"):
    """A 2x2x2 platform — planetlab platforms always have 8 nodes, so this
    is the differently-*shaped* problem for cache-key tests."""
    return Substrate(
        B_sm=np.array([[200.0, 150.0], [150.0, 200.0]]),
        B_mr=np.array([[500.0, 100.0], [500.0, 100.0]]),
        C_m=np.array([100.0, 100.0]),
        C_r=np.array([2000.0, 2000.0]),
        cluster_s=np.array([0, 1]),
        cluster_m=np.array([0, 1]),
        cluster_r=np.array([0, 1]),
        name=name,
    ).view(np.array([8000.0, 8000.0]), 1.0, name=name)


# ---------------------------------------------------------------------------
# cache semantics via the compile/hit counters
# ---------------------------------------------------------------------------


class TestSolverCache:
    def test_same_shape_second_solve_zero_new_compiles(self):
        opts = dict(n_restarts=3, steps=41)
        optimize_plan(_platform(2, 0.7, seed=0), "e2e_multi", seed=1, **opts)
        before = _snap()
        optimize_plan(_platform(2, 1.3, seed=1), "e2e_multi", seed=2, **opts)
        d = _delta(before)
        assert d["compiles"] == 0, d
        assert d["hits"] == d["calls"] and d["misses"] == 0, d

    def test_different_shape_compiles_exactly_once(self):
        opts = dict(n_restarts=3, steps=43)
        optimize_plan(_platform(2, seed=0), "e2e_multi", seed=1, **opts)
        before = _snap()
        optimize_plan(_small_platform(), "e2e_multi", seed=1, **opts)
        d = _delta(before)
        assert d["compiles"] == 1 and d["misses"] == 1, d
        before = _snap()
        optimize_plan(_small_platform("svc_small_2"), "e2e_multi", seed=3,
                      **opts)
        d = _delta(before)
        assert d["compiles"] == 0 and d["hits"] == d["calls"], d

    def test_cache_survives_across_geoschedule_instances(self):
        opts = dict(n_restarts=3, steps=47)

        def schedule(tag):
            view = _small_platform(f"svc_sched_{tag}")
            sib = view.substrate.view(
                np.array([4000.0, 4000.0]), 1.0, name=f"svc_sib_{tag}"
            )
            return GeoSchedule([GeoJob(view), GeoJob(sib)])

        schedule("a").plan("independent", mode="e2e_multi",
                           barriers=BARRIERS_GGL, **opts)
        before = _snap()
        schedule("b").plan("independent", mode="e2e_multi",
                           barriers=BARRIERS_GGL, **opts)
        d = _delta(before)
        assert d["compiles"] == 0, (
            f"a fresh GeoSchedule re-compiled a known shape: {d}"
        )

    def test_reset_zeroes_counters_not_executables(self):
        opts = dict(n_restarts=3, steps=53)
        optimize_plan(_platform(2, seed=0), "e2e_multi", seed=1, **opts)
        reset_solver_cache_stats()
        assert _snap() == {"calls": 0, "hits": 0, "misses": 0,
                           "compiles": 0, "entries": 0, "shapes": 0}
        # the key set was cleared too (a repeat is a "miss" again), but the
        # jit executable survives: no new compile
        optimize_plan(_platform(2, seed=0), "e2e_multi", seed=1, **opts)
        d = _snap()
        assert d["misses"] == 1 and d["compiles"] == 0, d

    def test_solver_service_shares_the_process_cache(self):
        svc1 = SolverService(mode="e2e_multi", barriers=BARRIERS_GGL,
                             n_restarts=3, steps=59)
        svc1.plan(_platform(2, seed=0), seed=1)
        before = svc1.stats()
        svc2 = SolverService(mode="e2e_multi", barriers=BARRIERS_GGL,
                             n_restarts=3, steps=59)
        res = svc2.plan_many([_platform(2, seed=1), _platform(2, seed=2)],
                             seeds=[3, 4])
        assert len(res) == 2
        d = _delta(before, svc2.stats())
        # a batch of 2 is a NEW executable (B is a shape axis) but a second
        # service instance pays nothing extra for it afterwards
        before = svc2.stats()
        svc1.plan_many([_platform(2, seed=3), _platform(2, seed=4)],
                       seeds=[5, 6])
        d = _delta(before, svc1.stats())
        assert d["compiles"] == 0 and d["hits"] == d["calls"], d


# ---------------------------------------------------------------------------
# batched solves == sequential per-request solves
# ---------------------------------------------------------------------------


class TestBatchedEquivalence:
    # short anneals: the check targets the request-batching plumbing
    # (seeds, scales, per-request assembly), not f32 chaos — longer
    # anneals amplify vmap-vs-single XLA fusion round-off chaotically
    OPTS = dict(n_restarts=4, steps=10)

    @pytest.mark.parametrize("mode", ["e2e_multi", "myopic_multi",
                                      "e2e_push", "myopic_push",
                                      "e2e_shuffle"])
    def test_plan_batch_matches_sequential(self, mode):
        plats = [_platform(2, alpha=a, seed=s)
                 for s, a in enumerate((0.5, 1.0, 2.0))]
        seeds = [11, 12, 13]
        batch = optimize_plan_batch(plats, mode, barriers=BARRIERS_GGL,
                                    seeds=seeds, **self.OPTS)
        for p, s, b in zip(plats, seeds, batch):
            solo = optimize_plan(p, mode, barriers=BARRIERS_GGL, seed=s,
                                 **self.OPTS)
            np.testing.assert_allclose(b.plan.x, solo.plan.x, atol=1e-6)
            np.testing.assert_allclose(b.plan.y, solo.plan.y, atol=1e-6)
            assert b.makespan == pytest.approx(solo.makespan, rel=1e-4)

    def test_pinned_fixed_x_is_the_plans_x(self):
        """``e2e_shuffle`` with a pinned ``fixed_x`` returns exactly that
        push matrix, one request at a time and batched."""
        plats = [_platform(2, alpha=a, seed=s)
                 for s, a in enumerate((0.5, 1.0, 2.0))]
        seeds = [21, 22, 23]
        pinned = [np.asarray(local_push_plan(p).x) for p in plats]
        batch = optimize_plan_batch(plats, "e2e_shuffle",
                                    barriers=BARRIERS_GGL, seeds=seeds,
                                    fixed_x=pinned, **self.OPTS)
        for p, s, fx, b in zip(plats, seeds, pinned, batch):
            solo = optimize_plan(p, "e2e_shuffle", barriers=BARRIERS_GGL,
                                 seed=s, fixed_x=fx, **self.OPTS)
            np.testing.assert_array_equal(solo.plan.x, fx)
            np.testing.assert_array_equal(b.plan.x, fx)

    def test_replan_batch_matches_sequential(self):
        plats = [_platform(2, alpha=1.0, seed=s) for s in (0, 1, 2)]
        incs = [uniform_plan(p) for p in plats]
        seeds = [5, 6, 7]
        batch = replan_batch(plats, incs, barriers=BARRIERS_GGL,
                             seeds=seeds, **self.OPTS)
        for p, inc, s, b in zip(plats, incs, seeds, batch):
            solo = replan(p, inc, barriers=BARRIERS_GGL, seed=s,
                          **self.OPTS)
            np.testing.assert_allclose(b.plan.x, solo.plan.x, atol=1e-6)
            np.testing.assert_allclose(b.plan.y, solo.plan.y, atol=1e-6)

    def test_mixed_shapes_grouped_not_rejected(self):
        plats = [_platform(2, seed=0), _platform(4, seed=0),
                 _platform(2, seed=1)]
        res = optimize_plan_batch(plats, "e2e_multi", barriers=BARRIERS_GGL,
                                  seeds=[1, 2, 3], **self.OPTS)
        assert [r.plan.x.shape[1] for r in res] == [p.nM for p in plats]

    def test_seed_length_mismatch_raises(self):
        with pytest.raises(ValueError, match="one seed per platform"):
            optimize_plan_batch([_platform(2)], "e2e_multi", seeds=[1, 2],
                                **self.OPTS)
        with pytest.raises(ValueError, match="one incumbent"):
            replan_batch([_platform(2)], [], **self.OPTS)


# ---------------------------------------------------------------------------
# incremental warm-start replans
# ---------------------------------------------------------------------------


class TestIncrementalReplan:
    def test_never_modeled_worse_all_27_barrier_triples(self):
        p = _platform(2, alpha=1.0, seed=0)
        inc = uniform_plan(p)
        fresh = JobProgress.fresh(p)
        for barriers in itertools.product("GPL", repeat=3):
            inc_span = float(CostModel(p, barriers).price_residual(
                fresh, inc)["makespan"])
            res = replan(p, inc, barriers=barriers, n_restarts=2,
                         steps=200, seed=3, incremental=True)
            assert res.makespan <= inc_span + 1e-9, (
                f"incremental replan modeled worse than the incumbent "
                f"under {barriers}: {res.makespan} > {inc_span}"
            )

    def test_incremental_reuses_full_anneal_executable(self):
        """lr/tau are weak-typed traced scalars and the incremental budget
        reuses known steps values here, so flipping incremental must not
        trigger a new compile once both step counts are warm."""
        p = _platform(2, alpha=1.0, seed=0)
        inc = uniform_plan(p)
        # warm both executables: full (steps=200) and incremental (25)
        replan(p, inc, n_restarts=4, steps=200, seed=1, incremental=False)
        replan(p, inc, n_restarts=4, steps=200, seed=1, incremental=True)
        before = _snap()
        replan(p, inc, n_restarts=4, steps=200, seed=2, incremental=True)
        replan(p, inc, n_restarts=4, steps=200, seed=2, incremental=False)
        d = _delta(before)
        assert d["compiles"] == 0, d

    def test_shared_incremental_budget_divides_by_stack_size(self):
        """replan_schedule's incremental polish pays ONE anneal budget for
        the whole stack: the per-job step budget divided by the
        power-of-two-quantized live-job count, floored at 8."""
        from repro.core.optimize import (
            _incremental_budget,
            _shared_incremental_budget,
        )

        n, s = _incremental_budget(8, 200)
        assert (n, s) == (4, 25)
        assert _shared_incremental_budget(8, 200, 1) == (4, 25)
        assert _shared_incremental_budget(8, 200, 2) == (4, 12)
        # quantized divisor: 3 and 4 jobs land on the same static budget
        assert _shared_incremental_budget(8, 200, 3) \
            == _shared_incremental_budget(8, 200, 4) == (4, 8)
        # the floor: the stack can grow without the budget vanishing
        assert _shared_incremental_budget(8, 200, 100) == (4, 8)
        assert _shared_incremental_budget(8, 1600, 2) == (4, 100)

    def test_shared_incremental_schedule_warm_cache_and_not_worse(self):
        """Counter-verified (the satellite acceptance): a repeat
        incremental co-replan at the same stack size is a pure warm hit —
        zero new compiles — and the shared budget keeps the float64
        never-modeled-worse selection."""
        from repro.core.makespan import CostModel as _CM
        from repro.core.optimize import replan_schedule

        view = _small_platform("svc_sched_budget")
        sub = view.substrate
        sib = sub.view(np.array([4000.0, 4000.0]), 1.0, name="svc_bud_b")
        plans = [uniform_plan(view), uniform_plan(sib)]
        fresh = [JobProgress.fresh(view, 0), JobProgress.fresh(sib, 1)]
        opts = dict(barriers=BARRIERS_GGL, n_restarts=4, steps=1600)
        res = replan_schedule(sub, plans, fresh, seed=1, incremental=True,
                              **opts)
        assert res.makespan <= max(res.before) + 1e-9
        before = _snap()
        res2 = replan_schedule(sub, plans, fresh, seed=2,
                               incremental=True, **opts)
        d = _delta(before)
        assert d["compiles"] == 0, d
        assert res2.makespan <= max(res2.before) + 1e-9

    def test_incremental_starts_from_incumbent_basin(self):
        """A near-optimal incumbent survives the low-temperature polish:
        the result is the incumbent or something modeled at least as
        good, never a basin-hopped regression."""
        p = _platform(2, alpha=1.0, seed=1)
        good = optimize_plan(p, "e2e_multi", barriers=BARRIERS_GGL,
                             n_restarts=6, steps=150, seed=0)
        res = replan(p, good.plan, barriers=BARRIERS_GGL, n_restarts=4,
                     steps=200, seed=5, incremental=True)
        assert res.makespan <= good.makespan + 1e-6


# ---------------------------------------------------------------------------
# measured solver cost: SolveTimeEMA + OnlineConfig wiring
# ---------------------------------------------------------------------------


class TestSolveTimeEMA:
    def test_fixed_mode_pins_the_charge(self):
        ema = SolveTimeEMA(fixed=2.5)
        ema.observe(0.001)
        assert ema.charge_s() == 2.5

    def test_fallback_before_first_warm_sample(self):
        assert SolveTimeEMA().charge_s() == 1.0

    def test_measured_charge_quantizes_to_half_decades(self):
        ema = SolveTimeEMA()
        ema.observe(0.02)
        assert ema.charge_s() == pytest.approx(10.0 ** -1.5)
        for _ in range(50):
            ema.observe(0.8)
        assert ema.charge_s() == pytest.approx(1.0)

    def test_cold_compile_samples_are_excluded(self):
        ema = SolveTimeEMA()
        ema.observe(30.0, compiled=True)
        assert ema.charge_s() == 1.0 and ema.excluded == 1
        ema.observe(0.02)
        assert ema.charge_s() == pytest.approx(10.0 ** -1.5)
        ema.observe(30.0, compiled=True)  # still excluded when warm
        assert ema.charge_s() == pytest.approx(10.0 ** -1.5)

    def test_nonpositive_and_nonfinite_samples_excluded(self):
        ema = SolveTimeEMA()
        ema.observe(0.0)
        ema.observe(-1.0)
        ema.observe(float("nan"))
        assert ema.samples == 0 and ema.excluded == 3

    def test_validation(self):
        with pytest.raises(ValueError, match="fixed"):
            SolveTimeEMA(fixed=-1.0)
        with pytest.raises(ValueError, match="beta"):
            SolveTimeEMA(beta=0.0)


class TestOnlineConfigMeasuredCost:
    def test_defaults_are_measured_and_full_anneal(self):
        cfg = OnlineConfig()
        assert cfg.solver_cost_s is None
        assert cfg.incremental is False

    def test_negative_pinned_cost_rejected(self):
        with pytest.raises(ValueError, match="solver_cost_s"):
            OnlineConfig(solver_cost_s=-0.5)

    def test_reactive_incremental_policy_config(self):
        cfg = get_online_config("reactive_incremental")
        assert cfg.shared and cfg.incremental
        assert cfg.hysteresis == 1.0 and cfg.solver_cost_s is None
        fn = get_online_policy("reactive_incremental")
        assert fn("drift", None) and fn("arrival", None)
        assert not fn("tick", None)


# ---------------------------------------------------------------------------
# hysteresis invariants under measured cost (PR 3/4 behavior preserved)
# ---------------------------------------------------------------------------


def _drift_frozen():
    sub = Substrate(
        B_sm=np.array([[200.0, 150.0], [150.0, 200.0]]),
        B_mr=np.array([[500.0, 100.0], [500.0, 100.0]]),
        C_m=np.array([100.0, 100.0]),
        C_r=np.array([2000.0, 2000.0]),
        cluster_s=np.array([0, 1]),
        cluster_m=np.array([0, 1]),
        cluster_r=np.array([0, 1]),
        name="svc_pair",
    ).with_traces({
        "shuffle[m0->r0]": CapacityTrace.step(500.0, 2.0, 105.0),
        "shuffle[m1->r0]": CapacityTrace.step(500.0, 2.0, 105.0),
    })
    job = GeoJob(sub.view(np.array([8000.0, 8000.0]), 1.0, name="steady"))
    return GeoSchedule([job]).plan(
        "independent", mode="e2e_multi", barriers=BARRIERS_GGL,
        n_restarts=4, steps=80,
    )


def _drift_run(online, frozen=None):
    frozen = frozen if frozen is not None else _drift_frozen()
    return frozen.run_online(
        policy="reactive", cfg=SimConfig(barriers=BARRIERS_GGL),
        n_restarts=4, steps=80, online=online,
    )


class TestHysteresisInvariantsUnderMeasuredCost:
    def test_zero_hysteresis_decisions_identical_measured_vs_pinned(self):
        """hysteresis=0 swaps on any improvement — the charge (measured or
        pinned) multiplies a zero gate, so PR 3 behavior is bit-identical
        whichever cost model is in force."""
        measured = _drift_run(OnlineConfig(hysteresis=0.0))
        pinned = _drift_run(OnlineConfig(hysteresis=0.0, solver_cost_s=1.0))
        assert [
            (d.time, d.event, d.job, d.action) for d in measured.decisions
        ] == [
            (d.time, d.event, d.job, d.action) for d in pinned.decisions
        ]
        assert measured.makespan_online == pinned.makespan_online

    def test_infinite_hysteresis_never_solves(self):
        """hysteresis=inf reproduces `static` without even attempting a
        solve, so the measured EMA never gets a sample either."""
        frozen = _drift_frozen()
        before = _snap()
        report = _drift_run(OnlineConfig(hysteresis=float("inf")), frozen)
        assert _delta(before)["calls"] == 0
        assert report.swaps == ()
        static = _drift_run(OnlineConfig(hysteresis=float("inf"),
                                         solver_cost_s=123.0))
        assert report.makespan_online == static.makespan_online
