"""Reproductions of the paper's figures (one function per figure/table).

Each function returns a dict of results and emits CSV rows via
benchmarks.common.  Numbers to compare against the paper:

* Fig 4: model-vs-execution correlation (paper: R²=0.9412, slope 1.1464).
* Fig 5: e2e-multi vs myopic-multi vs uniform (82–87% / 65–82%).
* Fig 6: multi-phase vs best single-phase (37–64%).
* Fig 7: barrier relaxation, normalized to all-global (biggest win at α=1,
  late boundaries more valuable).
* Fig 8: 1/2/4/8 data centers — optimization wins grow with distribution.
* Fig 9: three applications, optimized plan vs Hadoop-like vs uniform
  (paper: 31–41% over vanilla Hadoop).
* Fig 10/11: dynamic mechanisms atop optimized/baseline plans.
* Fig 12: replication across slow links.
"""
from __future__ import annotations

import dataclasses
import itertools
import time
from typing import Dict

import numpy as np

from repro.api import (
    Arrival, GeoJob, GeoPipeline, GeoSchedule, OnlineConfig, split_sources,
)
from repro.compile_cache import compile_cache_off
from repro.core.makespan import BARRIERS_GGL
from repro.core.optimize import (
    optimize_plan,
    optimize_plan_batch,
    replan_batch,
    reset_solver_cache_stats,
    solver_cache_stats,
)
from repro.core.plan import local_push_plan, uniform_plan
from repro.core.platform import (
    CapacityTrace, FailureEvent, Substrate, planetlab_platform,
)
from repro.core.simulate import SimConfig, simulate, simulate_schedule
from repro.mapreduce.apps import (
    generate_documents, generate_logs, inverted_index, sessionization,
    word_count,
)

from .common import emit, timeit

_OPT = dict(n_restarts=16, steps=400)


def fig4_validation() -> Dict:
    """Correlate model-predicted makespan with discrete-event-executed
    makespan across plans × α × barrier configs (paper Fig 4)."""
    preds, meas = [], []
    configs = [("G", "P", "L"), ("P", "P", "L"), ("P", "G", "L"), ("G", "G", "L")]
    for alpha in [0.1, 1.0, 2.0]:
        p = planetlab_platform(8, alpha=alpha, seed=0)
        job = GeoJob(p)
        plans = {
            "uniform": uniform_plan(p),
            "opt": optimize_plan(p, "e2e_multi", **_OPT).plan,
        }
        for barriers, (pname, plan) in itertools.product(configs, plans.items()):
            job.with_plan(plan, barriers)
            preds.append(job.planned.makespan)
            meas.append(job.simulate(chunk_mb=32.0).makespan)
    preds, meas = np.asarray(preds), np.asarray(meas)
    slope, intercept = np.polyfit(preds, meas, 1)
    r2 = float(np.corrcoef(preds, meas)[0, 1] ** 2)
    p = planetlab_platform(8, alpha=1.0, seed=0)
    bench = GeoJob(p).with_plan(uniform_plan(p))
    us, _ = timeit(lambda: bench.simulate(chunk_mb=32.0))
    emit("fig4_validation", us, f"R2={r2:.4f};slope={slope:.3f}")
    return {"r2": r2, "slope": float(slope), "n": len(preds)}


def fig5_e2e_vs_myopic() -> Dict:
    out = {}
    for alpha in [0.1, 1.0, 10.0]:
        p = planetlab_platform(8, alpha=alpha, seed=0)
        us, res = timeit(
            lambda: {m: optimize_plan(p, m, **_OPT) for m in
                     ["uniform", "myopic_multi", "e2e_multi"]},
            repeats=1,
        )
        red_uni = 1 - res["e2e_multi"].makespan / res["uniform"].makespan
        red_myo = 1 - res["e2e_multi"].makespan / res["myopic_multi"].makespan
        emit(f"fig5_alpha{alpha}", us,
             f"vs_uniform={red_uni:.2%};vs_myopic={red_myo:.2%}")
        out[alpha] = {
            m: {"makespan": r.makespan, **r.breakdown} for m, r in res.items()
        }
    return out


def fig6_single_vs_multi() -> Dict:
    out = {}
    for alpha in [0.1, 1.0, 10.0]:
        p = planetlab_platform(8, alpha=alpha, seed=0)
        res = {m: optimize_plan(p, m, **_OPT) for m in
               ["uniform", "e2e_push", "e2e_shuffle", "e2e_multi"]}
        best_single = min(res["e2e_push"].makespan, res["e2e_shuffle"].makespan)
        red = 1 - res["e2e_multi"].makespan / best_single
        emit(f"fig6_alpha{alpha}", 0.0, f"multi_vs_best_single={red:.2%}")
        out[alpha] = {m: r.makespan for m, r in res.items()}
    return out


def fig7_barriers() -> Dict:
    """Relax one global barrier at a time to pipelining (optimized plans),
    normalized to the all-global optimum."""
    out = {}
    combos = {
        "all_global": ("G", "G", "G"),
        "pipe_push_map": ("P", "G", "G"),
        "pipe_map_shuffle": ("G", "P", "G"),
        "pipe_shuffle_reduce": ("G", "G", "P"),
        "all_pipelined": ("P", "P", "P"),
    }
    for alpha in [0.1, 1.0, 10.0]:
        p = planetlab_platform(8, alpha=alpha, seed=0)
        base = optimize_plan(p, "e2e_multi", barriers=("G", "G", "G"), **_OPT)
        row = {}
        for name, b in combos.items():
            r = optimize_plan(p, "e2e_multi", barriers=b, **_OPT)
            row[name] = r.makespan / base.makespan
        out[alpha] = row
        emit(f"fig7_alpha{alpha}", 0.0,
             ";".join(f"{k}={v:.3f}" for k, v in row.items()))
    return out


def fig8_environments() -> Dict:
    out = {}
    for ndc in [1, 2, 4, 8]:
        for alpha in [0.1, 1.0, 10.0]:
            p = planetlab_platform(ndc, alpha=alpha, seed=0)
            res = {m: optimize_plan(p, m, **_OPT).makespan
                   for m in ["uniform", "myopic_multi", "e2e_multi"]}
            out[f"{ndc}dc_alpha{alpha}"] = res
            emit(
                f"fig8_{ndc}dc_alpha{alpha}", 0.0,
                f"myopic_ratio={res['myopic_multi']/res['uniform']:.3f};"
                f"e2e_ratio={res['e2e_multi']/res['uniform']:.3f}",
            )
    return out


def fig9_applications() -> Dict:
    """Three real applications through the :class:`repro.api.GeoJob` facade;
    makespan = actual byte movement priced through the emulated PlanetLab
    platform by the same cost model the planner optimized."""
    out = {}
    apps = {
        "word_count": (word_count(), generate_documents(600, 60, seed=5)),
        "sessionization": (sessionization(gap=1000), generate_logs(40_000, 400, seed=5)),
        "inverted_index": (inverted_index(), generate_documents(600, 60, seed=6)),
    }
    for name, (app, (keys, vals)) in apps.items():
        probe = planetlab_platform(8, alpha=1.0, seed=0)
        srcs = split_sources(keys, vals, probe.nS)
        # probe-measure the app's alpha + input volumes to feed the model
        job = GeoJob(probe, app).calibrate(srcs)
        p = job.platform
        setups = {
            "uniform": lambda: job.with_plan(uniform_plan(p), BARRIERS_GGL),
            "hadoop_local": lambda: job.with_plan(local_push_plan(p), BARRIERS_GGL),
            "optimized": lambda: job.plan("e2e_multi", barriers=BARRIERS_GGL,
                                          **_OPT),
        }
        row, err = {}, {}
        for pname, setup in setups.items():
            setup()
            us, report = timeit(lambda: job.execute(srcs), repeats=1)
            row[pname] = report.measured
            err[pname] = report.model_error()
        out[name] = {"alpha": p.alpha, "model_error": err, **row}
        red = 1 - row["optimized"]["makespan"] / row["hadoop_local"]["makespan"]
        emit(f"fig9_{name}", us,
             f"alpha={p.alpha:.2f};vs_hadoop={red:.2%};"
             f"model_err={err['optimized']:+.1%}")
    return out


def fig10_dynamics() -> Dict:
    """Dynamic mechanisms (speculation / + stealing) atop the optimized and
    the Hadoop-baseline plans, with runtime stragglers the planner cannot
    see."""
    p = planetlab_platform(8, alpha=1.0, seed=0)
    jobs = {
        "optimized": GeoJob(p).plan("e2e_multi", barriers=BARRIERS_GGL, **_OPT),
        "hadoop_baseline": GeoJob(p).with_plan(local_push_plan(p), BARRIERS_GGL),
    }
    strag = {("m", 2): 4.0}
    out = {}
    for pname, job in jobs.items():
        row = {}
        for dyn, cfg in {
            "static": SimConfig(barriers=BARRIERS_GGL, stragglers=strag),
            "spec": SimConfig(barriers=BARRIERS_GGL, stragglers=strag,
                              speculation=True),
            "spec+steal": SimConfig(barriers=BARRIERS_GGL, stragglers=strag,
                                    speculation=True, stealing=True),
        }.items():
            row[dyn] = job.simulate(cfg).makespan
        out[pname] = row
        emit(f"fig10_{pname}", 0.0,
             ";".join(f"{k}={v:.0f}s" for k, v in row.items()))
    return out


def fig12_replication() -> Dict:
    p = planetlab_platform(8, alpha=1.0, seed=0)
    plan = local_push_plan(p)
    out = {}
    for r in [1, 2, 3]:
        res = simulate(
            p, plan,
            SimConfig(barriers=BARRIERS_GGL, replication=r,
                      cross_cluster_replication=r > 1),
        ).as_dict()
        out[r] = res
        emit(f"fig12_replication{r}", 0.0,
             f"makespan={res['makespan']:.0f}s;push={res['push_end']:.0f}s")
    return out


def schedule_contention() -> Dict:
    """Multi-job scheduling on a shared substrate (PR 2): two concurrent
    jobs where per-job-myopic ("independent") planning collides on the
    mapper only one job can actually reach fast, while "sequential" and
    "joint" spread the second job out — the paper's end-to-end-vs-myopic
    gap, across jobs."""
    sub = Substrate(
        B_sm=np.array([[10_000.0, 1.0], [10_000.0, 10_000.0]]),
        B_mr=np.full((2, 2), 10_000.0),
        C_m=np.array([50.0, 50.0]),
        C_r=np.array([10_000.0, 10_000.0]),
        cluster_s=np.array([0, 1]),
        cluster_m=np.array([0, 1]),
        cluster_r=np.array([0, 1]),
        name="contended_pair",
    )
    jobs = [
        GeoJob(sub.view(np.array([40_000.0, 0.0]), 1.0, name="pinned")),
        GeoJob(sub.view(np.array([0.0, 40_000.0]), 1.0, name="flexible")),
    ]
    out = {}
    for policy in ("independent", "sequential", "joint"):
        report = (
            GeoSchedule(jobs)
            .plan(policy=policy, mode="e2e_multi", barriers=BARRIERS_GGL,
                  **_OPT)
            .simulate()
        )
        out[policy] = {
            "modeled": report.makespan_modeled,
            "simulated": report.makespan_sim,
            "contended_resources": len(report.contended()),
            **report.sim.as_dict(),
        }
        emit(f"schedule_{policy}", 0.0,
             f"modeled={report.makespan_modeled:.0f}s;"
             f"sim={report.makespan_sim:.0f}s")
    gap = 1 - out["joint"]["simulated"] / out["independent"]["simulated"]
    emit("schedule_joint_vs_independent", 0.0, f"reduction={gap:.0%}")
    out["joint_vs_independent_reduction"] = gap
    return out


def pipeline_chain_substrate() -> Substrate:
    """The ``pipeline_chain`` fabric: asymmetric *outgoing* access.  Node 0
    hosts the fast reducer (r0: 300 MB/s vs r1: 60 MB/s) but its outgoing
    push links crawl at 4 MB/s; node 1's reducer is slow but its push
    links run at wire speed.  Placing a non-final stage's reduce output on
    r0 is locally optimal and strands the next stage's input behind the
    4 MB/s links — the cross-stage trap stagewise planning walks into."""
    return Substrate(
        B_sm=np.array([[4.0, 4.0], [200.0, 200.0]]),
        B_mr=np.full((2, 2), 200.0),
        C_m=np.array([100.0, 100.0]),
        C_r=np.array([300.0, 60.0]),
        cluster_s=np.array([0, 1]),
        cluster_m=np.array([0, 1]),
        cluster_r=np.array([0, 1]),
        name="pipeline_chain",
    )


def pipeline_chain() -> Dict:
    """Multi-stage pipelines (PR 5): a 3-stage chain where ``end_to_end``
    cross-stage planning beats ``stagewise``.  Stagewise places stage-k
    reducers where stage k finishes fastest (the fast r0), stranding stage
    k+1's 6 GB behind node 0's 4 MB/s outgoing links; end-to-end feels the
    downstream push cost through the inter-stage D coupling and keeps
    non-final reduce output on the well-connected node, conceding reduce
    speed to win the pipeline.  Both modeled (critical-path composition)
    and simulated (real per-source release gating) sides are emitted."""
    sub = pipeline_chain_substrate()

    def stages():
        return [
            GeoJob(sub.view(np.array([0.0, 6000.0]), 1.0, name="ingest")),
            GeoJob(sub.view(np.zeros(2), 1.0, name="transform")),
            GeoJob(sub.view(np.zeros(2), 0.5, name="aggregate")),
        ]

    out = {}
    for mode in ("stagewise", "end_to_end"):
        pipe = GeoPipeline(stages(), name=f"chain_{mode}")
        us, report = timeit(
            lambda: pipe.plan(mode, stage_mode="e2e_multi",
                              barriers=BARRIERS_GGL, **_OPT).simulate(),
            repeats=1,
        )
        out[mode] = {
            "modeled": report.makespan_modeled,
            "simulated": report.makespan_sim,
            "stage_makespans": list(report.result.stage_makespans),
            "stage_finishes": list(report.result.finishes),
        }
        emit(f"pipeline_chain_{mode}", us,
             f"modeled={report.makespan_modeled:.0f}s;"
             f"sim={report.makespan_sim:.0f}s")
    gap = 1 - out["end_to_end"]["simulated"] / out["stagewise"]["simulated"]
    emit("pipeline_chain_e2e_vs_stagewise", 0.0, f"reduction={gap:.0%}")
    out["e2e_vs_stagewise_reduction"] = gap
    return out


def schedule_online() -> Dict:
    """Online control plane (PR 3): re-planning over streaming arrivals and
    drifting capacities.  A steady job's nominal optimum concentrates its
    shuffle on the fast backbone links into reducer r0; both links degrade
    250x at t=105s — mid-shuffle — and a second job arrives at t=50s, mid
    map phase.  The *frozen joint* plan (clairvoyant about the arrival,
    blind to the drift) crawls through the degraded links; ``reactive``
    re-plans each job's residual at the arrival/drift events and swaps the
    not-yet-committed chunks onto the healthy path; ``horizon`` does the
    same on a fixed 40s cadence."""
    sub = Substrate(
        B_sm=np.full((2, 2), 200.0),
        B_mr=np.array([[500.0, 100.0], [500.0, 100.0]]),
        C_m=np.array([100.0, 100.0]),
        C_r=np.array([2000.0, 2000.0]),
        cluster_s=np.array([0, 1]),
        cluster_m=np.array([0, 1]),
        cluster_r=np.array([0, 1]),
        name="online_pair",
    ).with_traces({
        "shuffle[m0->r0]": CapacityTrace.step(500.0, 2.0, 105.0),
        "shuffle[m1->r0]": CapacityTrace.step(500.0, 2.0, 105.0),
    })
    steady = GeoJob(sub.view(np.array([8000.0, 8000.0]), 1.0, name="steady"))
    late_view = sub.view(np.array([4000.0, 4000.0]), 1.0, name="late")
    cfg = SimConfig(barriers=BARRIERS_GGL)
    t_arrival = 50.0

    # the frozen baseline: both jobs planned jointly offline, on nominal
    # capacities, with full knowledge of the release times
    frozen = GeoSchedule([steady, GeoJob(late_view)]).plan(
        "joint", mode="e2e_multi", barriers=BARRIERS_GGL, **_OPT
    )
    frozen_sim = simulate_schedule(
        [(steady.platform, frozen.planned.plans[0], cfg),
         (late_view, frozen.planned.plans[1],
          SimConfig(barriers=BARRIERS_GGL, start_time=t_arrival))],
        substrate=sub,
    )
    out = {"frozen_joint": {"simulated": frozen_sim.makespan,
                            **frozen_sim.as_dict()}}
    emit("schedule_online_frozen", 0.0, f"sim={frozen_sim.makespan:.0f}s")

    sched = GeoSchedule([steady]).plan(
        "independent", mode="e2e_multi", barriers=BARRIERS_GGL, **_OPT
    )
    for policy, extra in (("static", {}), ("reactive", {}),
                          ("horizon", {"replan_dt": 40.0})):
        arrival = Arrival(
            GeoJob(late_view).with_plan(frozen.planned.plans[1],
                                        BARRIERS_GGL),
            t_arrival,
        )
        us, report = timeit(
            lambda: sched.run_online(
                policy=policy, arrivals=[arrival], cfg=cfg,
                n_restarts=_OPT["n_restarts"], steps=_OPT["steps"], **extra,
            ),
            repeats=1,
        )
        out[policy] = {
            "simulated": report.makespan_online,
            "static_baseline": report.makespan_static,
            "improvement_vs_static": report.improvement,
            "decisions": len(report.decisions),
            "swaps": len(report.swaps),
            **report.sim.as_dict(),
        }
        emit(f"schedule_online_{policy}", us,
             f"sim={report.makespan_online:.0f}s;"
             f"swaps={len(report.swaps)}")
    gap = 1 - out["reactive"]["simulated"] / out["frozen_joint"]["simulated"]
    emit("schedule_online_reactive_vs_frozen", 0.0, f"reduction={gap:.0%}")
    out["reactive_vs_frozen_joint_reduction"] = gap
    return out


def shared_online_substrate(t_drift: float = 110.0) -> Substrate:
    """The ``schedule_online_shared`` fabric: asymmetric reducer access plus
    a mid-shuffle compute drift.  The steady job's sources (s0/s1) reach
    mappers m0/m1, which see both reducers; the late job's sources (s2/s3)
    reach m2/m3, whose only usable shuffle path is into r1 — the late job
    is *stuck* on r1, a fact only shared-capacity pricing can see.  The
    fast reducer r0 degrades 300→40 MB/s at ``t_drift`` (mid-shuffle of
    the steady job); two later trace steps on dead push links are pure
    nuisance events — nothing real changes, but event-triggered policies
    fire, and hysteresis-free re-planning swaps on the solver's epsilon
    improvements (thrash) while the replan-cost charge rejects them."""
    return Substrate(
        B_sm=np.array([
            [200.0, 200.0, 1.0, 1.0],
            [200.0, 200.0, 1.0, 1.0],
            [1.0, 1.0, 200.0, 200.0],
            [1.0, 1.0, 200.0, 200.0],
        ]),
        B_mr=np.array([
            [200.0, 200.0],
            [200.0, 200.0],
            [1.0, 200.0],
            [1.0, 200.0],
        ]),
        C_m=np.array([100.0, 100.0, 100.0, 100.0]),
        C_r=np.array([300.0, 60.0]),
        cluster_s=np.array([0, 0, 1, 1]),
        cluster_m=np.array([0, 0, 1, 1]),
        cluster_r=np.array([0, 1]),
        name="online_shared",
    ).with_traces({
        "reduce[r0]": CapacityTrace.step(300.0, 40.0, t_drift),
        "push[s0->m2]": CapacityTrace.step(1.0, 0.9, 150.0),
        "push[s1->m2]": CapacityTrace.step(1.0, 0.9, 180.0),
    })


def schedule_online_shared() -> Dict:
    """Shared-capacity residual co-replanning with replan-cost hysteresis
    (PR 4): overlapping jobs + mid-shuffle drift, where solo-residual
    re-planning thrashes and co-replanning wins.

    After the drift, the steady job's solo replan balances its residual
    reduce load against the *raw* capacities (40 vs 60 MB/s) — blind to
    the late job's 12 GB already stuck on r1 — and spills onto the reducer
    the other job cannot leave.  ``reactive_shared`` co-replans both
    residuals through shared pricing, keeps the flexible job on the
    degraded-but-private r0, and its hysteresis rejects the epsilon swaps
    the nuisance drift events bait out of hysteresis-free co-replanning."""
    sub = shared_online_substrate()
    steady = GeoJob(sub.view(np.array([8000.0, 8000.0, 0.0, 0.0]), 1.0,
                             name="steady"))
    late_view = sub.view(np.array([0.0, 0.0, 6000.0, 6000.0]), 1.0,
                         name="late")
    cfg = SimConfig(barriers=BARRIERS_GGL)
    t_arrival = 50.0

    frozen = GeoSchedule([steady, GeoJob(late_view)]).plan(
        "joint", mode="e2e_multi", barriers=BARRIERS_GGL, **_OPT
    )
    frozen_sim = simulate_schedule(
        [(steady.platform, frozen.planned.plans[0], cfg),
         (late_view, frozen.planned.plans[1],
          SimConfig(barriers=BARRIERS_GGL, start_time=t_arrival))],
        substrate=sub,
    )
    out = {"frozen_joint": {"simulated": frozen_sim.makespan,
                            **frozen_sim.as_dict()}}
    emit("schedule_online_shared_frozen", 0.0,
         f"sim={frozen_sim.makespan:.0f}s")

    sched = GeoSchedule([steady]).plan(
        "independent", mode="e2e_multi", barriers=BARRIERS_GGL, **_OPT
    )
    variants = (
        ("reactive_solo", "reactive", None),
        ("reactive_shared", "reactive_shared", None),
        ("shared_no_hysteresis", "reactive_shared",
         OnlineConfig(shared=True, hysteresis=0.0)),
    )
    for name, policy, online in variants:
        arrival = Arrival(
            GeoJob(late_view).with_plan(frozen.planned.plans[1],
                                        BARRIERS_GGL),
            t_arrival,
        )
        us, report = timeit(
            lambda: sched.run_online(
                policy=policy, arrivals=[arrival], cfg=cfg, online=online,
                n_restarts=_OPT["n_restarts"], steps=_OPT["steps"],
            ),
            repeats=1,
        )
        out[name] = {
            "simulated": report.makespan_online,
            "static_baseline": report.makespan_static,
            "improvement_vs_static": report.improvement,
            "decisions": len(report.decisions),
            "swaps": len(report.swaps),
            "rejected": len(report.rejected),
            "charged_s": report.charged_s,
            **report.sim.as_dict(),
        }
        emit(f"schedule_online_shared_{name}", us,
             f"sim={report.makespan_online:.0f}s;"
             f"swaps={len(report.swaps)};rejected={len(report.rejected)}")
    gap_frozen = 1 - (out["reactive_shared"]["simulated"]
                      / out["frozen_joint"]["simulated"])
    gap_solo = 1 - (out["reactive_shared"]["simulated"]
                    / out["reactive_solo"]["simulated"])
    emit("schedule_online_shared_vs_frozen", 0.0,
         f"reduction={gap_frozen:.0%}")
    emit("schedule_online_shared_vs_solo", 0.0, f"reduction={gap_solo:.0%}")
    out["shared_vs_frozen_joint_reduction"] = gap_frozen
    out["shared_vs_solo_reduction"] = gap_solo
    return out


def failover_substrate(failures=()) -> Substrate:
    """The ``schedule_failover`` fabric: two clusters (A: s0/s1, m0/m1,
    r0/r1 — B: s2, m2, r2) with a fast wide-area shuffle path into B's big
    reducer r2 (500 MB/s compute) that the joint plan leans on.  The fault
    sequence kills r1 mid-shuffle and then partitions cluster B with a
    late repair — severing exactly the path the plan concentrated on."""
    sub = Substrate(
        B_sm=np.array([
            [200.0, 200.0, 1.0],
            [200.0, 200.0, 1.0],
            [1.0, 1.0, 200.0],
        ]),
        B_mr=np.array([
            [200.0, 200.0, 150.0],
            [200.0, 200.0, 150.0],
            [2.0, 2.0, 200.0],
        ]),
        C_m=np.array([100.0, 100.0, 100.0]),
        C_r=np.array([100.0, 40.0, 500.0]),
        cluster_s=np.array([0, 0, 1]),
        cluster_m=np.array([0, 0, 1]),
        cluster_r=np.array([0, 0, 1]),
        name="failover",
    )
    return sub.with_failures(list(failures)) if failures else sub


def schedule_failover() -> Dict:
    """Failure injection & recovery (ROADMAP §2): a reducer death
    mid-shuffle plus a cluster partition with a late repair, against a
    frozen clairvoyant joint plan that concentrated shuffle on the paths
    the faults sever.

    The frozen plan parks everything bound for the partitioned cluster
    until repair (t=400s), so its makespan is pinned to the repair time.
    ``reactive_shared`` observes each fault, un-delivers the lost output,
    co-replans the residual around the dead reducer and severed links, and
    pulls the parked queue back onto surviving paths; ``reactive_failover``
    additionally toggles speculative re-execution at each fault decision.
    Both run with ``replication=2`` so lost map output re-executes from
    surviving replicas instead of re-pushing over the WAN."""
    FAILURES = [
        FailureEvent.reducer_kill(1, 115.0),
        FailureEvent.cluster_partition(1, 118.0, 400.0),
    ]
    sub0 = failover_substrate()
    d_steady = np.array([5000.0, 5000.0, 0.0])
    d_late = np.array([3000.0, 3000.0, 0.0])
    steady = GeoJob(sub0.view(d_steady, 1.0, name="steady"))
    late = GeoJob(sub0.view(d_late, 1.0, name="late"))
    frozen = GeoSchedule([steady, late]).plan(
        "joint", mode="e2e_multi", barriers=BARRIERS_GGL, **_OPT
    )
    cfg = SimConfig(barriers=BARRIERS_GGL, replication=2, audit=True)

    subf = failover_substrate(FAILURES)
    sv = subf.view(d_steady, 1.0, name="steady")
    lv = subf.view(d_late, 1.0, name="late")
    frozen_sim = simulate_schedule(
        [(sv, frozen.planned.plans[0], cfg),
         (lv, frozen.planned.plans[1], cfg)],
        substrate=subf,
    )
    out = {"frozen_joint": {"simulated": frozen_sim.makespan,
                            **frozen_sim.as_dict()}}
    emit("schedule_failover_frozen", 0.0, f"sim={frozen_sim.makespan:.0f}s")

    for policy in ("reactive_shared", "reactive_failover"):
        sched = GeoSchedule(
            [GeoJob(sv).with_plan(frozen.planned.plans[0], BARRIERS_GGL),
             GeoJob(lv).with_plan(frozen.planned.plans[1], BARRIERS_GGL)]
        ).with_plans()
        us, report = timeit(
            lambda: sched.run_online(policy=policy, cfg=cfg, **_OPT),
            repeats=1,
        )
        out[policy] = {
            "simulated": report.makespan_online,
            "static_baseline": report.makespan_static,
            "improvement_vs_static": report.improvement,
            "decisions": len(report.decisions),
            "swaps": len(report.swaps),
            "rejected": len(report.rejected),
            "charged_s": report.charged_s,
            **report.sim.as_dict(),
        }
        emit(f"schedule_failover_{policy}", us,
             f"sim={report.makespan_online:.0f}s;"
             f"swaps={len(report.swaps)};rejected={len(report.rejected)}")
    margin = 1 - (out["reactive_shared"]["simulated"]
                  / out["frozen_joint"]["simulated"])
    emit("schedule_failover_margin", 0.0, f"margin={margin:.0%}")
    out["failover_margin"] = margin
    out["failover_margin_speculative"] = 1 - (
        out["reactive_failover"]["simulated"]
        / out["frozen_joint"]["simulated"]
    )
    return out


def bench_planner() -> Dict:
    """Planner-as-a-service throughput (ROADMAP §1): plans/sec for batched
    same-shape solves, p50/p99 single-solve latency cold vs warm, the
    incremental-vs-full replan speedup, and the compile counts behind them
    — all gated by compare.py like any makespan."""
    n_restarts = _OPT["n_restarts"]
    # a step budget no other scenario uses: steps is a static jit arg, so
    # this guarantees the first solve below is a genuinely cold compile
    # even when the full benchmark suite ran first in this process
    steps = _OPT["steps"] + 3
    p = planetlab_platform(8, alpha=1.0, seed=3)
    opts = dict(n_restarts=n_restarts, steps=steps)

    reset_solver_cache_stats()
    with compile_cache_off():  # nor may the persistent cache serve it
        t0 = time.perf_counter()
        optimize_plan(p, "e2e_multi", seed=0, **opts)
        cold_s = time.perf_counter() - t0

    warm_lat = []
    for s in range(1, 9):
        t0 = time.perf_counter()
        optimize_plan(p, "e2e_multi", seed=s, **opts)
        warm_lat.append(time.perf_counter() - t0)
    p50_ms = float(np.percentile(warm_lat, 50) * 1e3)
    p99_ms = float(np.percentile(warm_lat, 99) * 1e3)

    # batched throughput: 8 concurrent same-shape requests, one dispatch
    views = [planetlab_platform(8, alpha=1.0, seed=s) for s in range(8)]
    seeds = list(range(10, 18))
    optimize_plan_batch(views, "e2e_multi", seeds=seeds, **opts)  # warm B=8
    t0 = time.perf_counter()
    optimize_plan_batch(views, "e2e_multi", seeds=seeds, **opts)
    batch_s = time.perf_counter() - t0
    plans_per_s = len(views) / batch_s

    # incremental replan vs full anneal, each timed warm through the
    # batched service path run_online actually uses (replan_batch over the
    # 8 views — one dispatch, so Python/dispatch overhead is amortized the
    # way it is in production)
    incumbents = [
        r.plan for r in optimize_plan_batch(views, "e2e_multi",
                                            seeds=seeds, **opts)
    ]
    # the speedup is measured at the PRODUCTION anneal budget (the library
    # default run_online uses), not the quick smoke budget — at tiny step
    # counts the fixed per-request cost (f64 pricing, batch assembly)
    # swamps the anneal and understates what the online loop gains
    ropts = dict(n_restarts=n_restarts, steps=500)
    for incremental in (False, True):
        replan_batch(views, incumbents, seeds=seeds,
                     incremental=incremental, **ropts)

    def best_of(incremental, repeats=3):
        # best-of-N: the min is the least scheduler-noise-polluted sample
        best = float("inf")
        for _ in range(repeats):
            t0 = time.perf_counter()
            replan_batch(views, incumbents, seeds=seeds,
                         incremental=incremental, **ropts)
            best = min(best, time.perf_counter() - t0)
        return best

    full_s = best_of(incremental=False)
    inc_s = best_of(incremental=True)

    stats = solver_cache_stats()
    out = {
        "throughput": {
            "plans_per_s": plans_per_s,
            "warm_vs_cold_speedup": cold_s / (p50_ms / 1e3),
            "incremental_speedup": full_s / inc_s,
        },
        "latency": {"cold_s": cold_s, "p50_ms": p50_ms, "p99_ms": p99_ms},
        "cache": {"compiles": stats["compiles"], "hits": stats["hits"],
                  "misses": stats["misses"]},
    }
    emit("bench_planner_throughput", batch_s * 1e6,
         f"plans_per_s={plans_per_s:.1f};"
         f"warm_vs_cold={out['throughput']['warm_vs_cold_speedup']:.0f}x")
    emit("bench_planner_latency", np.mean(warm_lat) * 1e6,
         f"cold={cold_s:.2f}s;p50={p50_ms:.0f}ms;p99={p99_ms:.0f}ms")
    emit("bench_planner_incremental", inc_s * 1e6,
         f"full={full_s*1e3:.0f}ms;incremental={inc_s*1e3:.0f}ms;"
         f"speedup={out['throughput']['incremental_speedup']:.1f}x")
    return out


def bench_scale() -> Dict:
    """Scale-wall benchmark (ROADMAP §3): the three-tier scale scenario.

    Three gated measurements on the edge->region->backbone substrates of
    :mod:`repro.core.topology`:

    * **100-node tier** — the same 100-job mix executed by the scalar and
      the vectorized DES hot path: events/sec for both, the speedup, and
      a makespan cross-check (the two paths are bit-identical; the
      vectorized one is gated at >= 5x by the baseline floor).
    * **rel-error contract** — fluid-mode vs per-chunk DES makespan over
      all 27 barrier triples at fine chunking (``rel_err_pct`` is gated
      one-sided: it may only shrink, with headroom up to the documented
      2% ceiling).
    * **1000-node tier** — ~10^3 nodes x 100 jobs in fluid mode: the
      deterministic makespan is gated; wall-clock is reported (CI
      budget: < 60 s).
    """
    from repro.core.simulate import open_schedule
    from repro.core.topology import scale_job_mix, scale_tier_substrate

    # -- 100-node tier: scalar vs vectorized DES --------------------------
    sub = scale_tier_substrate(seed=0)  # 4x12 edges + 4x8 maps + 2x6 reds
    n_nodes = sub.nS + sub.nM + sub.nR
    entries = scale_job_mix(
        sub, n_jobs=100, seed=3, base_cfg=SimConfig(chunk_mb=16.0)
    )

    def run_des(vectorized: bool):
        jobs = [
            (p, plan, dataclasses.replace(c, vectorized=vectorized))
            for p, plan, c in entries
        ]
        eng = open_schedule(jobs, substrate=sub)  # build excluded: the
        t0 = time.perf_counter()                  # hot path is run()
        res = eng.run()
        wall = time.perf_counter() - t0
        events = sum(r.n_chunks for r in res.resources.values())
        return res, wall, events

    res_s, wall_scalar, events = run_des(vectorized=False)
    res_v, wall_vec, events_v = run_des(vectorized=True)
    speedup = wall_scalar / wall_vec
    ev_per_s_scalar = events / wall_scalar
    ev_per_s_vec = events_v / wall_vec

    # -- fluid-vs-DES rel-error over the 27 barrier triples ---------------
    p = planetlab_platform(4, alpha=1.3, seed=5)
    plan = uniform_plan(p)
    rel_errs = {}
    for trip in itertools.product("GLP", repeat=3):
        b = "".join(trip)
        des = simulate(p, plan, SimConfig(barriers=b, chunk_mb=4.0,
                                          vectorized=True, audit=True))
        fl = simulate(p, plan, SimConfig(barriers=b, mode="fluid",
                                         audit=True))
        rel_errs[b] = abs(fl.makespan - des.makespan) / des.makespan
    rel_err_pct = 100.0 * max(rel_errs.values())

    # -- 1000-node tier: fluid mode ---------------------------------------
    sub1k = scale_tier_substrate(
        n_regions=12, edges_per_region=40, mappers_per_region=28,
        n_backbone=4, reducers_per_backbone=45, seed=1,
    )
    n_nodes_1k = sub1k.nS + sub1k.nM + sub1k.nR
    entries_1k = scale_job_mix(
        sub1k, n_jobs=100, seed=3, arrival_spread_s=600.0,
        base_cfg=SimConfig(mode="fluid"),
    )
    eng = open_schedule(entries_1k, substrate=sub1k)
    t0 = time.perf_counter()
    res_1k = eng.run()
    wall_1k = time.perf_counter() - t0

    out = {
        "des_100": {
            "n_nodes": n_nodes,
            "events": events,
            "events_per_s": ev_per_s_vec,
            "events_per_s_scalar": ev_per_s_scalar,
            "speedup_x": speedup,
            "makespan": res_v.makespan,
            "matches_scalar": bool(
                abs(res_v.makespan - res_s.makespan) < 1e-9
            ),
        },
        "fluid_vs_des": {
            "rel_err_pct": rel_err_pct,
            "worst_triple": max(rel_errs, key=rel_errs.get),
        },
        "fluid_1000": {
            "n_nodes": n_nodes_1k,
            "n_jobs": len(entries_1k),
            "makespan": res_1k.makespan,
            "wall_s": wall_1k,
        },
    }
    emit("scale_tier_des100", wall_vec * 1e6,
         f"events_per_s={ev_per_s_vec:.0f};speedup={speedup:.1f}x;"
         f"match={out['des_100']['matches_scalar']}")
    emit("scale_tier_fluid_relerr", 0.0,
         f"max_rel_err={rel_err_pct:.3f}%;"
         f"worst={out['fluid_vs_des']['worst_triple']}")
    emit("scale_tier_fluid1000", wall_1k * 1e6,
         f"nodes={n_nodes_1k};jobs={len(entries_1k)};"
         f"makespan={res_1k.makespan:.0f}s")
    return out


def online_brownout(
    n_regions: int = 12, edges_per_region: int = 40,
    mappers_per_region: int = 28, n_backbone: int = 4,
    reducers_per_backbone: int = 45, n_jobs: int = 100,
) -> Dict:
    """The scale tier's online scenario: a seeded 3-tier substrate (996
    nodes at the defaults) whose first backbone's reducers brown out to 5%
    at t=250 s, and a fluid-mode job mix whose last tenth of releases
    stream in at t=300 s and t=480 s.  Returns the planned ``schedule``,
    its ``arrivals``, per-job ``cfgs``, the pinned ``reactive_shared``
    ``online`` config, ``n_nodes`` and ``n_jobs``."""
    from repro.core.topology import scale_job_mix, scale_tier_substrate

    sub0 = scale_tier_substrate(
        n_regions=n_regions, edges_per_region=edges_per_region,
        mappers_per_region=mappers_per_region, n_backbone=n_backbone,
        reducers_per_backbone=reducers_per_backbone, seed=1,
    )
    cluster_r = np.asarray(sub0.cluster_r)
    browned = np.flatnonzero(cluster_r == cluster_r[0])
    C_r = np.asarray(sub0.C_r)
    sub = sub0.with_traces({
        f"reduce[r{k}]": CapacityTrace.step(
            float(C_r[k]), float(C_r[k]) * 0.05, 250.0)
        for k in browned
    })
    entries = scale_job_mix(
        sub, n_jobs=n_jobs, seed=3, arrival_spread_s=600.0,
        base_cfg=SimConfig(mode="fluid"),
    )
    n_stream = n_jobs // 10
    order = np.argsort([c.start_time for _, _, c in entries])
    jobs, cfgs = [], []
    for i in order[:n_jobs - n_stream]:
        pv, pl, c = entries[int(i)]
        jobs.append(GeoJob(pv).with_plan(pl, c.barriers))
        cfgs.append(c)
    arrivals = []
    for n, i in enumerate(order[n_jobs - n_stream:]):
        pv, pl, c = entries[int(i)]
        arrivals.append(Arrival(GeoJob(pv).with_plan(pl, c.barriers),
                                300.0 if n < n_stream // 2 else 480.0, cfg=c))
    return {
        "schedule": GeoSchedule(jobs).with_plans(),
        "arrivals": arrivals,
        "cfgs": cfgs,
        # pinned decision cost: measured-EMA charges would make the
        # swap/keep sequence (and the gated makespan) host-dependent
        "online": OnlineConfig(shared=True, hysteresis=1.0,
                               incremental=True, solver_cost_s=5.0),
        "n_nodes": sub.nS + sub.nM + sub.nR,
        "n_jobs": len(entries),
    }


def bench_scale_online() -> Dict:
    """Online control at the scale tier (ROADMAP §3): steered vectorized
    drains, fluid capacity traces, and a 1000-node online run.

    Three gated measurements:

    * **steered_100** — the 100-node/100-job mix at fine chunking driven
      through mid-run decision points (``run_until`` + ``snapshot`` +
      ``inject`` + ``swap_plan``) on both DES paths.  The steered
      vectorized drain is gated at >= 5x wall-clock over the scalar
      steered path with byte-identical results (full ``as_dict``
      equality, not just makespan).
    * **traced_fluid** — fluid mode vs per-chunk DES on a substrate with
      ``CapacityTrace`` drift on every tier (push/map/shuffle/reduce all
      step mid-run), across a barrier-triple subset: ``rel_err_pct`` is
      gated one-sided under the documented 2% fluid contract.
    * **online_1000** — ~10^3 nodes x 100 jobs in fluid mode with a
      backbone-wide reducer brownout at t=250s: ``reactive_shared``
      incremental co-replanning against the frozen plan.  The run must
      finish under the 60 s CI budget; the online margin and decision
      throughput may only fall so far.
    """
    import json as _json

    from repro.core.simulate import open_schedule
    from repro.core.topology import scale_job_mix, scale_tier_substrate

    # -- steered 100-node tier: scalar vs vectorized drains ----------------
    sub = scale_tier_substrate(seed=0)
    entries = scale_job_mix(
        sub, n_jobs=100, seed=3, base_cfg=SimConfig(chunk_mb=4.0)
    )
    CUTS = (600.0, 1800.0)

    def run_steered(vectorized: bool):
        jobs = [
            (p, plan, dataclasses.replace(c, vectorized=vectorized))
            for p, plan, c in entries
        ]
        eng = open_schedule(jobs, substrate=sub)
        t0 = time.perf_counter()
        for i, cut in enumerate(CUTS):
            eng.run_until(cut)
            eng.snapshot()
            if i == 0:
                # one decision point: admit a streaming arrival and
                # cross-swap two incumbent routings mid-flight
                p0, plan0, c0 = entries[0]
                eng.inject([(p0, plan0, dataclasses.replace(
                    c0, vectorized=vectorized, start_time=cut))])
                eng.swap_plan(0, entries[1][1])
                eng.swap_plan(1, entries[0][1])
        res = eng.run()
        return res, time.perf_counter() - t0

    res_s, wall_scalar = run_steered(vectorized=False)
    res_v, wall_vec = run_steered(vectorized=True)
    speedup = wall_scalar / wall_vec
    identical = (
        _json.dumps(res_s.as_dict(), sort_keys=True)
        == _json.dumps(res_v.as_dict(), sort_keys=True)
    )

    # -- traced fluid vs traced DES ----------------------------------------
    p = planetlab_platform(4, alpha=1.3, seed=5)
    plan = uniform_plan(p)
    tsub = Substrate.of(p).with_traces({
        "push[s0->m1]": CapacityTrace.step(
            float(p.B_sm[0, 1]), float(p.B_sm[0, 1]) * 0.25, 40.0),
        "map[m0]": CapacityTrace.step(
            float(p.C_m[0]), float(p.C_m[0]) * 0.5, 80.0),
        "shuffle[m1->r0]": CapacityTrace.step(
            float(p.B_mr[1, 0]), float(p.B_mr[1, 0]) * 0.3, 150.0),
        "reduce[r2]": CapacityTrace.step(
            float(p.C_r[2]), float(p.C_r[2]) * 0.4, 200.0),
    })
    view = tsub.view(p.D, p.alpha)
    rel_errs = {}
    for b in ("GGL", "GGG", "LLL", "PPP", "LGP"):
        des = simulate_schedule(
            [(view, plan, SimConfig(barriers=b, chunk_mb=4.0,
                                    vectorized=True, audit=True))],
            substrate=tsub)
        fl = simulate_schedule(
            [(view, plan, SimConfig(barriers=b, mode="fluid", audit=True))],
            substrate=tsub)
        rel_errs[b] = abs(fl.makespan - des.makespan) / des.makespan
    rel_err_pct = 100.0 * max(rel_errs.values())

    # -- 1000-node tier: online control under a backbone brownout ----------
    scenario = online_brownout()
    n_nodes_1k = scenario["n_nodes"]
    t0 = time.perf_counter()
    report = scenario["schedule"].run_online(
        policy="reactive_shared", arrivals=scenario["arrivals"],
        cfg=scenario["cfgs"], online=scenario["online"], **_OPT,
    )
    wall_1k = time.perf_counter() - t0
    decisions_per_s = len(report.decisions) / wall_1k if wall_1k else 0.0

    out = {
        "steered_100": {
            "n_nodes": sub.nS + sub.nM + sub.nR,
            "n_jobs": len(entries) + 1,
            "speedup_x": speedup,
            "makespan": res_v.makespan,
            "matches_scalar": bool(identical),
            "wall_scalar_s": wall_scalar,
            "wall_vec_s": wall_vec,
        },
        "traced_fluid": {
            "rel_err_pct": rel_err_pct,
            "worst_triple": max(rel_errs, key=rel_errs.get),
            "n_scenarios": len(rel_errs),
        },
        "online_1000": {
            "n_nodes": n_nodes_1k,
            "n_jobs": scenario["n_jobs"],
            "makespan": report.makespan_online,
            "static_makespan": report.makespan_static,
            "online_margin": report.improvement,
            "decisions": len(report.decisions),
            "swaps": len(report.swaps),
            "rejected": len(report.rejected),
            "decisions_per_s": decisions_per_s,
            "wall_s": wall_1k,
        },
    }
    emit("scale_online_steered100", wall_vec * 1e6,
         f"speedup={speedup:.1f}x;identical={identical};"
         f"makespan={res_v.makespan:.0f}s")
    emit("scale_online_traced_fluid", 0.0,
         f"max_rel_err={rel_err_pct:.3f}%;"
         f"worst={out['traced_fluid']['worst_triple']}")
    emit("scale_online_1000", wall_1k * 1e6,
         f"nodes={n_nodes_1k};margin={report.improvement:.0%};"
         f"decisions_per_s={decisions_per_s:.1f};"
         f"swaps={len(report.swaps)}")
    return out
