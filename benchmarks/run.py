"""Benchmark runner — one benchmark per paper figure, all driven
through the :class:`repro.api.GeoJob` facade (plan →
price → execute on one shared cost model).  Emits
``name,us_per_call,derived`` CSV rows (also saved to
``reports/benchmarks.csv``) and a JSON dump of full results.

``--json PATH`` additionally writes a machine-readable timing document —
``{scenario: {wall_s, results}}`` with modeled/simulated makespans where the
scenario produces them — which CI uploads as an artifact to seed the bench
trajectory.

    PYTHONPATH=src python -m benchmarks.run [--quick] [--json PATH]
                                            [--planner-json PATH]

The JSON meta header records jax/numpy/git provenance plus the solver
cache counters (compiles, hits, misses) accumulated over the run.
"""
from __future__ import annotations

import argparse
import json
import os
import subprocess
import time

from repro.compile_cache import use_compile_cache

from . import paper_figures as F
from .common import flush_csv


def _json_default(o):
    import numpy as np

    if isinstance(o, (np.floating, np.integer)):
        return float(o)
    return str(o)


def _provenance() -> dict:
    """Library versions + git SHA, so uploaded timing artifacts are
    comparable across CI runs (and a baseline mismatch can be traced to a
    toolchain change rather than a code regression)."""
    import jax
    import numpy as np

    try:
        sha = subprocess.run(
            ["git", "rev-parse", "HEAD"], capture_output=True, text=True,
            cwd=os.path.dirname(os.path.abspath(__file__)), timeout=10,
        ).stdout.strip() or None
    except (OSError, subprocess.SubprocessError):
        sha = None
    return {"jax": jax.__version__, "numpy": np.__version__,
            "git_sha": sha}


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--quick", action="store_true",
                    help="small solver budgets (smoke-run the whole suite)")
    ap.add_argument("--json", default=None, metavar="PATH",
                    help="write machine-readable per-scenario timings "
                         "(modeled/simulated makespans + wall seconds)")
    ap.add_argument("--scenario", action="append", default=None,
                    metavar="NAME",
                    help="run only the named scenario (repeatable) — lets "
                         "CI and local dev re-run a single scenario")
    ap.add_argument("--planner-json", default=None, metavar="PATH",
                    help="also write just the bench_planner scenario (plus "
                         "meta) as its own JSON document — the planner-"
                         "throughput artifact CI uploads")
    ap.add_argument("--profile", action="store_true",
                    help="run the scenarios under cProfile and write the "
                         "top-20 cumulative functions next to --json (or "
                         "into --out) — how the executor hot path was "
                         "found")
    ap.add_argument("--out", default="reports")
    args = ap.parse_args()
    use_compile_cache()
    if args.quick:
        F._OPT = dict(n_restarts=6, steps=200)
    os.makedirs(args.out, exist_ok=True)

    scenarios = [
        ("fig4", F.fig4_validation),
        ("fig5", F.fig5_e2e_vs_myopic),
        ("fig6", F.fig6_single_vs_multi),
        ("fig7", F.fig7_barriers),
        ("fig8", F.fig8_environments),
        ("fig9", F.fig9_applications),
        ("fig10", F.fig10_dynamics),
        ("fig12", F.fig12_replication),
        ("schedule", F.schedule_contention),
        ("schedule_online", F.schedule_online),
        ("schedule_online_shared", F.schedule_online_shared),
        ("schedule_failover", F.schedule_failover),
        ("pipeline_chain", F.pipeline_chain),
        ("bench_planner", F.bench_planner),
        ("bench_scale", F.bench_scale),
        ("bench_scale_online", F.bench_scale_online),
    ]
    if args.scenario:
        known = {name for name, _ in scenarios}
        unknown = sorted(set(args.scenario) - known)
        if unknown:
            ap.error(f"unknown scenario(s) {unknown} — choose from "
                     f"{sorted(known)}")
        scenarios = [(n, fn) for n, fn in scenarios if n in args.scenario]

    profiler = None
    if args.profile:
        import cProfile

        profiler = cProfile.Profile()

    results, wall = {}, {}
    print("name,us_per_call,derived")
    for name, fn in scenarios:
        t0 = time.perf_counter()
        if profiler is not None:
            results[name] = profiler.runcall(fn)
        else:
            results[name] = fn()
        wall[name] = time.perf_counter() - t0

    if profiler is not None:
        import io
        import pstats

        buf = io.StringIO()
        pstats.Stats(profiler, stream=buf).sort_stats(
            "cumulative").print_stats(20)
        profile_path = (
            os.path.splitext(args.json)[0] + "-profile.txt"
            if args.json else os.path.join(args.out, "profile.txt")
        )
        profile_dir = os.path.dirname(profile_path)
        if profile_dir:
            os.makedirs(profile_dir, exist_ok=True)
        with open(profile_path, "w") as f:
            f.write(buf.getvalue())
        print(f"[profile] top-20 cumulative in {profile_path}")

    flush_csv(os.path.join(args.out, "benchmarks.csv"))

    with open(os.path.join(args.out, "benchmarks.json"), "w") as f:
        json.dump(results, f, indent=1, default=_json_default)

    if args.json or args.planner_json:
        from repro.core.optimize import solver_cache_stats

        # cumulative solver-cache counters over the whole run: compile-time
        # vs steady-state throughput is visible in the bench trajectory
        meta = {"quick": bool(args.quick),
                "opt": {k: int(v) for k, v in F._OPT.items()},
                "total_wall_s": sum(wall.values()),
                "solver_cache": solver_cache_stats(),
                **_provenance()}

    def _write_json(path, doc):
        json_dir = os.path.dirname(path)
        if json_dir:
            os.makedirs(json_dir, exist_ok=True)
        with open(path, "w") as f:
            json.dump(doc, f, indent=1, default=_json_default)

    if args.json:
        _write_json(args.json, {
            "meta": meta,
            "scenarios": {
                name: {"wall_s": wall[name], "results": results[name]}
                for name in results
            },
        })
        print(f"[json] machine-readable timings in {args.json}")

    if args.planner_json:
        if "bench_planner" not in results:
            ap.error("--planner-json requires the bench_planner scenario "
                     "to run (drop the --scenario filter or include it)")
        _write_json(args.planner_json, {
            "meta": meta,
            "scenarios": {
                "bench_planner": {"wall_s": wall["bench_planner"],
                                  "results": results["bench_planner"]},
            },
        })
        print(f"[json] planner throughput in {args.planner_json}")

    print(f"\n[done] results in {args.out}/benchmarks.{{csv,json}}")


if __name__ == "__main__":
    main()
