"""Benchmark-regression gate: compare a ``benchmarks.run --json`` document
against the committed baseline and fail on drift.

CI runs the quick benchmark suite, then::

    PYTHONPATH=src python -m benchmarks.compare reports/bench-timings.json

which fails (exit 1) when

* any scenario present in the baseline is missing from the current run, or
* any baseline makespan metric (leaf keys ``makespan`` / ``simulated`` /
  ``modeled`` inside a scenario's results) deviates from the baseline by
  more than ``--tolerance`` (relative, default 0.25), or
* any planner-throughput metric (``plans_per_s``, ``p50_ms``, ``p99_ms``,
  the speedup ratios, ``compiles``) regresses in its *bad* direction past
  its per-metric tolerance — latency/compile counts may only rise so far,
  throughput/speedups may only fall so far; improvement is never a
  failure (see ``METRIC_DIRECTIONS`` / ``METRIC_TOLERANCES``).

Most wall-clock numbers are deliberately *not* gated — they vary with
the host.  The exceptions carry wide one-sided gates: the scale-tier
``wall_s`` budgets (they enforce the < 60 s CI ceilings with 2x
headroom) and throughput/speedup ratios whose acceptance floors are part
of the scale-wall contract.  The makespan metrics are modeled/simulated
seconds produced by the deterministic cost model and discrete-event
executor with fixed seeds, so on a pinned toolchain they reproduce
closely; the planner metrics ARE wall clock, which is why their gates are
wide and one-sided.  The baseline records the jax/numpy versions and git
SHA it was seeded from (see ``benchmarks.run._provenance``) so a
toolchain-driven mismatch is distinguishable from a code regression.

Refreshing after an intentional change::

    PYTHONPATH=src python -m benchmarks.run --quick \
        --json reports/bench-timings.json
    PYTHONPATH=src python -m benchmarks.compare reports/bench-timings.json \
        --update-baseline

then commit ``benchmarks/baseline.json`` with the change that moved the
numbers.
"""
from __future__ import annotations

import argparse
import json
import math
import os
import sys
from typing import Dict, Optional

#: leaf keys inside a scenario's results that are gated.  Makespan metrics
#: (seconds) are emitted by the deterministic model/executor, not wall
#: clock; the ``bench_planner`` latency/throughput leaves ARE wall clock,
#: which is why they carry direction-aware per-metric tolerances below.
METRIC_KEYS = frozenset({
    "makespan", "simulated", "modeled",
    "plans_per_s", "p50_ms", "p99_ms",
    "warm_vs_cold_speedup", "incremental_speedup", "compiles",
    "events_per_s", "speedup_x", "rel_err_pct",
    "failover_margin",
    "online_margin", "decisions_per_s", "wall_s",
})

#: per-scenario tolerance overrides (relative; scenarios absent here use
#: ``--tolerance``).  Annealed-solver scenarios whose discrete chunk
#: routing amplifies small plan differences get a wider gate; tighten (or
#: extend via ``--scenario-tolerance NAME=VAL``) as they prove stable.
SCENARIO_TOLERANCES = {
    "pipeline_chain": 0.35,
}

#: which way a metric is allowed to drift freely: ``lower`` metrics only
#: fail when the current value comes in ABOVE baseline (latency, compile
#: counts), ``higher`` metrics only when it comes in BELOW (throughput,
#: speedups).  Metrics absent here are gated both ways (makespans).
METRIC_DIRECTIONS = {
    "p50_ms": "lower",
    "p99_ms": "lower",
    "compiles": "lower",
    "plans_per_s": "higher",
    "warm_vs_cold_speedup": "higher",
    "incremental_speedup": "higher",
    # bench_scale: executor throughput and the vectorized-DES speedup may
    # only fall so far; the fluid-vs-DES rel-error may only grow so far
    "events_per_s": "higher",
    "speedup_x": "higher",
    "rel_err_pct": "lower",
    # schedule_failover: the recovery win over the frozen plan may only
    # shrink so far — the acceptance floor is >= 20% margin
    "failover_margin": "higher",
    # bench_scale_online: the online win over the frozen plan and the
    # decision throughput may only fall so far; wall-clock may only rise
    # so far (the 1000-node run carries a < 60 s CI budget)
    "online_margin": "higher",
    "decisions_per_s": "higher",
    "wall_s": "lower",
}

#: per-metric (leaf key) tolerance overrides — these beat the scenario
#: tolerance.  Wall-clock planner metrics on shared CI runners are far
#: noisier than deterministic makespans, so their gates are wide; the
#: acceptance floors (>=5x warm-vs-cold, >=3x incremental) still bind
#: because the baselines sit well above them.
METRIC_TOLERANCES = {
    "p50_ms": 3.0,
    "p99_ms": 3.0,
    "plans_per_s": 0.75,
    "warm_vs_cold_speedup": 0.6,
    "incremental_speedup": 0.6,
    "compiles": 0.5,
    # wall-clock-derived, so wide — with the baseline at ~13x the 0.6
    # floor still enforces the >= 5x vectorized-DES acceptance criterion
    "events_per_s": 0.75,
    "speedup_x": 0.6,
    # baseline rel-err is ~0.07%; 25x headroom keeps the gate under the
    # documented 2% fluid-mode contract while ignoring float jitter
    "rel_err_pct": 25.0,
    # deterministic simulated margin (~0.5 at baseline): 0.6 headroom
    # floors it at ~0.2 — the >= 20% failover acceptance criterion
    "failover_margin": 0.6,
    # bench_scale_online: the margin is deterministic (pinned
    # solver_cost_s) but the throughput and wall gates are host
    # wall-clock, so they are wide and one-sided
    "online_margin": 0.6,
    "decisions_per_s": 0.75,
    "wall_s": 1.0,
    # scenario-scoped override (``scenario:leaf`` beats the bare leaf):
    # the steered-drain speedup baseline sits just above the >= 5x
    # acceptance floor, so it gets a tight gate — the ratio is
    # host-stable because both sides run on the same machine
    "bench_scale_online:speedup_x": 0.08,
}

DEFAULT_BASELINE = os.path.join(os.path.dirname(os.path.abspath(__file__)),
                                "baseline.json")


def _walk(node, path, out: Dict[str, float]) -> None:
    if isinstance(node, dict):
        for key, value in node.items():
            if key in METRIC_KEYS and isinstance(value, (int, float)) \
                    and math.isfinite(value):
                out[f"{path}/{key}"] = float(value)
            else:
                _walk(value, f"{path}/{key}", out)
    elif isinstance(node, list):
        for idx, value in enumerate(node):
            _walk(value, f"{path}[{idx}]", out)


def extract_metrics(doc: dict) -> Dict[str, float]:
    """Flatten a ``--json`` timing document (or an already-trimmed
    baseline) to ``{scenario/.../metric: seconds}``."""
    if "metrics" in doc:  # a trimmed baseline written by --update-baseline
        return {k: float(v) for k, v in doc["metrics"].items()}
    metrics: Dict[str, float] = {}
    for name, scenario in doc.get("scenarios", {}).items():
        _walk(scenario.get("results", {}), name, metrics)
    return metrics


def scenario_names(metrics: Dict[str, float]) -> "set[str]":
    return {path.split("/", 1)[0] for path in metrics}


def compare(
    baseline: Dict[str, float],
    current: Dict[str, float],
    tolerance: float,
    scenario_tolerances: "Optional[Dict[str, float]]" = None,
    metric_tolerances: "Optional[Dict[str, float]]" = None,
) -> "list[str]":
    """Return the list of failures (empty = gate passes).

    ``scenario_tolerances`` overrides ``tolerance`` per scenario (the
    metric path's leading component), defaulting to
    :data:`SCENARIO_TOLERANCES`; ``metric_tolerances`` overrides both per
    leaf metric key (defaulting to :data:`METRIC_TOLERANCES`), with a
    ``scenario:leaf`` entry beating a bare ``leaf`` entry.  Deviation
    is direction-aware per :data:`METRIC_DIRECTIONS`: a latency metric
    that got *faster* or a throughput metric that got *faster* never
    fails, however far it moved."""
    overrides = SCENARIO_TOLERANCES if scenario_tolerances is None \
        else scenario_tolerances
    metric_overrides = METRIC_TOLERANCES if metric_tolerances is None \
        else metric_tolerances
    failures = []
    missing_scenarios = scenario_names(baseline) - scenario_names(current)
    for name in sorted(missing_scenarios):
        failures.append(f"scenario disappeared: {name}")
    for path, base in sorted(baseline.items()):
        scenario = path.split("/", 1)[0]
        leaf = path.rsplit("/", 1)[-1]
        if scenario in missing_scenarios:
            continue  # already reported wholesale
        if path not in current:
            failures.append(f"metric disappeared: {path}")
            continue
        cur = current[path]
        tol = metric_overrides.get(
            f"{scenario}:{leaf}",
            metric_overrides.get(leaf, overrides.get(scenario, tolerance)))
        # tiny epsilon floor only (the gated metrics are deterministic
        # model outputs, so sub-second baselines deserve the same relative
        # gate as hundred-second ones)
        denom = max(abs(base), 1e-6)
        direction = METRIC_DIRECTIONS.get(leaf, "both")
        if direction == "lower":      # regression = came in above baseline
            dev, bound = (cur - base) / denom, f">+{tol:.0%}"
        elif direction == "higher":   # regression = came in below baseline
            dev, bound = (base - cur) / denom, f">-{tol:.0%}"
        else:
            dev, bound = abs(cur - base) / denom, f"±{tol:.0%}"
        if dev > tol:
            failures.append(
                f"{path}: {cur:.2f} vs baseline {base:.2f} "
                f"({dev:+.0%} outside {bound})"
            )
    return failures


def main() -> int:
    ap = argparse.ArgumentParser(
        description="fail when benchmark makespans drift from the baseline"
    )
    ap.add_argument("current", help="bench-timings.json from benchmarks.run")
    ap.add_argument("--baseline", default=DEFAULT_BASELINE)
    ap.add_argument("--tolerance", type=float, default=0.25,
                    help="max relative deviation per metric (default 0.25)")
    ap.add_argument("--scenario-tolerance", action="append", default=[],
                    metavar="NAME=VAL",
                    help="per-scenario tolerance override (repeatable), "
                         "e.g. --scenario-tolerance pipeline_chain=0.4; "
                         "adds to the built-in SCENARIO_TOLERANCES")
    ap.add_argument("--metric-tolerance", action="append", default=[],
                    metavar="KEY=VAL",
                    help="per-metric (leaf key) tolerance override "
                         "(repeatable), e.g. --metric-tolerance p99_ms=5.0; "
                         "beats scenario tolerances, adds to the built-in "
                         "METRIC_TOLERANCES")
    ap.add_argument("--update-baseline", action="store_true",
                    help="rewrite the baseline from the current run "
                         "instead of comparing")
    args = ap.parse_args()
    def _parse_overrides(items, base, flag):
        out = dict(base)
        for item in items:
            name, _, value = item.partition("=")
            if not name or not value:
                ap.error(f"{flag} expects NAME=VAL, got {item!r}")
            try:
                out[name] = float(value)
            except ValueError:
                ap.error(f"bad tolerance value in {item!r}")
        return out

    scenario_tolerances = _parse_overrides(
        args.scenario_tolerance, SCENARIO_TOLERANCES, "--scenario-tolerance")
    metric_tolerances = _parse_overrides(
        args.metric_tolerance, METRIC_TOLERANCES, "--metric-tolerance")

    with open(args.current) as f:
        doc = json.load(f)
    current = extract_metrics(doc)
    if not current:
        print("[compare] FAIL: no gated metrics in the current run "
              f"({args.current})")
        return 1

    if args.update_baseline:
        trimmed = {"meta": doc.get("meta", {}), "metrics": current}
        with open(args.baseline, "w") as f:
            json.dump(trimmed, f, indent=1, sort_keys=True)
            f.write("\n")
        print(f"[compare] baseline refreshed: {len(current)} metrics "
              f"-> {args.baseline}")
        return 0

    if not os.path.exists(args.baseline):
        print(f"[compare] FAIL: no baseline at {args.baseline} — seed one "
              "with --update-baseline")
        return 1
    with open(args.baseline) as f:
        base_doc = json.load(f)
    baseline = extract_metrics(base_doc)

    failures = compare(baseline, current, args.tolerance,
                       scenario_tolerances, metric_tolerances)
    new = sorted(set(current) - set(baseline))
    if new:
        print(f"[compare] {len(new)} metric(s) not in baseline (not gated; "
              "run --update-baseline to adopt):")
        for path in new[:10]:
            print(f"  + {path} = {current[path]:.2f}s")
    if failures:
        print(f"[compare] FAIL: {len(failures)} regression(s) vs "
              f"{args.baseline} (tolerance ±{args.tolerance:.0%}):")
        for failure in failures:
            print(f"  ! {failure}")
        meta = base_doc.get("meta", {})
        if meta:
            print(f"[compare] baseline provenance: {json.dumps(meta)}")
        return 1
    print(f"[compare] OK: {len(baseline)} metric(s) within "
          f"±{args.tolerance:.0%} of baseline")
    return 0


if __name__ == "__main__":
    sys.exit(main())
