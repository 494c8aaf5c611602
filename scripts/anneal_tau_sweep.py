"""Sweep the fresh-plan anneal's starting temperature over platform sizes.

    PYTHONPATH=src:. python3 scripts/anneal_tau_sweep.py --sites 32 64 \
        --tau0 rule 0.3 0.03 --requests 4 --seed 7

For each number of sites ``N`` the benchmark's planner client draws
``--requests`` platforms from ``--seed`` (``bench.gen.planetlab``: one node
per site, Table 1's link ranges; planetlab8's own sites at ``N = 8``, else
Table 1's continents split 8:5:3 as in planetlab512) and plans them in one
``plan_many`` call at the configuration's solver budget, with the anneal's
``tau0_frac`` forced to each value of ``--tau0`` (``rule``: the program's
own choice).  Each plan is priced by the float64 reference against the best
of the simple plans (``bench.reference``), the benchmark's
``plan_vs_simple``.  One JSON line per point.
"""
from __future__ import annotations

import argparse
import json
import time
from pathlib import Path

import numpy as np

from bench import gen, reference

CONFIGS = Path(__file__).resolve().parent.parent / "bench" / "configs"


def sites(n: int, cfg8: dict) -> list:
    if n == len(cfg8["platform"]["sites"]):
        return cfg8["platform"]["sites"]
    us, eu = n * 8 // 16, n * 5 // 16
    return ([[f"us{i}", "US"] for i in range(us)]
            + [[f"eu{i}", "EU"] for i in range(eu)]
            + [[f"asia{i}", "Asia"] for i in range(n - us - eu)])


def requests(cfg: dict, n_sites: int, count: int, seed: int) -> list:
    p = dict(cfg["platform"], sites=sites(n_sites, cfg))
    out = []
    for i in range(count):
        g = gen.rng(seed, 1, i)
        out.append(gen.planetlab(p, float(g.choice(cfg["alpha"])), g))
    return out


def main(argv=None) -> None:
    ap = argparse.ArgumentParser(prog="anneal_tau_sweep")
    ap.add_argument("--sites", type=int, nargs="+", required=True)
    ap.add_argument("--tau0", nargs="+", required=True)
    ap.add_argument("--requests", type=int, default=4)
    ap.add_argument("--seed", type=int, default=7)
    args = ap.parse_args(argv)

    import repro.core.optimize as opt
    from repro.core.platform import Platform

    cfg = json.loads((CONFIGS / "planetlab8.json").read_text())
    s = cfg["solver"]
    rule = opt._anneal_tau0_frac
    for n in args.sites:
        reqs = requests(cfg, n, args.requests, args.seed)
        plats = [Platform(**{k: a[k] for k in (
            "D", "B_sm", "B_mr", "C_m", "C_r", "alpha", "cluster_s",
            "cluster_m", "cluster_r")}, name=f"r{i}")
            for i, a in enumerate(reqs)]
        best_simple = [min(reference.makespan(a, x, y, cfg["barriers"])
                           for x, y in reference.simple_plans(a))
                       for a in reqs]
        for t in args.tau0:
            opt._anneal_tau0_frac = rule if t == "rule" else (
                lambda *_, v=float(t): v)
            t0 = time.perf_counter()
            res = opt.optimize_plan_batch(
                plats, s["mode"], barriers=tuple(cfg["barriers"]),
                n_restarts=s["n_restarts"], steps=s["steps"],
                seeds=list(range(len(plats))))
            secs = time.perf_counter() - t0
            ratios = [reference.makespan(a, r.plan.x, r.plan.y,
                                         cfg["barriers"]) / b
                      for a, r, b in zip(reqs, res, best_simple)]
            print(json.dumps(dict(
                sites=n, tau0=t, tau0_frac=opt._anneal_tau0_frac(n, n, n),
                plan_vs_simple=float(np.mean(ratios)),
                ratios=[round(float(r), 4) for r in ratios],
                seconds=round(secs, 2))), flush=True)
        opt._anneal_tau0_frac = rule


if __name__ == "__main__":
    main()
