"""The planner service under fresh plan requests.

Requests go to ``repro.core.optimize.SolverService.plan_many``.  A request
is one platform of the configuration drawn from the run's seed.  The
traffic file chooses the loop:

* ``"loop": "open"``: ``rate_per_s`` arrivals over the window in blocks of
  ``block_s`` seconds drawn from ``data_seed``, their order from the run's
  seed (:func:`bench.gen.arrivals`).  One batcher serves the queue: it
  takes the queued requests, up to ``max_batch``, cut to a power of two,
  so the window only uses the shapes set-up warmed.  Every request due in
  the window is served, the queue drained after it, and timed from when
  it was due.
* ``"loop": "closed"``: rounds of ``outstanding`` requests from a pool of
  ``pool`` requests made in set-up, the next round sent when the last
  one's plans are back, until the window has passed.

The solver budget (mode, barriers, restarts, steps) is the
configuration's, passed explicitly, so a change of the service's defaults
does not change the work.
"""
from __future__ import annotations

import math
import time
from collections import deque

import jax
import numpy as np

from bench import gen, reference
from bench.record import Record

# seed streams of gen.rng, one per kind of input
REQUEST, ARRIVALS = 1, 3


def _bucket(n: int, cap: int) -> int:
    """The largest power of two no larger than ``min(n, cap)``."""
    return 1 << (min(n, cap).bit_length() - 1)


class Client:
    def __init__(self, config: dict, traffic: dict, seed: int, log):
        from repro.core.optimize import SolverService

        self.cfg, self.mix, self.seed, self.log = config, traffic, seed, log
        s = config["solver"]
        self.barriers = config["barriers"]
        self.svc = SolverService(mode=s["mode"], barriers=tuple(self.barriers),
                                 n_restarts=s["n_restarts"], steps=s["steps"])
        self.max_batch = int(traffic.get("max_batch", 1))

    # -- inputs ------------------------------------------------------------
    def _request(self, i: int) -> dict:
        from repro.core.platform import Platform

        g = gen.rng(self.seed, REQUEST, i)
        a = gen.planetlab(self.cfg["platform"],
                          float(g.choice(self.cfg["alpha"])), g)
        return dict(arrays=a, solver_seed=i, platform=Platform(
            D=a["D"], B_sm=a["B_sm"], B_mr=a["B_mr"], C_m=a["C_m"],
            C_r=a["C_r"], alpha=a["alpha"], cluster_s=a["cluster_s"],
            cluster_m=a["cluster_m"], cluster_r=a["cluster_r"],
            name=f"r{i}"))

    def _call(self, reqs: list):
        with jax.profiler.TraceAnnotation("bench.plan_many"):
            return self.svc.plan_many([r["platform"] for r in reqs],
                                      seeds=[r["solver_seed"] for r in reqs])

    # -- set-up ------------------------------------------------------------
    def setup(self, seconds: float) -> None:
        """Make every request from the seed and warm every batch shape
        the window will use."""
        mix = self.mix
        t = time.perf_counter()
        if mix["loop"] == "open":
            block = float(mix["block_s"])
            self.due = gen.arrivals(
                float(mix["rate_per_s"]), block, math.ceil(seconds / block),
                mix["data_seed"], gen.rng(self.seed, ARRIVALS))
            self.due = self.due[self.due < seconds]
            self.requests = [self._request(i) for i in range(len(self.due))]
            sizes = [1 << b for b in range(self.max_batch.bit_length())]
        else:
            self.requests = [self._request(i) for i in range(int(mix["pool"]))]
            sizes = [int(mix["outstanding"])]
        self.log(f"set-up: {len(self.requests)} requests "
                 f"{time.perf_counter() - t:.3f}s")
        for rnd in range(2):  # the first round compiles or loads, the second
            for b in sizes:  # must find it all warm
                t = time.perf_counter()
                self._call(self.requests[:b])
                self.log(f"set-up: warm plan x{b} round {rnd} "
                         f"{time.perf_counter() - t:.3f}s")

    # -- window ------------------------------------------------------------
    def window(self, seconds: float) -> Record:
        rec = Record()
        if self.mix["loop"] == "open":
            self._open(seconds, rec)
        else:
            self._closed(seconds, rec)
        return rec

    def _serve(self, rec: Record, idxs: list, ops: list, t0: float) -> None:
        """One call for requests ``idxs``, recorded in ``rec.ops[ops]``."""
        start = time.perf_counter() - t0
        try:
            answers = self._call([self.requests[i] for i in idxs])
        except Exception as e:  # a raised call fails its requests
            self.log(f"plan x{len(idxs)} raised {e!r}")
            answers = [None] * len(idxs)
        end = time.perf_counter() - t0
        rec.dispatches.append(dict(kind="plan", n=len(idxs), start=start,
                                   end=end))
        for o, ans in zip(ops, answers):
            rec.ops[o].update(start=start, done=end, answer=ans)

    def _open(self, seconds: float, rec: Record) -> None:
        due = self.due[self.due < seconds]
        rec.ops = [dict(kind="plan", due=float(d), work=1, req=i)
                   for i, d in enumerate(due)]
        queue: deque = deque()
        nxt, half_seen, end_seen = 0, False, False
        t0 = time.perf_counter()
        while True:
            now = time.perf_counter() - t0
            while nxt < len(due) and due[nxt] <= now:
                queue.append(nxt)
                nxt += 1
            if not half_seen and now >= seconds / 2:
                half_seen, rec.backlog_at_half = True, len(queue)
            if not end_seen and now >= seconds:
                end_seen, rec.backlog_at_end = True, len(queue)
            if not queue:
                if nxt >= len(due):
                    break
                with jax.profiler.TraceAnnotation("bench.wait_arrival"):
                    time.sleep(max(due[nxt] - now, 0.0))
                continue
            batch = [queue.popleft()
                     for _ in range(_bucket(len(queue), self.max_batch))]
            self._serve(rec, batch, batch, t0)
        rec.window_s = max(time.perf_counter() - t0, seconds)

    def _closed(self, seconds: float, rec: Record) -> None:
        n, k = len(self.requests), int(self.mix["outstanding"])
        t0 = time.perf_counter()
        sent = 0
        while time.perf_counter() - t0 < seconds:
            idxs = [(sent + j) % n for j in range(k)]
            ops = list(range(len(rec.ops), len(rec.ops) + k))
            now = time.perf_counter() - t0
            rec.ops += [dict(kind="plan", sent=now, work=1, req=i)
                        for i in idxs]
            self._serve(rec, idxs, ops, t0)
            sent += k
        rec.window_s = time.perf_counter() - t0

    def close(self) -> None:
        self.svc = None

    # -- comparison with the reference ---------------------------------------
    def check(self, rec: Record, limits: dict, control: bool = False) -> dict:
        """Hold every answer of the window against the float64 reference.

        ``price_gap``: the relative gap between the makespan the service
        reports for its plan (float64 pricing on the host) and the
        reference's price of that plan.  ``objective_gap``: the same for
        the objective the device's anneal reports (float32).
        ``invalid_plans``: plans off the simplex (Equations 1-3).
        ``plan_vs_simple``: the mean, over the window's plans, of a plan's
        reference makespan over that of the best of the reference's
        simple plans for its platform (:func:`bench.reference.simple_plans`);
        an anneal that does not improve on its starting points reads about
        1.  With ``control`` the reference itself, one precision step down
        (float32 pricing, bfloat16 objective), stands in for the service's
        numbers.
        """
        b = self.barriers
        price = objective = 0.0
        invalid = failed = 0
        ratio = []
        for op in rec.ops:
            ans = op.get("answer")
            if ans is None:
                failed += 1
                continue
            a = self.requests[op["req"]]["arrays"]
            x, y = ans.plan.x, ans.plan.y
            bad = not _valid(x, y, a)
            invalid += bad
            if bad:
                failed += 1
                continue
            ref = reference.makespan(a, x, y, b)
            got_price, got_obj = ans.makespan, ans.objective
            if control:
                got_price = reference.makespan(a, x, y, b, np.float32)
                got_obj = reference.makespan(a, x, y, b,
                                             reference.DTYPES["bfloat16"])
            gap_p = abs(got_price - ref) / ref
            gap_o = abs(got_obj - ref) / ref
            price, objective = max(price, gap_p), max(objective, gap_o)
            ratio.append(ref / min(reference.makespan(a, sx, sy, b)
                                   for sx, sy in reference.simple_plans(a)))
            failed += bool(gap_p > limits["price_gap"]
                           or gap_o > limits["objective_gap"])
        quality = float(np.mean(ratio)) if ratio else math.inf
        if ratio:
            self.log(f"plan quality: makespan / best simple plan's, mean "
                     f"{quality:.4f}, worst {np.max(ratio):.4f} over "
                     f"{len(ratio)} plans")
        return dict(failed=failed, numbers=dict(
            price_gap=price, objective_gap=objective,
            invalid_plans=invalid, plan_vs_simple=quality))


def _valid(x, y, a) -> bool:
    """Equations 1-3: push rows and the shuffle row on the simplex."""
    x, y = np.asarray(x), np.asarray(y)
    return (x.shape == a["B_sm"].shape and y.shape == (a["B_mr"].shape[1],)
            and bool(np.all(np.isfinite(x)) and np.all(np.isfinite(y)))
            and bool(np.all(x >= 0) and np.all(y >= 0))
            and bool(np.all(np.abs(x.sum(axis=1) - 1) <= 1e-9))
            and abs(float(y.sum()) - 1) <= 1e-9)
