"""Faults planted in the program underneath a run, each of which has to
turn ``correct`` false.  The tests plant them on the CPU
(``bench/tests/test_faults.py``); ``python3 -m bench.study --fault <name>``
reads one on the chip at a cell's own size.

Each fault takes ``set_attr(obj, name, value)``, ``setattr`` or pytest's
``monkeypatch.setattr``, and replaces one function of the program:

* ``frozen_anneal``: the solver's anneal takes no step, so every request
  gets the best of its starting points (a step that leaves its state
  unchanged);
* ``half_batch_solver`` / ``half_batch_reduce``: half of a dispatch's
  requests, or of a reducer's rows, left out, the rest standing in;
* ``altered_plan`` / ``altered_count``: an answer altered where it is
  produced (the plan permuted after the device chose it; one count off by
  one).
"""
from __future__ import annotations

__all__ = ["PLANNER", "WORDCOUNT"]

STEPS_ARG = 10  # position of ``steps`` in ``_solve_batch_many``'s arguments


def frozen_anneal(set_attr) -> None:
    import repro.core.optimize as opt

    real = opt._solve_batch_many

    def frozen(*args, **kw):
        args = list(args)
        args[STEPS_ARG] = 0
        return real(*args, **kw)

    set_attr(opt, "_solve_batch_many", frozen)


def half_batch_solver(set_attr) -> None:
    import jax.numpy as jnp

    import repro.core.optimize as opt

    real = opt._solve_batch_many

    def half(arrs, lx, ly, xf, yf, sc, *a, **k):
        h = max(lx.shape[0] // 2, 1)
        xs, ys, ex = real(tuple(v[:h] for v in arrs), lx[:h], ly[:h], xf[:h],
                          yf[:h], sc[:h], *a, **k)
        idx = jnp.arange(lx.shape[0]) % h
        return xs[idx], ys[idx], ex[idx]

    set_attr(opt, "_solve_batch_many", half)


def altered_plan(set_attr) -> None:
    import numpy as np

    from repro.core.plan import ExecutionPlan

    real = ExecutionPlan.renormalized.__func__

    def rolled(cls, x, y, meta=""):
        return real(cls, np.roll(np.asarray(x), 1, axis=1), y, meta)

    set_attr(ExecutionPlan, "renormalized", classmethod(rolled))


def half_batch_reduce(set_attr) -> None:
    import repro.kernels.ops as kops

    real = kops.sorted_segment_sum

    def half(values, segment_ids, num_segments, **k):
        n = values.shape[0] // 2
        return 2 * real(values[:n], segment_ids[:n], num_segments, **k)

    set_attr(kops, "sorted_segment_sum", half)


def altered_count(set_attr) -> None:
    import repro.kernels.ops as kops

    real = kops.sorted_segment_sum

    def off_by_one(values, segment_ids, num_segments, **k):
        return real(values, segment_ids, num_segments, **k).at[0].add(1)

    set_attr(kops, "sorted_segment_sum", off_by_one)


PLANNER = {f.__name__: f for f in (frozen_anneal, half_batch_solver,
                                   altered_plan)}
WORDCOUNT = {f.__name__: f for f in (half_batch_reduce, altered_count)}
