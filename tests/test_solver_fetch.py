"""The solver picks each request's best restart on the device: what
``_run_solver_many`` returns is bit for bit what the whole anneal's
restarts and the un-annealed warm starts give when the best is picked on
the host, and the inputs it puts are the float32 casts of the host's
float64 arrays, the start logits the host's warm starts and then the
restarts drawn on the device.

The solver runs at 4 restarts x 40 steps on 8-node PlanetLab platforms."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

import repro.core.optimize as opt
from repro.core import optimize_plan, optimize_plan_batch
from repro.core.makespan import BARRIERS_GGL, makespan
from repro.core.plan import ExecutionPlan, uniform_plan
from repro.core.platform import planetlab_platform

R, STEPS = 4, 40
STATICS = ("loss_kind", "barriers", "opt_x", "opt_y", "steps")


def _platforms(b):
    return [planetlab_platform(2, alpha=a, seed=s)
            for s, a in zip(range(b), (0.5, 1.0, 2.0))]


def _recorded(monkeypatch):
    """Wrap the solver so each call's arguments are kept."""
    calls, real = [], opt._solve_batch_many

    def keep(*args, **kw):
        calls.append((args, kw))
        return real(*args, **kw)

    monkeypatch.setattr(opt, "_solve_batch_many", keep)
    return calls


def _reference(args, kw):
    """The anneal over every restart, then the un-annealed warm starts,
    the best of them all picked on the host."""
    anneal = jax.jit(opt._anneal_restarts,
                     static_argnames=STATICS + ("tau0_frac",))
    arrs, lx, ly, xf, yf, sc, loss_kind, barriers, opt_x, opt_y, steps = args
    statics = dict(loss_kind=loss_kind, barriers=barriers, opt_x=opt_x,
                   opt_y=opt_y)
    annealed = anneal(arrs, lx, ly, xf, yf, sc, steps=steps, **kw, **statics)
    w = opt._WARM_STARTS
    warm = anneal(arrs, lx[:, :w], ly[:, :w], xf, yf, sc, steps=0, **statics)
    xs, ys, exact = (np.concatenate([np.asarray(a), np.asarray(b)], axis=1)
                     for a, b in zip(annealed, warm))
    out = []
    for b in range(exact.shape[0]):
        best = int(np.argmin(exact[b]))
        plan = ExecutionPlan.renormalized(xs[b, best], ys[b, best])
        out.append((plan.x, plan.y, float(exact[b, best])))
    return out


@pytest.mark.parametrize("b", [1, 3])
@pytest.mark.parametrize("loss_kind,opt_x,opt_y,fixed", [
    ("e2e", True, True, False),
    ("shuffle", False, True, True),
    # the locality warm start beats every annealed push plan here
    ("push", True, False, False),
])
def test_best_restart_on_the_device_is_the_hosts_pick(
        monkeypatch, b, loss_kind, opt_x, opt_y, fixed):
    platforms = _platforms(b)
    seeds = [11 + 5 * i for i in range(b)]
    fixed_x = ([np.asarray(opt.local_push_plan(p).x) for p in platforms]
               if fixed else None)
    calls = _recorded(monkeypatch)
    got = opt._run_solver_many(platforms, loss_kind, BARRIERS_GGL, opt_x,
                               opt_y, fixed_x, None, R, STEPS, seeds)
    (args, kw), = calls
    assert kw == {"tau0_frac": 0.3} and args[10] == STEPS
    # the inputs went over as the float32 casts of the host's arrays
    raw = [p.as_arrays() for p in platforms]
    for i, a in enumerate(args[0]):
        want = np.stack([np.asarray(r[i], np.float64) for r in raw])
        np.testing.assert_array_equal(np.asarray(a),
                                      np.asarray(jnp.asarray(want,
                                                             jnp.float32)))
    # the start logits are the host's warm starts, then the device's draw
    w = opt._WARM_STARTS
    warm = [opt._warm_logits(p) for p in platforms]
    wx = np.stack([x[:w] for x, _ in warm]).astype(np.float32)
    wy = np.stack([y[:w] for _, y in warm]).astype(np.float32)
    starts = opt._start_logits(wx, wy, opt._seed_words(seeds), R)
    for pos, init, host in ((1, 0, wx), (2, 1, wy)):
        np.testing.assert_array_equal(np.asarray(args[pos]),
                                      np.asarray(starts[init]))
        np.testing.assert_array_equal(np.asarray(args[pos])[:, :w], host)
    scales = [max(makespan(p, uniform_plan(p), barriers=BARRIERS_GGL), 1e-6)
              for p in platforms]
    np.testing.assert_array_equal(np.asarray(args[5]),
                                  np.asarray(scales, np.float32))
    want = _reference(args, kw)
    assert len(got) == len(want) == b
    for (x, y, obj), (wx, wy, wobj) in zip(got, want):
        np.testing.assert_array_equal(x, wx)
        np.testing.assert_array_equal(y, wy)
        assert obj == wobj


@pytest.mark.parametrize("b", [1, 3])
def test_myopic_multi_batch_matches_one_request_at_a_time(b):
    """Both rounds batched against one request at a time: one executable
    shape at B = 1, so bit for bit; at B = 3 the vmapped batch rounds in
    float32 apart from the B = 1 executable (``test_solver_service``'s
    tolerance)."""
    platforms = _platforms(b)
    seeds = [3 + 7 * i for i in range(b)]
    batch = optimize_plan_batch(platforms, "myopic_multi",
                                barriers=BARRIERS_GGL, n_restarts=R,
                                steps=STEPS, seeds=seeds)
    for p, s, got in zip(platforms, seeds, batch):
        solo = optimize_plan(p, "myopic_multi", barriers=BARRIERS_GGL,
                             n_restarts=R, steps=STEPS, seed=s)
        if b == 1:
            np.testing.assert_array_equal(got.plan.x, solo.plan.x)
            np.testing.assert_array_equal(got.plan.y, solo.plan.y)
            assert got.objective == solo.objective
            assert got.makespan == solo.makespan
        else:
            np.testing.assert_allclose(got.plan.x, solo.plan.x, atol=1e-6)
            np.testing.assert_allclose(got.plan.y, solo.plan.y, atol=1e-6)
            assert got.makespan == pytest.approx(solo.makespan, rel=1e-4)
