"""The fresh-plan anneal's start logits (``_start_logits``): each
request's warm starts, made on the host, bit for bit, then its random
restarts, drawn on the device from its seed alone; and what a plan puts
on the device (``plan.h2d_bytes``), which no longer holds the restarts."""
import jax
import numpy as np
import pytest

import repro.core.optimize as opt
from repro import tracing
from repro.core.makespan import BARRIERS_GGL
from repro.core.platform import planetlab_platform

W = opt._WARM_STARTS
R = 12
PLATFORMS = [planetlab_platform(2, alpha=a, seed=s)
             for s, a in enumerate((0.5, 1.0, 2.0))]
SEEDS = [11, 2**33 + 11, 7]


def _warm(platforms, r=R):
    w = min(W, r)
    warm = [opt._warm_logits(p) for p in platforms]
    return (np.stack([x[:w] for x, _ in warm]).astype(np.float32),
            np.stack([y[:w] for _, y in warm]).astype(np.float32))


def _starts(platforms, seeds, r=R):
    lx, ly = opt._start_logits(*_warm(platforms, r), opt._seed_words(seeds),
                               r)
    return np.asarray(lx), np.asarray(ly)


def test_shapes_and_dtype():
    lx, ly = _starts(PLATFORMS, SEEDS)
    p = PLATFORMS[0]
    assert lx.shape == (3, R, p.nS, p.nM) and ly.shape == (3, R, p.nR)
    assert lx.dtype == ly.dtype == np.float32


def test_the_warm_rows_are_the_host_warm_starts():
    wx, wy = _warm(PLATFORMS)
    lx, ly = _starts(PLATFORMS, SEEDS)
    np.testing.assert_array_equal(lx[:, :W], wx)
    np.testing.assert_array_equal(ly[:, :W], wy)
    # and the host's fresh-plan starts of old, warm rows and all, still
    # begin with them: the re-plan paths draw from _initial_logits
    for b, (p, s) in enumerate(zip(PLATFORMS, SEEDS)):
        hx, hy = opt._initial_logits(p, R, s)
        np.testing.assert_array_equal(hx[:W], wx[b])
        np.testing.assert_array_equal(hy[:W], wy[b])


@pytest.mark.parametrize("pos", [0, 1, 2])
def test_a_request_draws_the_same_alone_and_anywhere_in_a_batch(pos):
    alone = _starts(PLATFORMS[:1], SEEDS[:1])
    others = list(range(1, 3))
    order = others[:pos] + [0] + others[pos:]
    batch = _starts([PLATFORMS[i] for i in order], [SEEDS[i] for i in order])
    for a, b in zip(alone, batch):
        np.testing.assert_array_equal(a[0], b[pos])


def test_restart_r_draws_the_same_whatever_the_number_of_restarts():
    short, long = _starts(PLATFORMS, SEEDS, 8), _starts(PLATFORMS, SEEDS, R)
    for a, b in zip(short, long):
        np.testing.assert_array_equal(a, b[:, :8])


@pytest.mark.parametrize("seeds", [(5, 6), (5, 2**32 + 5), (2**40, 2**40 + 1),
                                   (0, 2**64 - 1)])
def test_two_seeds_draw_different_restarts(seeds):
    p = PLATFORMS[0]
    lx, ly = _starts([p, p], list(seeds))
    assert np.isfinite(lx).all() and np.isfinite(ly).all()
    assert not np.any(lx[0, W:] == lx[1, W:])
    assert not np.any(ly[0, W:] == ly[1, W:])


@pytest.mark.parametrize("seed", [-1, 2**64])
def test_a_seed_outside_64_bits_is_refused(seed):
    with pytest.raises(ValueError, match="2\\*\\*64"):
        opt._seed_words([seed])


@pytest.mark.parametrize("r", [1, W])
def test_no_more_restarts_than_warm_starts_draws_nothing(monkeypatch, r):
    calls = []

    def no_draw(*a, **k):
        raise AssertionError("drew restarts for R <= W")

    real = opt._solve_batch_many
    monkeypatch.setattr(opt, "_start_logits", no_draw)
    monkeypatch.setattr(opt, "_solve_batch_many", lambda *a, **k: (
        calls.append(a), real(*a, **k))[1])
    opt._run_solver_many(PLATFORMS, "e2e", BARRIERS_GGL, True, True, None,
                         None, r, 5, SEEDS)
    (args,) = calls
    wx, wy = _warm(PLATFORMS, r)
    np.testing.assert_array_equal(np.asarray(args[1]), wx)
    np.testing.assert_array_equal(np.asarray(args[2]), wy)


def test_each_restart_draws_its_own_spread_within_the_law():
    """σ ~ U(0.3, 3.0) per restart, the same σ for its ``x`` and ``y``:
    over 200 restarts of 64 x 64 logits each row's sample std lies in
    [0.3, 3.0] within 10 %, and the rows cover the range."""
    n = 200
    wx = np.zeros((1, W, 64, 64), np.float32)
    wy = np.zeros((1, W, 4096), np.float32)
    lx, ly = opt._start_logits(wx, wy, opt._seed_words([2**35 + 3]), W + n)
    sx = np.asarray(lx)[0, W:].std(axis=(1, 2))
    sy = np.asarray(ly)[0, W:].std(axis=1)
    for s in (sx, sy):
        assert (s > 0.3 * 0.9).all() and (s < 3.0 * 1.1).all()
        assert s.min() < 0.5 and s.max() > 2.8
    np.testing.assert_allclose(sx, sy, rtol=0.1)
    assert abs(float(np.asarray(lx)[0, W:].mean())) < 0.05


@pytest.mark.parametrize("mode", ["e2e_multi", "e2e_shuffle", "myopic_push"])
def test_a_plan_counts_the_bytes_it_puts_and_puts_no_restarts(monkeypatch,
                                                               mode):
    put, real = [], jax.device_put

    def keep(x, *a, **k):
        put.extend(jax.tree.leaves(x))
        return real(x, *a, **k)

    monkeypatch.setattr(jax, "device_put", keep)
    tracing.clear()
    with tracing.recording():
        opt.optimize_plan_batch(PLATFORMS, mode, barriers=BARRIERS_GGL,
                                n_restarts=R, steps=5, seeds=SEEDS)
    try:
        (root,) = [s for s in tracing.spans()
                   if s.name == "geoplan.plan_many"]
    finally:
        tracing.clear()
    assert root.attrs["plan.h2d_bytes"] == sum(a.nbytes for a in put) > 0
    assert root.attrs["plan.h2d"] == len(put)
    p, b = PLATFORMS[0], len(PLATFORMS)
    restarts = {(b, R, p.nS, p.nM), (b, R, p.nR)}
    assert not any(np.shape(a) in restarts for a in put)
    # warm starts and seeds, six platform arrays and the scales, and the
    # fixed plan that only a shuffle-only or a push-only solve reads
    assert len(put) == 3 + 7 + (mode != "e2e_multi")
