"""``chip_smoke.py`` on the CPU: the script refuses to run without a TPU, and
each of its phase functions passes its own checks at tiny sizes.

The phases are called directly (``main`` would stop at the device gate);
the suite's ``JAX_PLATFORMS=cpu`` keeps them on the host, where the Pallas
kernels run in interpret mode.  The four-chip phases run on a 1x1 mesh of
the one CPU device.
"""
import json

import jax
import pytest

import chip_smoke as cs
from repro.configs import ARCHS, get_config


@pytest.fixture(autouse=True)
def _on_cpu():
    assert jax.default_backend() == "cpu"
    with jax.default_device(jax.devices("cpu")[0]):
        yield


def _tiny_qwen3():
    return get_config("qwen3-1.7b").reduced()


def test_main_refuses_without_tpu(capsys):
    assert cs.main([]) == 2
    out, err = capsys.readouterr()
    assert "needs 1 TPU chip" in err
    for line in out.splitlines():
        with pytest.raises(ValueError):
            json.loads(line)


def test_planner_phase_tiny():
    out = cs.planner_phase(
        n_requests=2, n_restarts=4, steps=50,
        scenario=dict(n_regions=2, edges_per_region=4, mappers_per_region=3,
                      n_backbone=2, reducers_per_backbone=3, n_jobs=10),
    )
    assert len(out["plan_makespans"]) == 2
    assert out["device_rel"] == 0.0  # both solves ran on the CPU device
    assert out["online"].makespan_online < out["online"].makespan_static


def test_engine_phase_tiny():
    out = cs.engine_phase(n_docs=200, words_per_doc=50, vocab=300,
                          n_restarts=4, steps=50)
    paths = {path for _, _, path in out["reducers"]}
    assert "pallas-interpret" in paths and "pallas" not in paths
    assert out["compiles"] >= 1


def test_serve_phase_tiny():
    out = cs.serve_phase(_tiny_qwen3(), n_requests=3, slots=2, max_len=64,
                         max_new=4)
    assert out["logits_rel"] <= cs.LOGITS_RTOL
    assert sorted(out["outputs"]["warm"]) == [0, 1, 2]


def test_train_phases_tiny_on_one_device_mesh():
    cfg = _tiny_qwen3()
    out = cs.train_sharded_phase(cfg, mesh_shape=(1, 1), steps=3, batch=2,
                                 seq=16)
    assert len(out["metrics"]) == 3
    cmp = cs.train_compare_phase(cfg, mesh_shape=(1, 1), batch=2, seq=16)
    assert cmp["sharded"]["loss"] == pytest.approx(cmp["single"]["loss"],
                                                   rel=cs.TRAIN_LOSS_RTOL)
    assert cmp["diffs"]["worst_leaf"] <= cs.TRAIN_LEAF_RTOL


def test_train_compare_refuses_a_one_shard_gradient():
    """A data-parallel step that skipped its gradient all-reduce reports
    the full batch's loss with the gradient of one data shard; one that
    dropped a shard reports that shard's loss too.  Both must fail."""
    cfg = _tiny_qwen3()
    data = cs._lm_batch(cfg, 4, 16)
    half = {k: v[:2] for k, v in data.items()}
    runs = {}
    for name, batch in (("full", data), ("shard", half)):
        (metrics,), leaves = cs._train_step_metrics(cfg, None, batch, 1)
        runs[name] = dict(metrics, leaves=leaves)
    full, shard = runs["full"], runs["shard"]
    assert cs.compare_train_steps(full, full)["worst_leaf"] == 0.0
    no_all_reduce = dict(shard, loss=full["loss"])
    with pytest.raises(cs.CheckFailed, match="grad norm"):
        cs.compare_train_steps(no_all_reduce, full)
    with pytest.raises(cs.CheckFailed, match="gradient norm"):
        cs.compare_train_steps(dict(no_all_reduce, grad_norm=full["grad_norm"]),
                               full)
    with pytest.raises(cs.CheckFailed, match="loss"):
        cs.compare_train_steps(shard, full)


def test_moe_phase_tiny_on_one_device_mesh():
    cfg = ARCHS["granite-moe-3b-a800m"].reduced()
    out = cs.moe_phase(cfg, mesh_shape=(1, 1), batch=2, seq=8)
    assert out["max_err"] <= cs.MOE_TOL


def test_check_raises_on_a_failed_check():
    with pytest.raises(cs.CheckFailed, match="the thing"):
        cs.check(False, "the thing")
