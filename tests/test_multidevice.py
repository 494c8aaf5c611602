"""Multi-device semantics tests.

These run in subprocesses with ``--xla_force_host_platform_device_count=8``
(the flag must be set before jax initializes, and the main test process
must keep seeing 1 device), covering:

* expert-parallel MoE via shard_map == single-device reference,
* the hierarchical (pod, data) all-reduce == plain tree-sum,
* a reduced-config dry-run cell on a tiny mesh (the same machinery the
  512-device production sweep uses),
* elastic checkpoint re-shard: save sharded on a 2x4 mesh, restore on 1.
"""
import os
import subprocess
import sys
import textwrap

import pytest

_ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def run_sub(body: str, n_devices: int = 8) -> str:
    code = (
        "import os\n"
        f"os.environ['XLA_FLAGS'] = '--xla_force_host_platform_device_count={n_devices}'\n"
        + textwrap.dedent(body)
    )
    env = dict(os.environ)
    env["PYTHONPATH"] = os.path.join(_ROOT, "src")
    env["JAX_PLATFORMS"] = "cpu"  # a child never takes the chip
    env.pop("XLA_FLAGS", None)
    r = subprocess.run(
        [sys.executable, "-c", code], capture_output=True, text=True,
        env=env, timeout=560,
    )
    assert r.returncode == 0, f"STDOUT:\n{r.stdout}\nSTDERR:\n{r.stderr}"
    return r.stdout


def test_moe_shard_map_matches_single_device():
    out = run_sub("""
        import jax, jax.numpy as jnp, numpy as np, dataclasses
        from repro.configs import ARCHS
        from repro.models import layers as L
        from repro.models import model as M
        from jax.sharding import NamedSharding, PartitionSpec as P

        cfg = dataclasses.replace(
            ARCHS["granite-moe-3b-a800m"].reduced(), capacity_factor=8.0)
        key = jax.random.PRNGKey(0)
        p = L.init_moe(cfg, key, tp=4)
        x = jax.random.normal(jax.random.PRNGKey(1), (4, 16, cfg.d_model))

        y_ref, aux_ref = L.moe_fwd(cfg, p, x, mesh=None)

        from repro.launch.mesh import make_mesh
        mesh = make_mesh((2, 4), ("data", "model"))
        xs = jax.device_put(x, NamedSharding(mesh, P("data", None, None)))
        y_ep, aux_ep = jax.jit(
            lambda pp, xx: L.moe_fwd(cfg, pp, xx, mesh=mesh))(p, xs)
        np.testing.assert_allclose(np.asarray(y_ep), np.asarray(y_ref),
                                   atol=2e-4, rtol=2e-4)
        np.testing.assert_allclose(float(aux_ep), float(aux_ref), rtol=1e-4)
        print("MOE_OK")
    """)
    assert "MOE_OK" in out


def test_hierarchical_allreduce_matches_psum():
    out = run_sub("""
        import jax, jax.numpy as jnp, numpy as np
        from repro.train.collective_schedule import hierarchical_allreduce
        from repro.launch.mesh import make_mesh
        mesh = make_mesh((2, 4), ("pod", "data"))
        tree = {
            "a": jnp.arange(1000, dtype=jnp.float32).reshape(10, 100),
            "b": jnp.ones((7,), jnp.float32),
        }
        got = jax.jit(lambda t: hierarchical_allreduce(t, mesh, mean=False))(tree)
        # every device holds the same (replicated) tree: sum over 8 devices
        np.testing.assert_allclose(np.asarray(got["a"]),
                                   8.0 * np.asarray(tree["a"]), rtol=1e-6)
        np.testing.assert_allclose(np.asarray(got["b"]), 8.0, rtol=1e-6)
        print("HIER_OK")
    """)
    assert "HIER_OK" in out


@pytest.mark.parametrize("arch,shape", [
    ("qwen3-1.7b", "train_4k"),
    ("granite-moe-3b-a800m", "train_4k"),
    ("falcon-mamba-7b", "decode_32k"),
])
def test_dryrun_cell_reduced_mesh(arch, shape):
    """The dry-run machinery (shardings, lowering, collective parsing) on a
    2x4 mesh with reduced configs — the exact code path of the production
    512-device sweep."""
    out = run_sub(f"""
        import jax, json
        from repro.launch.dryrun import run_cell
        from repro.launch.mesh import make_mesh
        mesh = make_mesh((2, 4), ("data", "model"))
        rep = run_cell({arch!r}, {shape!r}, multi_pod=False, mesh=mesh,
                       reduced=True)
        assert rep["hlo_flops_per_device"] > 0
        assert rep["per_device_bytes"] > 0
        print("CELL_OK", json.dumps(rep["collectives_per_device_bytes"]))
    """)
    assert "CELL_OK" in out


def test_elastic_checkpoint_reshard(tmp_path):
    """Save a train state sharded over a 2x4 mesh; restore it on a single
    device (different topology) and verify bitwise equality."""
    out = run_sub(f"""
        import jax, jax.numpy as jnp, numpy as np, functools
        from repro.configs import ARCHS, padded_for_tp
        from repro.models import model as M
        from repro.models.sharding import axis_rules, DEFAULT_RULES
        from repro.train.checkpoint import CheckpointManager
        from repro.train.train_step import init_state, state_shardings
        from jax.sharding import NamedSharding

        cfg = padded_for_tp(ARCHS["qwen3-1.7b"].reduced(), 4)
        from repro.launch.mesh import make_mesh
        mesh = make_mesh((2, 4), ("data", "model"))
        with axis_rules(mesh, DEFAULT_RULES):
            params = M.init(cfg, jax.random.PRNGKey(0), tp=4)
            state = init_state(cfg, params)
            sh = state_shardings(
                cfg, jax.tree.map(
                    lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype), state),
                mesh)
            state = jax.tree.map(jax.device_put, state, sh)
        mgr = CheckpointManager({str(tmp_path)!r}, keep=2)
        mgr.save(5, state)
        print("SAVED", mgr.steps())
    """)
    assert "SAVED [5]" in out
    # restore in THIS process (1 device — a different topology)
    import jax

    from repro.configs import ARCHS, padded_for_tp
    from repro.models import model as M
    from repro.train.checkpoint import CheckpointManager
    from repro.train.train_step import init_state

    cfg = padded_for_tp(ARCHS["qwen3-1.7b"].reduced(), 4)
    params = M.init(cfg, jax.random.PRNGKey(0), tp=4)
    like = jax.tree.map(
        lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype),
        init_state(cfg, params),
    )
    mgr = CheckpointManager(str(tmp_path), keep=2)
    restored, _, step = mgr.restore(None, like)
    assert step == 5
    import numpy as np

    for a, b in zip(
        jax.tree_util.tree_leaves(restored.params),
        jax.tree_util.tree_leaves(params),
    ):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
