"""Compile-only checks for a described TPU v5e: the main path's kernels at
real sizes go through the TPU compiler without a chip attached.

Nothing here runs on a device.  Each test lowers and compiles for
``topologies.get_topology_desc("v5e:2x2")`` and asserts what the compiler
emitted.  The topology is described inside a module fixture (never at
import), because only one process at a time may load the TPU library.
"""
import os

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies

    from repro.compile_cache import compile_cache_off

    # a compile for a described chip is written to the persistent cache but
    # cannot be read back without one: keep the cache off here
    with pytest.MonkeyPatch.context() as mp, compile_cache_off():
        mp.setenv("TPU_LOG_DIR", os.environ.get("TPU_LOG_DIR", "disabled"))
        try:
            desc = topologies.get_topology_desc(
                platform="tpu", topology_name="v5e:2x2"
            )
        except Exception as e:  # noqa: BLE001 — any failure means "cannot describe"
            pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
        yield desc


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


def _spec(shape, dtype, sharding):
    return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)


@pytest.mark.parametrize("num_segments", [1250, 10_000])
def test_segment_sum_compiles(one_chip, num_segments):
    """The engine reduce's kernel: one value column (word counts), an
    unaligned segment count, N=65,536 rows."""
    from repro.kernels.segment_reduce import segment_sum

    fn = jax.jit(lambda v, i: segment_sum(v, i, num_segments, interpret=False))
    compiled = fn.lower(
        _spec((65_536, 1), jnp.float32, one_chip),
        _spec((65_536,), jnp.int32, one_chip),
    ).compile()
    assert "tpu_custom_call" in compiled.as_text()


def test_flash_attention_compiles_at_qwen3_prefill(one_chip):
    """qwen3-1.7b prefill geometry: 16 query heads over 8 KV heads,
    head_dim 128, T=2,048, bf16."""
    from repro.kernels.flash_attention import flash_attention

    q = _spec((1, 16, 2048, 128), jnp.bfloat16, one_chip)
    kv = _spec((1, 8, 2048, 128), jnp.bfloat16, one_chip)
    fn = jax.jit(lambda q, k, v: flash_attention(q, k, v, interpret=False))
    compiled = fn.lower(q, kv, kv).compile()
    assert "tpu_custom_call" in compiled.as_text()


def test_solver_compiles_at_thousand_node_tier(one_chip):
    """The annealed solve at the scale tier's shapes: 480 sources x 336
    mappers in x, 180 reducers in y, 16 restarts of one request."""
    from repro.core.optimize import _solve_batch_many

    nS, nM, nR, R = 480, 336, 180, 16
    f32 = jnp.float32
    arrs = tuple(
        _spec((1,) + s, f32, one_chip)
        for s in [(nS,), (nS, nM), (nM, nR), (nM,), (nR,), ()]
    )
    compiled = _solve_batch_many._jitted.lower(
        arrs,
        _spec((1, R, nS, nM), f32, one_chip),
        _spec((1, R, nR), f32, one_chip),
        _spec((1, nS, nM), f32, one_chip),
        _spec((1, nR), f32, one_chip),
        _spec((1,), f32, one_chip),
        loss_kind="e2e", barriers=("G", "G", "L"), opt_x=True, opt_y=True,
        steps=400,
    ).compile()
    mem = compiled.memory_analysis()
    assert mem.temp_size_in_bytes < 16 * 2**30


def test_solver_compiles_at_planetlab512(one_chip):
    """The ``planetlab512`` cell's call: 8 requests x 24 restarts over 512
    sites, 500 steps, the sized start; the anneal's state fits one chip
    with room to spare."""
    from repro.core.optimize import _anneal_tau0_frac, _solve_batch_many

    B, R, n = 8, 24, 512
    f32 = jnp.float32
    arrs = tuple(
        _spec((B,) + s, f32, one_chip)
        for s in [(n,), (n, n), (n, n), (n,), (n,), ()]
    )
    compiled = _solve_batch_many._jitted.lower(
        arrs,
        _spec((B, R, n, n), f32, one_chip),
        _spec((B, R, n), f32, one_chip),
        _spec((B, n, n), f32, one_chip),
        _spec((B, n), f32, one_chip),
        _spec((B,), f32, one_chip),
        loss_kind="e2e", barriers=("G", "G", "G"), opt_x=True, opt_y=True,
        steps=500, tau0_frac=_anneal_tau0_frac(n, n, n),
    ).compile()
    mem = compiled.memory_analysis()
    assert mem.temp_size_in_bytes + mem.argument_size_in_bytes < 4 * 2**30


def test_start_logits_compiles_at_planetlab512(one_chip):
    """The draw of the ``planetlab512`` call's starts: 8 requests' 3 warm
    starts in, 24 restarts out, their 201 MB the program's output."""
    from repro.core.optimize import _start_logits

    B, W, R, n = 8, 3, 24, 512
    compiled = _start_logits._jitted.lower(
        _spec((B, W, n, n), jnp.float32, one_chip),
        _spec((B, W, n), jnp.float32, one_chip),
        _spec((B, 2), jnp.uint32, one_chip),
        n_restarts=R,
    ).compile()
    mem = compiled.memory_analysis()
    assert mem.output_size_in_bytes >= 4 * B * R * (n * n + n)
    assert mem.temp_size_in_bytes < 2**30
