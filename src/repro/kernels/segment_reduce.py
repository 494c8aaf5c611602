"""Sorted-segment-sum Pallas TPU kernel — the MapReduce combiner primitive.

The plan-driven MapReduce engine reduces sorted (key, value) runs; the hot
loop is a segment sum.  A GPU implementation would use warp ballots /
shared-memory atomics; the TPU-native adaptation turns the scatter-add into
an **MXU one-hot matmul**: for each VMEM block of rows we build the one-hot
partition matrix ``P[n, s] = (ids[n] == s)`` with ``broadcasted_iota`` and
accumulate ``valuesᵀ @ P`` (a ``(D, S)`` block) into a VMEM-resident
output block across the sequential grid dimension.  No atomics, no
data-dependent control flow — just dense systolic work.

TARGET: TPU.  VALIDATED: ``interpret=True`` vs ref.segment_sum_ref;
compiled for a described v5e in ``tests/test_tpu_compile.py``.
"""
from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from . import interpret_mode

__all__ = ["segment_sum"]

#: Elements of the (block_n, num_segments) one-hot block.  With the fp32
#: contraction's working copies it must fit v5e's 16 MiB scoped VMEM, so
#: many segments shrink the row block instead.
_ONEHOT_ELEMS = 1 << 20


def _segsum_kernel(v_ref, id_ref, o_ref, *, block_n, num_segments):
    i = pl.program_id(0)

    @pl.when(i == 0)
    def _init():
        o_ref[...] = jnp.zeros_like(o_ref)

    vals = v_ref[...].astype(jnp.float32)  # (bn, D)
    ids = id_ref[...]  # (bn, 1) int32
    seg = jax.lax.broadcasted_iota(jnp.int32, (block_n, num_segments), 1)
    onehot = (ids == seg).astype(jnp.float32)  # (bn, S)
    # fp32 contraction: a bf16 pass would round the summed values (word
    # counts past 256 stop being exact)
    o_ref[...] += jax.lax.dot_general(
        vals, onehot, (((0,), (0,)), ((), ())),
        precision=jax.lax.Precision.HIGHEST,
        preferred_element_type=jnp.float32,
    )


@functools.partial(
    jax.jit, static_argnames=("num_segments", "block_n", "interpret")
)
def segment_sum(
    values: jnp.ndarray,  # (N, D)
    segment_ids: jnp.ndarray,  # (N,) int32
    num_segments: int,
    block_n: int = 512,
    interpret: Optional[bool] = None,
) -> jnp.ndarray:
    """Segment sum; semantics = ref.segment_sum_ref (ids need not be sorted
    for correctness, but sorted runs are the intended/benchmarked case)."""
    N, D = values.shape
    if interpret is None:
        interpret = interpret_mode()
    # largest power-of-two row block whose one-hot fits the budget
    fit = 1 << max(3, (_ONEHOT_ELEMS // max(num_segments, 1)).bit_length() - 1)
    bn = min(block_n, fit, N)
    Np = -(-N // bn) * bn
    if Np != N:
        values = jnp.pad(values, ((0, Np - N), (0, 0)))
        # pad ids with an out-of-range id so they hit no segment
        segment_ids = jnp.pad(
            segment_ids, (0, Np - N), constant_values=num_segments
        )
    nb = Np // bn
    kernel = functools.partial(
        _segsum_kernel, block_n=bn, num_segments=num_segments
    )
    out = pl.pallas_call(
        kernel,
        grid=(nb,),
        in_specs=[
            pl.BlockSpec((bn, D), lambda i: (i, 0)),
            pl.BlockSpec((bn, 1), lambda i: (i, 0)),
        ],
        # (D, S): segments on the lanes, so a narrow D pads to 8 sublanes
        # rather than to 128 lanes
        out_specs=pl.BlockSpec((D, num_segments), lambda i: (0, 0)),
        out_shape=jax.ShapeDtypeStruct((D, num_segments), jnp.float32),
        interpret=interpret,
    )(values, segment_ids.astype(jnp.int32).reshape(-1, 1))
    return out.T.astype(values.dtype)
