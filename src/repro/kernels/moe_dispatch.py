"""MoE token-dispatch Pallas TPU kernel.

Scatters routed tokens into per-expert capacity buffers ``(E, C, D)``.  A
GPU implementation scatters with atomics; the TPU-native adaptation is again
an MXU one-hot matmul: per (expert, token-block) grid cell we build
``P[c, n] = (expert_ids[n] == e) & (slot_ids[n] == c)`` and accumulate
``P @ tokens`` into the expert's VMEM-resident buffer.  Capacity overflow
(``slot >= C``) drops tokens exactly like the reference.

The slot assignment (cumulative position of each token within its expert)
is computed outside the kernel — it is a cheap prefix-sum over int32s; the
bandwidth- and MXU-heavy scatter is what the kernel owns.

TARGET: TPU.  VALIDATED: ``interpret=True`` vs ref.moe_dispatch_ref.
"""
from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from . import interpret_mode

__all__ = ["moe_dispatch", "compute_slots"]


def compute_slots(expert_ids: jnp.ndarray, num_experts: int) -> jnp.ndarray:
    """Position of each token within its expert's buffer (0-based), i.e. a
    per-expert running count in token order."""
    onehot = jax.nn.one_hot(expert_ids, num_experts, dtype=jnp.int32)
    running = jnp.cumsum(onehot, axis=0) - 1  # (T, E)
    return jnp.take_along_axis(running, expert_ids[:, None], axis=1).squeeze(-1)


def _dispatch_kernel(t_ref, id_ref, slot_ref, o_ref, *, block_t, capacity, nt):
    e = pl.program_id(0)
    ti = pl.program_id(1)

    @pl.when(ti == 0)
    def _init():
        o_ref[...] = jnp.zeros_like(o_ref)

    toks = t_ref[...].astype(jnp.float32)  # (bt, D)
    ids = id_ref[...]  # (bt, 1)
    slots = slot_ref[...]  # (bt, 1)
    cap_iota = jax.lax.broadcasted_iota(jnp.int32, (capacity, block_t), 0)
    sel = jnp.logical_and(
        ids.T == e, slots.T == cap_iota
    ).astype(jnp.float32)  # (C, bt)
    o_ref[0] += jax.lax.dot_general(
        sel, toks, (((1,), (0,)), ((), ())),
        preferred_element_type=jnp.float32,
    )


@functools.partial(
    jax.jit, static_argnames=("num_experts", "capacity", "block_t", "interpret")
)
def moe_dispatch(
    tokens: jnp.ndarray,  # (T, D)
    expert_ids: jnp.ndarray,  # (T,)
    slot_ids: jnp.ndarray,  # (T,)
    num_experts: int,
    capacity: int,
    block_t: int = 256,
    interpret: Optional[bool] = None,
) -> jnp.ndarray:
    """Dispatch; semantics = ref.moe_dispatch_ref.  Returns (E, C, D)."""
    T, D = tokens.shape
    if interpret is None:
        interpret = interpret_mode()
    bt = min(block_t, T)
    Tp = -(-T // bt) * bt
    if Tp != T:
        tokens = jnp.pad(tokens, ((0, Tp - T), (0, 0)))
        expert_ids = jnp.pad(expert_ids, (0, Tp - T), constant_values=num_experts)
        slot_ids = jnp.pad(slot_ids, (0, Tp - T), constant_values=capacity)
    nt = Tp // bt
    kernel = functools.partial(
        _dispatch_kernel, block_t=bt, capacity=capacity, nt=nt
    )
    out = pl.pallas_call(
        kernel,
        grid=(num_experts, nt),
        in_specs=[
            pl.BlockSpec((bt, D), lambda e, t: (t, 0)),
            pl.BlockSpec((bt, 1), lambda e, t: (t, 0)),
            pl.BlockSpec((bt, 1), lambda e, t: (t, 0)),
        ],
        out_specs=pl.BlockSpec((1, capacity, D), lambda e, t: (e, 0, 0)),
        out_shape=jax.ShapeDtypeStruct((num_experts, capacity, D), jnp.float32),
        interpret=interpret,
    )(
        tokens,
        expert_ids.astype(jnp.int32).reshape(-1, 1),
        slot_ids.astype(jnp.int32).reshape(-1, 1),
    )
    return out.astype(tokens.dtype)
