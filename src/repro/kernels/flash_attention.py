"""Blockwise-softmax (flash) attention Pallas TPU kernel.

Prefill-path attention with GQA, causal and sliding-window masking.  The
kernel tiles queries and keys into VMEM blocks (``BlockSpec``), keeps the
running max / normalizer / accumulator in VMEM scratch across the
(sequential) kv-block grid dimension, and uses the MXU for both the
``q·kᵀ`` and ``p·v`` contractions.  Fully-masked kv blocks (beyond the
causal frontier or behind the sliding window) are skipped with ``pl.when``,
which makes causal attention ~2× and windowed attention ~T/W cheaper than
the dense loop — this is the arithmetic the roofline analysis credits.

TARGET: TPU (MXU 128×128; block shapes default to multiples of 128).
VALIDATED: ``interpret=True`` on CPU against :func:`repro.kernels.ref.attention_ref`.
"""
from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from . import interpret_mode

__all__ = ["flash_attention"]

_NEG_INF = float("-inf")


def _attn_kernel(
    q_ref, k_ref, v_ref, o_ref, m_scr, l_scr, acc_scr,
    *, scale, causal, window, q_offset, kv_len, bq, bk, nk,
):
    ki = pl.program_id(3)
    qi = pl.program_id(2)

    @pl.when(ki == 0)
    def _init():
        m_scr[...] = jnp.full_like(m_scr, _NEG_INF)
        l_scr[...] = jnp.zeros_like(l_scr)
        acc_scr[...] = jnp.zeros_like(acc_scr)

    q_pos = qi * bq + jax.lax.broadcasted_iota(jnp.int32, (bq, bk), 0) + q_offset
    k_pos = ki * bk + jax.lax.broadcasted_iota(jnp.int32, (bq, bk), 1)

    # Block-level skip: the whole kv block is masked out for this q block.
    q_lo = qi * bq + q_offset
    q_hi = q_lo + bq - 1
    k_lo, k_hi = ki * bk, ki * bk + bk - 1
    live = k_lo <= jnp.minimum(q_hi, kv_len - 1) if causal else k_lo < kv_len
    if window is not None:
        live = jnp.logical_and(live, k_hi > q_lo - window)

    @pl.when(live)
    def _body():
        q = q_ref[0, 0].astype(jnp.float32)
        k = k_ref[0, 0].astype(jnp.float32)
        s = jax.lax.dot_general(
            q, k, (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32
        ) * scale  # (bq, bk)

        mask = k_pos < kv_len
        if causal:
            mask = jnp.logical_and(mask, k_pos <= q_pos)
        if window is not None:
            mask = jnp.logical_and(mask, k_pos > q_pos - window)
        s = jnp.where(mask, s, _NEG_INF)

        m_prev = m_scr[...]
        l_prev = l_scr[...]
        m_new = jnp.maximum(m_prev, jnp.max(s, axis=1, keepdims=True))
        m_safe = jnp.where(m_new == _NEG_INF, 0.0, m_new)
        alpha = jnp.where(m_prev == _NEG_INF, 0.0, jnp.exp(m_prev - m_safe))
        p = jnp.exp(s - m_safe)
        p = jnp.where(mask, p, 0.0)
        l_new = alpha * l_prev + jnp.sum(p, axis=1, keepdims=True)
        v = v_ref[0, 0].astype(jnp.float32)
        pv = jax.lax.dot_general(
            p, v, (((1,), (0,)), ((), ())), preferred_element_type=jnp.float32
        )
        acc_scr[...] = acc_scr[...] * alpha + pv
        m_scr[...] = m_new
        l_scr[...] = l_new

    @pl.when(ki == nk - 1)
    def _finalize():
        l = l_scr[...]
        o_ref[0, 0, :, :] = (
            acc_scr[...] / jnp.where(l == 0.0, 1.0, l)
        ).astype(o_ref.dtype)


@functools.partial(
    jax.jit,
    static_argnames=(
        "causal", "window", "q_offset", "scale", "block_q", "block_k", "interpret",
    ),
)
def flash_attention(
    q: jnp.ndarray,  # (B, Hq, T, Dh)
    k: jnp.ndarray,  # (B, Hkv, S, Dh)
    v: jnp.ndarray,  # (B, Hkv, S, Dh)
    causal: bool = True,
    window: Optional[int] = None,
    q_offset: int = 0,
    scale: Optional[float] = None,
    block_q: int = 128,
    block_k: int = 128,
    interpret: Optional[bool] = None,
) -> jnp.ndarray:
    """Flash attention.  Semantics = :func:`repro.kernels.ref.attention_ref`."""
    B, Hq, T, Dh = q.shape
    _, Hkv, S, _ = k.shape
    assert Hq % Hkv == 0, (Hq, Hkv)
    group = Hq // Hkv
    if scale is None:
        scale = Dh**-0.5
    if interpret is None:
        interpret = interpret_mode()

    bq = min(block_q, T)
    bk = min(block_k, S)
    Tp = -(-T // bq) * bq
    Sp = -(-S // bk) * bk
    if Tp != T:
        q = jnp.pad(q, ((0, 0), (0, 0), (0, Tp - T), (0, 0)))
    if Sp != S:
        k = jnp.pad(k, ((0, 0), (0, 0), (0, Sp - S), (0, 0)))
        v = jnp.pad(v, ((0, 0), (0, 0), (0, Sp - S), (0, 0)))
    nq, nk = Tp // bq, Sp // bk

    kernel = functools.partial(
        _attn_kernel,
        scale=scale,
        causal=causal,
        window=window,
        q_offset=q_offset,
        kv_len=S,
        bq=bq,
        bk=bk,
        nk=nk,
    )
    from jax.experimental.pallas import tpu as pltpu

    out = pl.pallas_call(
        kernel,
        grid=(B, Hq, nq, nk),
        in_specs=[
            pl.BlockSpec((1, 1, bq, Dh), lambda b, h, i, j: (b, h, i, 0)),
            pl.BlockSpec(
                (1, 1, bk, Dh), lambda b, h, i, j, g=group: (b, h // g, j, 0)
            ),
            pl.BlockSpec(
                (1, 1, bk, Dh), lambda b, h, i, j, g=group: (b, h // g, j, 0)
            ),
        ],
        out_specs=pl.BlockSpec((1, 1, bq, Dh), lambda b, h, i, j: (b, h, i, 0)),
        out_shape=jax.ShapeDtypeStruct((B, Hq, Tp, Dh), q.dtype),
        scratch_shapes=[
            pltpu.VMEM((bq, 1), jnp.float32),  # running max m
            pltpu.VMEM((bq, 1), jnp.float32),  # running normalizer l
            pltpu.VMEM((bq, Dh), jnp.float32),  # output accumulator
        ],
        interpret=interpret,
    )(q, k, v)
    return out[:, :, :T]
