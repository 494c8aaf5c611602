"""Mamba-1 selective-scan Pallas TPU kernel (chunked parallel scan).

GPU Mamba implementations rely on warp-level shuffles and shared-memory
scans; the TPU-native adaptation is a **chunked scan**: the sequence is cut
into VMEM-resident chunks, each chunk is solved with a log-depth
``associative_scan`` on the VPU (fully parallel over the d_inner block and
the state dimension), and the inter-chunk state is carried through VMEM
scratch across the sequential chunk grid dimension.  d_inner is tiled as a
second grid dimension so the per-block working set
(``chunk × bd × d_state`` floats) fits VMEM.

TARGET: TPU.  VALIDATED: ``interpret=True`` vs :func:`repro.kernels.ref.mamba_scan_ref`.
"""
from __future__ import annotations

import functools
from typing import Optional, Tuple

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from . import interpret_mode

__all__ = ["mamba_scan"]


def _mamba_kernel(x_ref, d_ref, A_ref, B_ref, C_ref, Dp_ref, h0_ref,
                  y_ref, hT_ref, h_scr, *, nchunks, use_h0):
    c = pl.program_id(2)

    @pl.when(c == 0)
    def _init():
        h_scr[...] = h0_ref[0].astype(jnp.float32) if use_h0 else jnp.zeros_like(h_scr)

    x = x_ref[0].astype(jnp.float32)  # (T, bd)
    dt = d_ref[0].astype(jnp.float32)  # (T, bd)
    A = A_ref[...].astype(jnp.float32)  # (bd, Ds)
    Bc = B_ref[0].astype(jnp.float32)  # (T, Ds)
    Cc = C_ref[0].astype(jnp.float32)  # (T, Ds)
    Dp = Dp_ref[...].astype(jnp.float32)  # (1, bd)

    decay = jnp.exp(dt[:, :, None] * A[None])  # (T, bd, Ds)
    inject = (dt * x)[:, :, None] * Bc[:, None, :]  # (T, bd, Ds)

    def op(l, r):
        return (l[0] * r[0], r[1] + r[0] * l[1])

    cumdecay, hs = jax.lax.associative_scan(op, (decay, inject), axis=0)
    hs = hs + cumdecay * h_scr[...][None]
    y = jnp.sum(hs * Cc[:, None, :], axis=2) + Dp * x  # (T, bd)
    y_ref[0] = y.astype(y_ref.dtype)
    h_scr[...] = hs[-1]

    @pl.when(c == nchunks - 1)
    def _final():
        hT_ref[0] = h_scr[...].astype(hT_ref.dtype)


@functools.partial(
    jax.jit,
    static_argnames=("chunk", "block_d", "interpret"),
)
def mamba_scan(
    x: jnp.ndarray,  # (B, T, Di)
    delta: jnp.ndarray,  # (B, T, Di)
    A: jnp.ndarray,  # (Di, Ds)
    Bc: jnp.ndarray,  # (B, T, Ds)
    Cc: jnp.ndarray,  # (B, T, Ds)
    D: jnp.ndarray,  # (Di,)
    h0: Optional[jnp.ndarray] = None,  # (B, Di, Ds)
    chunk: int = 128,
    block_d: int = 128,
    interpret: Optional[bool] = None,
) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """Selective scan; semantics = :func:`repro.kernels.ref.mamba_scan_ref`.

    Returns ``(y, h_T)``.  ``h0`` enables stateful decode (the serving path
    carries the SSM state between steps).
    """
    B, T, Di = x.shape
    Ds = A.shape[1]
    if interpret is None:
        interpret = interpret_mode()
    ck = min(chunk, T)
    bd = min(block_d, Di)
    assert Di % bd == 0, (Di, bd)
    Tp = -(-T // ck) * ck
    if Tp != T:
        pad = ((0, 0), (0, Tp - T), (0, 0))
        # zero delta on padding => identity dynamics, zero injection
        x, delta, Bc, Cc = (jnp.pad(a, pad) for a in (x, delta, Bc, Cc))
    nchunks = Tp // ck
    nd = Di // bd
    use_h0 = h0 is not None
    if h0 is None:
        h0 = jnp.zeros((B, Di, Ds), jnp.float32)

    kernel = functools.partial(_mamba_kernel, nchunks=nchunks, use_h0=use_h0)
    y, hT = pl.pallas_call(
        kernel,
        grid=(B, nd, nchunks),
        in_specs=[
            pl.BlockSpec((1, ck, bd), lambda b, d, c: (b, c, d)),  # x
            pl.BlockSpec((1, ck, bd), lambda b, d, c: (b, c, d)),  # delta
            pl.BlockSpec((bd, Ds), lambda b, d, c: (d, 0)),  # A
            pl.BlockSpec((1, ck, Ds), lambda b, d, c: (b, c, 0)),  # B
            pl.BlockSpec((1, ck, Ds), lambda b, d, c: (b, c, 0)),  # C
            pl.BlockSpec((1, bd), lambda b, d, c: (0, d)),  # D (skip)
            pl.BlockSpec((1, bd, Ds), lambda b, d, c: (b, d, 0)),  # h0
        ],
        out_specs=[
            pl.BlockSpec((1, ck, bd), lambda b, d, c: (b, c, d)),  # y
            pl.BlockSpec((1, bd, Ds), lambda b, d, c: (b, d, 0)),  # hT
        ],
        out_shape=[
            jax.ShapeDtypeStruct((B, Tp, Di), x.dtype),
            jax.ShapeDtypeStruct((B, Di, Ds), jnp.float32),
        ],
        scratch_shapes=[pltpu.VMEM((bd, Ds), jnp.float32)],
        interpret=interpret,
    )(x, delta, A, Bc, Cc, D.reshape(1, Di), h0)
    return y[:, :T], hT
