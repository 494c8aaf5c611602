"""RG-LRU gated linear recurrence Pallas TPU kernel (RecurrentGemma).

    h_t = a_t ⊙ h_{t-1} + sqrt(1 − a_t²) ⊙ x_t

Same chunked-parallel-scan structure as :mod:`repro.kernels.mamba_scan`:
log-depth ``associative_scan`` inside a VMEM chunk, inter-chunk carry in
scratch across the sequential chunk grid dimension, feature dimension tiled
as its own grid axis.

TARGET: TPU.  VALIDATED: ``interpret=True`` vs :func:`repro.kernels.ref.rglru_scan_ref`.
"""
from __future__ import annotations

import functools
from typing import Optional, Tuple

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from . import interpret_mode

__all__ = ["rglru_scan"]


def _rglru_kernel(x_ref, a_ref, h0_ref, y_ref, hT_ref, h_scr, *, nchunks, use_h0):
    c = pl.program_id(2)

    @pl.when(c == 0)
    def _init():
        h_scr[...] = h0_ref[...].astype(jnp.float32) if use_h0 else jnp.zeros_like(h_scr)

    x = x_ref[0].astype(jnp.float32)  # (T, bd)
    a = a_ref[0].astype(jnp.float32)  # (T, bd)
    inject = jnp.sqrt(jnp.maximum(1.0 - a * a, 0.0)) * x

    def op(l, r):
        return (l[0] * r[0], r[1] + r[0] * l[1])

    cumdecay, hs = jax.lax.associative_scan(op, (a, inject), axis=0)
    hs = hs + cumdecay * h_scr[...]
    y_ref[0] = hs.astype(y_ref.dtype)
    h_scr[...] = hs[-1:]

    @pl.when(c == nchunks - 1)
    def _final():
        hT_ref[...] = h_scr[...].astype(hT_ref.dtype)


@functools.partial(jax.jit, static_argnames=("chunk", "block_d", "interpret"))
def rglru_scan(
    x: jnp.ndarray,  # (B, T, D)
    a: jnp.ndarray,  # (B, T, D) in (0, 1)
    h0: Optional[jnp.ndarray] = None,  # (B, D)
    chunk: int = 256,
    block_d: int = 256,
    interpret: Optional[bool] = None,
) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """Gated linear recurrence; semantics = ref.rglru_scan_ref.

    Returns ``(h_all, h_T)``.
    """
    B, T, D = x.shape
    if interpret is None:
        interpret = interpret_mode()
    ck = min(chunk, T)
    bd = min(block_d, D)
    assert D % bd == 0, (D, bd)
    Tp = -(-T // ck) * ck
    if Tp != T:
        pad = ((0, 0), (0, Tp - T), (0, 0))
        x = jnp.pad(x, pad)
        # a=1 on padding: h_t = 1·h + 0·x, so the carried state (and hence
        # h_T) is preserved through padded steps.
        a = jnp.pad(a, pad, constant_values=1.0)
    nchunks = Tp // ck
    nd = D // bd
    use_h0 = h0 is not None
    if h0 is None:
        h0 = jnp.zeros((B, D), jnp.float32)

    kernel = functools.partial(_rglru_kernel, nchunks=nchunks, use_h0=use_h0)
    y, hT = pl.pallas_call(
        kernel,
        grid=(B, nd, nchunks),
        in_specs=[
            pl.BlockSpec((1, ck, bd), lambda b, d, c: (b, c, d)),
            pl.BlockSpec((1, ck, bd), lambda b, d, c: (b, c, d)),
            pl.BlockSpec((1, bd), lambda b, d, c: (b, d)),
        ],
        out_specs=[
            pl.BlockSpec((1, ck, bd), lambda b, d, c: (b, c, d)),
            pl.BlockSpec((1, bd), lambda b, d, c: (b, d)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((B, Tp, D), x.dtype),
            jax.ShapeDtypeStruct((B, D), jnp.float32),
        ],
        scratch_shapes=[pltpu.VMEM((1, bd), jnp.float32)],
        interpret=interpret,
    )(x, a, h0)
    return y[:, :T], hT
