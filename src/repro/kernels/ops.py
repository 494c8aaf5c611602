"""Public ops layer: jit'd wrappers that select kernel vs reference.

Models and the MapReduce engine call these; each op dispatches to the Pallas
kernel when shapes warrant it (and pads/tiles appropriately), or to the pure
jnp reference for tiny shapes where kernel launch structure is overhead.
``use_kernel=False`` forces the reference path everywhere (useful to isolate
kernels in A/B tests and on the dry-run path, where XLA's fused attention is
lowered instead so `cost_analysis` sees the dense FLOPs).
"""
from __future__ import annotations

from typing import Optional, Tuple

import jax.numpy as jnp

from . import interpret_mode, ref
from .flash_attention import flash_attention
from .mamba_scan import mamba_scan
from .moe_dispatch import compute_slots, moe_dispatch
from .rglru_scan import rglru_scan
from .segment_reduce import segment_sum

__all__ = [
    "attention",
    "ssm_scan",
    "gated_linear_recurrence",
    "sorted_segment_sum",
    "segment_sum_path",
    "dispatch_tokens",
    "combine_tokens",
    "compute_slots",
]

#: Below these sizes the kernel's block/grid machinery is pure overhead.
_MIN_KERNEL_SEQ = 64


def attention(
    q, k, v,
    causal: bool = True,
    window: Optional[int] = None,
    q_offset: int = 0,
    use_kernel: bool = True,
    block_q: int = 128,
    block_k: int = 128,
):
    """GQA attention (B, Hq, T, Dh) × (B, Hkv, S, Dh) → (B, Hq, T, Dh)."""
    T, S = q.shape[2], k.shape[2]
    if use_kernel and T >= _MIN_KERNEL_SEQ and S >= _MIN_KERNEL_SEQ:
        return flash_attention(
            q, k, v, causal=causal, window=window, q_offset=q_offset,
            block_q=block_q, block_k=block_k,
        )
    return ref.attention_ref(q, k, v, causal=causal, window=window, q_offset=q_offset)


def ssm_scan(x, delta, A, Bc, Cc, D, h0=None, use_kernel: bool = True,
             chunk: int = 128, block_d: int = 128):
    """Mamba-1 selective scan → (y, h_T)."""
    if use_kernel and x.shape[1] >= _MIN_KERNEL_SEQ and x.shape[2] % block_d == 0:
        return mamba_scan(x, delta, A, Bc, Cc, D, h0, chunk=chunk, block_d=block_d)
    return ref.mamba_scan_ref(x, delta, A, Bc, Cc, D, h0)


def gated_linear_recurrence(x, a, h0=None, use_kernel: bool = True,
                            chunk: int = 256, block_d: int = 256):
    """RG-LRU → (h_all, h_T)."""
    if use_kernel and x.shape[1] >= _MIN_KERNEL_SEQ and x.shape[2] % block_d == 0:
        return rglru_scan(x, a, h0, chunk=chunk, block_d=block_d)
    return ref.rglru_scan_ref(x, a, h0)


def segment_sum_path(n_rows: int, use_kernel: bool = True) -> str:
    """What :func:`sorted_segment_sum` runs for ``n_rows`` rows:
    ``"pallas"`` (compiled for the TPU), ``"pallas-interpret"`` (the CPU
    test backend) or ``"reference"`` (below the kernel's row floor, or
    ``use_kernel=False``)."""
    if not use_kernel or n_rows < _MIN_KERNEL_SEQ:
        return "reference"
    return "pallas-interpret" if interpret_mode() else "pallas"


def sorted_segment_sum(values, segment_ids, num_segments: int,
                       use_kernel: bool = True, block_n: int = 512):
    if segment_sum_path(values.shape[0], use_kernel) != "reference":
        return segment_sum(values, segment_ids, num_segments, block_n=block_n)
    return ref.segment_sum_ref(values, segment_ids, num_segments)


def dispatch_tokens(tokens, expert_ids, num_experts: int, capacity: int,
                    use_kernel: bool = True) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """Route tokens into (E, C, D) buffers; returns (buffers, slot_ids)."""
    slots = compute_slots(expert_ids, num_experts)
    if use_kernel and tokens.shape[0] >= _MIN_KERNEL_SEQ:
        out = moe_dispatch(tokens, expert_ids, slots, num_experts, capacity)
    else:
        out = ref.moe_dispatch_ref(tokens, expert_ids, slots, num_experts, capacity)
    return out, slots


def combine_tokens(expert_out, expert_ids, slot_ids, gates, capacity: int):
    """Inverse of dispatch: gather expert outputs back to token order."""
    return ref.moe_combine_ref(expert_out, expert_ids, slot_ids, gates, capacity)
