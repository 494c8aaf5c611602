"""Production mesh construction.

``make_production_mesh`` is a FUNCTION (never a module-level constant) so
importing this module never touches jax device state — the dry-run sets its
fake-device XLA flag before any jax initialization.

Mesh layouts:

* single pod: ``(data=16, model=16)`` — 256 chips (one v5e pod).
  DP/FSDP over ``data``, TP/EP over ``model``.
* multi-pod: ``(pod=2, data=16, model=16)`` — 512 chips.  The ``pod`` axis
  is the DCN dimension: batch parallelism across pods, gradient reduction
  hierarchically scheduled (reduce-scatter on ICI, cross-pod on DCN,
  all-gather on ICI — see repro.train.collective_schedule).

Generalization to ``(P, D, T)`` is direct: the same axis names drive all
sharding rules, so a 16-pod 4096-chip job only changes the shape tuple.
"""
from __future__ import annotations

from typing import Tuple

import jax
from jax.sharding import AxisType

__all__ = ["make_production_mesh", "make_mesh", "batch_axes"]


def make_production_mesh(*, multi_pod: bool = False):
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return make_mesh(shape, axes)


def make_mesh(shape: Tuple[int, ...], axes: Tuple[str, ...]):
    """Arbitrary mesh with the framework's axis conventions."""
    return jax.make_mesh(shape, axes, axis_types=(AxisType.Auto,) * len(shape))


def batch_axes(mesh) -> tuple:
    return tuple(a for a in ("pod", "data") if a in mesh.axis_names)
