"""Pallas TPU kernels (``ops.py`` selects kernel vs ``ref.py`` reference)."""
from __future__ import annotations

import jax

__all__ = ["interpret_mode"]


def interpret_mode() -> bool:
    """Whether a Pallas kernel runs in interpret mode on the default backend:
    compiled on ``tpu``, interpreted on ``cpu`` (the test backend).  Any
    other backend raises, so no device silently runs the interpreter."""
    backend = jax.default_backend()
    if backend == "tpu":
        return False
    if backend == "cpu":
        return True
    raise RuntimeError(
        f"Pallas kernels here target TPU (compiled) or CPU (interpreted); "
        f"the default backend is {backend!r}"
    )
