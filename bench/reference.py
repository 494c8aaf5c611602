"""Plain references the comparison that decides ``correct`` runs against.

Nothing here imports the program.  :func:`makespan` is the paper's makespan
model (Heintz et al., arXiv:1207.7055 §2.2, Equations 4-14) written out
once in numpy: push, map, shuffle and reduce phase ends under a barrier
triple, hard maxima throughout.  ``dtype`` sets the precision every
quantity is held in, so the same code gives the float64 reference and the
lower-precision controls (float32, and bfloat16 through ``ml_dtypes``).
:func:`simple_plans` are the plans a planner has without solving, the
yardstick of a plan's quality.  :func:`word_counts` is the word-count reference: a plain ``bincount`` of
the generated words.
"""
from __future__ import annotations

import ml_dtypes
import numpy as np

__all__ = ["makespan", "simple_plans", "word_counts", "bf16_sums", "DTYPES"]

#: the precisions the reference and its controls are computed in
DTYPES = {"float64": np.float64, "float32": np.float32,
          "bfloat16": ml_dtypes.bfloat16}


def _combine(barrier: str):
    # after a global (G) or local (L) barrier the phases run one after
    # the other; pipelined (P) phases overlap fully
    return np.add if barrier in "GL" else np.maximum


def makespan(platform: dict, x, y, barriers: str = "GGG",
             dtype=np.float64) -> float:
    """Modeled seconds of plan ``(x, y)`` on ``platform`` (a dict of the
    arrays ``D, B_sm, B_mr, C_m, C_r`` and ``alpha``), every array and
    every intermediate held in ``dtype``."""
    c = lambda a: np.asarray(a, dtype=np.float64).astype(dtype)
    D, B_sm, B_mr = c(platform["D"]), c(platform["B_sm"]), c(platform["B_mr"])
    C_m, C_r = c(platform["C_m"]), c(platform["C_r"])
    alpha = c(platform["alpha"])
    x, y = c(x), c(y)
    b_pm, b_ms, b_sr = barriers
    # Equation 4: a mapper's push ends with its slowest incoming link
    v_push = D[:, None] * x
    push_end = np.max(v_push / B_sm, axis=0)
    map_in = np.sum(v_push, axis=0, dtype=dtype)
    # Equations 5/6 (G) or 12 (L/P)
    map_start = (np.full_like(push_end, np.max(push_end)) if b_pm == "G"
                 else push_end)
    map_end = _combine(b_pm)(map_start, map_in / C_m)
    # Equations 7/8 (G) or 13 (L/P): one shuffle row y for every mapper
    shuffle_t = alpha * (map_in[:, None] * y[None, :]) / B_mr
    shuffle_start = (np.full_like(map_end, np.max(map_end)) if b_ms == "G"
                     else map_end)
    shuffle_end = np.max(_combine(b_ms)(shuffle_start[:, None], shuffle_t),
                         axis=0)
    # Equations 9/10 (G) or 14 (L/P)
    reduce_in = alpha * np.sum(map_in, dtype=dtype) * y
    reduce_start = (np.full_like(shuffle_end, np.max(shuffle_end))
                    if b_sr == "G" else shuffle_end)
    reduce_end = _combine(b_sr)(reduce_start, reduce_in / C_r)
    return float(np.max(reduce_end))


def simple_plans(platform: dict) -> list:
    """Every pairing of three push plans (uniform, local: each source to
    the mappers of its own cluster, and in proportion to the source's link
    bandwidths) with three shuffle plans (uniform, and in proportion to
    the reducers' compute rates or their mean incoming bandwidths), as
    ``(x, y)`` pairs."""
    B_sm, B_mr = np.asarray(platform["B_sm"]), np.asarray(platform["B_mr"])
    n_m, n_r = B_sm.shape[1], B_mr.shape[1]
    local = (np.asarray(platform["cluster_s"])[:, None]
             == np.asarray(platform["cluster_m"])[None, :]).astype(float)
    local[local.sum(axis=1) == 0] = 1.0  # no local mapper: spread evenly
    rows = lambda m: m / m.sum(axis=-1, keepdims=True)
    xs = [np.full(B_sm.shape, 1.0 / n_m), rows(local), rows(B_sm)]
    ys = [np.full(n_r, 1.0 / n_r), rows(np.asarray(platform["C_r"], float)),
          rows(B_mr.mean(axis=0))]
    return [(x, y) for x in xs for y in ys]


def word_counts(words: np.ndarray, vocab: int) -> np.ndarray:
    """Occurrences of each word id in ``[0, vocab)``."""
    return np.bincount(words, minlength=vocab).astype(np.int64)


def bf16_sums(sums) -> np.ndarray:
    """The control: integer sums held in bfloat16, as a reduction one
    precision step below the float32 one would hold them."""
    return np.asarray(sums).astype(ml_dtypes.bfloat16).astype(np.int64)
