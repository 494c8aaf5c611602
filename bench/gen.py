"""Seeded inputs of every cell: platforms, corpora and arrivals.

These are copies of the program's own generators
(``repro.core.platform.planetlab_platform`` and
``repro.mapreduce.apps.generate_documents``), kept here so that the
yardstick stays fixed while the program changes.  Their constants come from
the configuration files.  Every function is a pure function of its
generator, and :func:`rng` derives independent generators from the run's
``--seed`` and a stream label.
"""
from __future__ import annotations

import numpy as np

__all__ = ["rng", "planetlab", "corpus", "arrivals"]


def rng(seed: int, *stream: int) -> np.random.Generator:
    """An independent generator for ``stream`` under the run's ``seed``
    (any whole number; negative seeds wrap)."""
    return np.random.default_rng(
        np.random.SeedSequence([int(seed) % (1 << 64), *map(int, stream)]))


def _log_uniform(g: np.random.Generator, lo_hi, size=None) -> np.ndarray:
    lo, hi = float(lo_hi[0]), float(lo_hi[1])
    return np.exp(g.uniform(np.log(lo), np.log(hi), size=size))


def planetlab(p: dict, alpha: float, g: np.random.Generator) -> dict:
    """The paper's emulated PlanetLab testbed (§4.1): every node is one
    source, one mapper and one reducer; inter-site links drawn
    log-uniformly within the Table 1 range of the continent pair, LAN links
    inside a site, compute rates log-uniform in ``compute_mbps``."""
    sites = [tuple(s) for s in p["sites"]]
    names = sorted({s for s, _ in sites})
    site_ids = np.array([names.index(s) for s, _ in sites], np.int64)
    table = {tuple(k.split("-")): v for k, v in p["table1_kbps"].items()}
    n = len(sites)
    pair_bw: dict = {}
    bw = np.zeros((n, n))
    for i, (si, ci) in enumerate(sites):
        for j, (sj, cj) in enumerate(sites):
            if si == sj:
                bw[i, j] = p["lan_mbps"]
                continue
            if (si, sj) not in pair_bw:
                pair_bw[(si, sj)] = float(
                    _log_uniform(g, table[(ci, cj)])) / 1024.0
            bw[i, j] = pair_bw[(si, sj)]
    rate: dict = {}
    for s, _ in sites:
        if s not in rate:
            rate[s] = (float(_log_uniform(g, p["compute_mbps"])),
                       float(_log_uniform(g, p["compute_mbps"])))
    return dict(
        D=np.full(n, float(p["data_per_source_mb"])),
        B_sm=bw.copy(), B_mr=bw.copy(),
        C_m=np.array([rate[s][0] for s, _ in sites]),
        C_r=np.array([rate[s][1] for s, _ in sites]),
        alpha=float(alpha),
        cluster_s=site_ids, cluster_m=site_ids.copy(),
        cluster_r=site_ids.copy(),
    )


def corpus(p: dict, g: np.random.Generator):
    """``docs * words_per_doc`` word records, Zipf(``zipf``) over
    ``vocab`` words: (doc-id keys, values packing doc id, position and
    word) and the plain word ids the reference counts."""
    n_docs, per_doc = int(p["docs"]), int(p["words_per_doc"])
    words = np.minimum(g.zipf(float(p["zipf"]), size=n_docs * per_doc),
                       int(p["vocab"])) - 1
    doc_ids = np.repeat(np.arange(n_docs, dtype=np.int64), per_doc)
    pos = np.tile(np.arange(per_doc, dtype=np.int64), n_docs)
    packed = (doc_ids << 40) | (pos << 20) | words
    return doc_ids, packed, words


def arrivals(rate_per_s: float, block_s: float, n_blocks: int,
             data_seed: int, g: np.random.Generator) -> np.ndarray:
    """Due times of an open loop at ``rate_per_s`` over ``n_blocks`` blocks
    of ``block_s`` seconds.

    Each block holds ``round(rate * block_s)`` arrivals placed uniformly
    at random, a Poisson process conditioned on its count.  The blocks are
    drawn from ``data_seed`` and only their order from ``g``: every seed
    offers the same arrivals, in another order, so a tail taken over the
    window does not change with the seed's luck in bursts.
    """
    order = g.permutation(n_blocks)
    n = int(round(rate_per_s * block_s))
    return np.concatenate([
        slot * block_s + np.sort(rng(data_seed, int(b)).uniform(0.0, block_s, n))
        for slot, b in enumerate(order)])
