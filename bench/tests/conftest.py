"""The benchmark's own tests run on the CPU, in one process: nothing here
starts a JAX child or needs a chip.

    JAX_PLATFORMS=cpu python3 -m pytest bench/tests -q
"""
import dataclasses
import json
import sys

import pytest

from bench import manifest

SRC = str(manifest.ROOT / "src")
if SRC not in sys.path:
    sys.path.insert(0, SRC)


def small(workload: str, mix: str = None, **traffic):
    """Cell ``workload`` cut to a size the CPU runs in seconds: 4
    restarts of 40 steps, 2,000-document corpora; ``mix`` swaps in another
    traffic file, ``traffic`` overrides its keys."""
    cell = manifest.load(workload)
    cfg = dict(cell.config, solver=dict(cell.config["solver"],
                                        n_restarts=4, steps=40))
    if "word_count" in cfg:
        cfg["word_count"] = dict(cfg["word_count"], docs=2000)
    base = cell.traffic if mix is None else json.loads(
        (manifest.BENCH / "traffic" / f"{mix}.json").read_text())
    return dataclasses.replace(cell, config=cfg,
                               traffic=dict(base, **traffic))


@pytest.fixture
def small_cell():
    return small
