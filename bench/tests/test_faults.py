"""A run whose timed path is broken underneath reports ``correct: false``.

Each test skips only the run's look for a chip and drives the rest of a
run (set-up, window, comparison, result line) with one fault of
:mod:`bench.faults` planted in the program, for each fault these cells
can have: a solver step that leaves its state unchanged, half of the
batch left out, and an answer altered where it is produced.  No exchange
between chips can be left out: every cell runs on one chip.
"""
import json

import pytest

from bench import faults, run


def _run(cell, capsys, seed=4_000_000_007):
    code = run.main(["--workload", cell.name, "--seed", str(seed),
                     "--seconds", "0.5", "--trace", "0"],
                    require_tpu=False, cell=cell)
    assert code == 0
    line = json.loads(capsys.readouterr().out.strip().splitlines()[-1])
    assert list(line)[-1] == "checks"
    return line


@pytest.mark.parametrize("fault", sorted(faults.PLANNER))
def test_planner_faults_fail(small_cell, capsys, monkeypatch, fault):
    cell = small_cell("planetlab8.plan_stream", mix="plan_closed", pool=8)
    assert _run(cell, capsys)["correct"] is True
    faults.PLANNER[fault](monkeypatch.setattr)
    line = _run(cell, capsys)
    assert line["correct"] is False
    if fault == "frozen_anneal":  # every plan valid, none annealed
        checks = line["checks"]["plan_vs_simple"]
        assert checks["value"] > checks["limit"]
    else:
        assert line["failed"] > 0


@pytest.mark.parametrize("fault", sorted(faults.WORDCOUNT))
def test_wordcount_faults_fail(small_cell, capsys, monkeypatch, fault):
    cell = small_cell("planetlab8.wordcount")
    assert _run(cell, capsys)["correct"] is True
    faults.WORDCOUNT[fault](monkeypatch.setattr)
    line = _run(cell, capsys)
    assert line["correct"] is False and line["failed"] == line["attempted"]
