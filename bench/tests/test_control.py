"""The control, the reference one precision step down in the program's
place, fails each cell's comparison; the program passes it.  The chip
readings that set the limits come from ``python3 -m bench.study`` at each
cell's own size; this keeps the same comparison at a size the CPU holds."""
from tests_support import drive


def test_planner_control_fails_and_program_passes(small_cell):
    cell = small_cell("planetlab8.plan_stream", mix="plan_closed", pool=8)
    cli, rec = drive(cell, seed=2_718_281_828, seconds=0.5)
    limits = cell.config["limits"]
    program = cli.check(rec, limits)
    control = cli.check(rec, limits, control=True)
    assert program["failed"] == 0
    assert all(program["numbers"][k] <= limits[k] for k in program["numbers"])
    assert control["failed"] > 0
    assert control["numbers"]["price_gap"] > limits["price_gap"]
    assert control["numbers"]["objective_gap"] > limits["objective_gap"]


def test_stream_serves_and_drains_every_request_due(small_cell):
    cell = small_cell("planetlab8.plan_stream", rate_per_s=20.0)
    cli, rec = drive(cell, seed=5, seconds=1.0)
    out = cli.check(rec, cell.config["limits"])
    assert out["failed"] == 0 and len(rec.ops) == 20
    assert all("done" in op for op in rec.ops)  # drained: all answered
    assert sum(d["n"] for d in rec.dispatches) == 20
    # a shorter second window (a traced run's) serves only what is due in it
    assert 0 < len(cli.window(0.5).ops) == int((cli.due < 0.5).sum()) < 20


def test_wordcount_control_fails_and_program_passes(small_cell):
    cell = small_cell("planetlab8.wordcount")
    cli, rec = drive(cell, seed=3, seconds=0.5)
    limits = cell.config["limits"]
    assert cli.check(rec, limits)["numbers"]["count_mismatch"] == 0
    control = cli.check(rec, limits, control=True)
    assert control["failed"] == len(rec.ops) > 0
    assert control["numbers"]["count_mismatch"] > 0
