"""Share, in %, of its HBM roofline that the planner's anneal reached in
the traced window.

The bytes counted are a lower bound on what the anneal needs
(:func:`needed_bytes`): in each step every annealed logit and its two Adam
moments are read and written once in float32, 24 bytes a logit, and
nothing else.  The counts are the ``params`` and ``steps`` attrs of the
program's ``geoplan.plan.solve`` spans; the time is the summed device time
of the solver's jitted program (``_solve_batch_many``).  Both are taken per
call into the planner service in the traced window.  A program whose solve
spans carry no such attrs gives nothing.
"""
from bench.program_spans import per_root

SOLVER = "_solve_batch_many"
SOLVE_SPAN = "geoplan.plan.solve"


def needed_bytes(params: int, steps: int) -> int:
    """Bytes ``steps`` annealing steps of ``params`` float32 logits move
    at the least: each logit and its two Adam moments, read and written."""
    return 2 * 3 * 4 * params * steps


def read(rec):
    t = rec.traced
    if t is None or t.peaks is None:
        return None
    calls = sum(d["kind"] == "plan" for d in t.dispatches)
    dev = sum(s for name, (s, _) in t.trace["modules"].items()
              if SOLVER in name)
    need = per_root(rec, "geoplan.plan_many", lambda r, kids: sum(
        needed_bytes(s.attrs["params"], s.attrs["steps"]) for s in kids
        if s.name == SOLVE_SPAN and "params" in s.attrs))
    if not calls or dev <= 0 or not need:
        return None
    return 100.0 * need / (dev / calls) / t.peaks["hbm_bytes_per_s"]
