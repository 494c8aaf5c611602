"""Device time, in ms, of the annealed solve per call into the planner
service: the summed device time of the solver's jitted program
(``_solve_batch_many``) in the traced window over the calls made in it."""

SOLVER = "_solve_batch_many"


def read(rec):
    t = rec.traced
    if t is None:
        return None
    calls = sum(d["kind"] == "plan" for d in t.dispatches)
    dev = sum(s for name, (s, _) in t.trace["modules"].items()
              if SOLVER in name)
    return dev / calls * 1e3 if calls and dev > 0 else None
