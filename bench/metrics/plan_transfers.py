"""Host-device transfers per call into the planner service, both ways:
the program's ``plan.h2d`` and ``plan.d2h`` counters as each
``geoplan.plan_many`` span of the traced window saw them grow."""
from bench.program_spans import per_root


def read(rec):
    return per_root(rec, "geoplan.plan_many", lambda r, kids: (
        r.attrs.get("plan.h2d", 0) + r.attrs.get("plan.d2h", 0)))
