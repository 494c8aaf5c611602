"""Host time, in ms, per call into the planner service spent fetching each
request's best plan from the device and renormalising it: the program's
``geoplan.plan.fetch`` spans per ``geoplan.plan_many`` call in the traced
window."""
from bench.program_spans import stage_ms


def read(rec):
    return stage_ms(rec, "geoplan.plan_many", "geoplan.plan.fetch")
