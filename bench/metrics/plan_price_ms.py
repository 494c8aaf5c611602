"""Host time, in ms, per call into the planner service spent pricing the
plans in float64 (makespan and phase breakdown): the program's
``geoplan.plan.price`` spans per ``geoplan.plan_many`` call in the traced
window."""
from bench.program_spans import stage_ms


def read(rec):
    return stage_ms(rec, "geoplan.plan_many", "geoplan.plan.price")
