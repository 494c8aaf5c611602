"""Time, in ms, per call into the planner service from the solver's
dispatch to its objectives back on the host: the program's
``geoplan.plan.solve`` spans (dispatch, the device's anneal, the sync) per
``geoplan.plan_many`` call in the traced window."""
from bench.program_spans import stage_ms


def read(rec):
    return stage_ms(rec, "geoplan.plan_many", "geoplan.plan.solve")
