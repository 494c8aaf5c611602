"""Host time, in ms, per call into the planner service spent making the
solver's inputs and putting them on the device: the program's
``geoplan.plan.prep`` spans (uniform plans, float64 scales, initial
logits, host-to-device puts) per ``geoplan.plan_many`` call in the traced
window."""
from bench.program_spans import stage_ms


def read(rec):
    return stage_ms(rec, "geoplan.plan_many", "geoplan.plan.prep")
