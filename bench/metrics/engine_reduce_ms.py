"""Time, in ms, per word-count job spent sorting each reducer's records
and reducing them, the ``segment_sum`` calls and their fetches included:
the program's ``geoplan.engine.reduce`` spans per ``geoplan.execute`` job
in the traced window."""
from bench.program_spans import stage_ms


def read(rec):
    return stage_ms(rec, "geoplan.execute", "geoplan.engine.reduce")
