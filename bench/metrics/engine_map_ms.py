"""Time, in ms, per word-count job spent running the application's map
over each mapper's records: the program's ``geoplan.engine.map`` spans
per ``geoplan.execute`` job in the traced window."""
from bench.program_spans import stage_ms


def read(rec):
    return stage_ms(rec, "geoplan.execute", "geoplan.engine.map")
