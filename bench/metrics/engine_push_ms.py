"""Time, in ms, per word-count job spent splitting each source's records
over the mappers: the program's ``geoplan.engine.push`` spans per
``geoplan.execute`` job in the traced window."""
from bench.program_spans import stage_ms


def read(rec):
    return stage_ms(rec, "geoplan.execute", "geoplan.engine.push")
