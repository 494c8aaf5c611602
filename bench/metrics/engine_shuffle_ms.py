"""Time, in ms, per word-count job spent hashing the mapped records into
buckets and routing them to the reducers: the program's
``geoplan.engine.shuffle`` spans per ``geoplan.execute`` job in the
traced window."""
from bench.program_spans import stage_ms


def read(rec):
    return stage_ms(rec, "geoplan.execute", "geoplan.engine.shuffle")
