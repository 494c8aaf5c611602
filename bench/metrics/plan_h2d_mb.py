"""Megabytes put on the device per call into the planner service: the
program's ``plan.h2d_bytes`` counter as each ``geoplan.plan_many`` span of
the traced window saw it grow.  Nothing where a call's span does not carry
the counter (a program that counts no bytes)."""
import math

from bench.program_spans import per_root


def read(rec):
    mb = per_root(rec, "geoplan.plan_many", lambda r, kids: (
        r.attrs.get("plan.h2d_bytes", math.nan) / 1e6))
    return None if mb is None or math.isnan(mb) else mb
