"""Mean host time, in ms, of one call into the planner service
(``plan_many``) in the window."""


def read(rec):
    calls = [d["end"] - d["start"] for d in rec.dispatches
             if d["kind"] == "plan"]
    return sum(calls) / len(calls) * 1e3 if calls else None
