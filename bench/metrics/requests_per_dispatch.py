"""Requests served per call into the planner service in the window."""


def read(rec):
    calls = [d["n"] for d in rec.dispatches if d["kind"] == "plan"]
    return sum(calls) / len(calls) if calls else None
