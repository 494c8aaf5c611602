"""From a profiler trace to device busy time, program times and idle gaps.

A traced run writes an ``.xplane.pb`` under its trace directory.
:func:`events` flattens what the metrics need into plain rows
``[plane, line, name, start_ns, duration_ns]``: each device plane's
program events (the ``XLA Modules`` line: one event per run of a jitted
program, named ``jit_<function>(<fingerprint>)``) and the benchmark's own
host spans (``bench.*``, written by ``jax.profiler.TraceAnnotation``).
Host and device events share the trace's clock.  Single ops are not read:
a solver's scan records every op of every iteration, millions in a
window, and the programs that hold them give the same busy time.
:func:`reduce` turns rows into the numbers the run reports.  Both work on
rows, so the tests check the reduction on a small recorded trace without
a chip.
"""
from __future__ import annotations

import glob
import os
from collections import defaultdict

__all__ = ["events", "reduce", "busy_union", "program", "MODULES_LINE",
           "SPAN_PREFIX", "WINDOW_SPAN"]

#: the device plane's line of whole jitted programs
MODULES_LINE = "XLA Modules"
#: host spans the benchmark writes around the calls into each layer
SPAN_PREFIX = "bench."
WINDOW_SPAN = "bench.window"


def events(trace_dir: str) -> list:
    """The rows of the newest ``.xplane.pb`` under ``trace_dir``."""
    from jax.profiler import ProfileData

    paths = sorted(glob.glob(os.path.join(
        trace_dir, "plugins", "profile", "*", "*.xplane.pb")))
    if not paths:
        raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
    rows = []
    for plane in ProfileData.from_file(paths[-1]).planes:
        device = plane.name.startswith("/device:")
        for line in plane.lines:
            if device and line.name != MODULES_LINE:
                continue
            for ev in line.events:
                if device or ev.name.startswith(SPAN_PREFIX):
                    rows.append([plane.name, line.name, ev.name,
                                 float(ev.start_ns), float(ev.duration_ns)])
    return rows


def program(name: str) -> str:
    """A jitted program's name without its fingerprint:
    ``jit__solve_batch_many(5216...)`` -> ``jit__solve_batch_many``."""
    return name.split("(", 1)[0]


def busy_union(intervals) -> list:
    """Merge ``(start, end)`` intervals into disjoint sorted ones."""
    out = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return out


def _clip(intervals, lo, hi) -> list:
    return [(max(s, lo), min(e, hi)) for s, e in intervals
            if min(e, hi) > max(s, lo)]


def _overlap(a, b) -> float:
    """Total overlap of two disjoint sorted interval lists."""
    i = j = 0
    tot = 0.0
    while i < len(a) and j < len(b):
        lo, hi = max(a[i][0], b[j][0]), min(a[i][1], b[j][1])
        if hi > lo:
            tot += hi - lo
        if a[i][1] < b[j][1]:
            i += 1
        else:
            j += 1
    return tot


def reduce(rows, top: int = 10) -> dict:
    """Reduce trace rows to the run's device numbers.

    The window is the ``bench.window`` span.  ``busy_s`` is the union of
    the device's program intervals inside it, averaged over the device
    planes; ``device_ops`` the ``top`` programs by summed device time;
    ``modules`` each program's summed device time and run count;
    ``idle_gaps`` the device's idle time inside the window, attributed to
    the host span that covered it (``host.other`` where none did);
    ``spans`` every host span as ``(name, start_s, end_s)`` and ``busy``
    the device's busy intervals, both in seconds on the trace's clock.
    """
    windows = [r for r in rows if r[2] == WINDOW_SPAN]
    if not windows:
        raise ValueError(f"trace has no {WINDOW_SPAN} span")
    w0 = windows[0][3] * 1e-9
    w1 = w0 + windows[0][4] * 1e-9
    per_device = defaultdict(list)
    modules = defaultdict(lambda: [0.0, 0])
    spans = []
    for plane, line, name, start, dur in rows:
        s, e = start * 1e-9, (start + dur) * 1e-9
        if plane.startswith("/device:"):
            inside = _clip([(s, e)], w0, w1)
            if not inside:
                continue
            per_device[plane].append(inside[0])
            modules[program(name)][0] += inside[0][1] - inside[0][0]
            modules[program(name)][1] += 1
        elif name != WINDOW_SPAN:
            spans.append((name, s, e))
    busies = {d: busy_union(iv) for d, iv in per_device.items()}
    busy_s = (sum(sum(e - s for s, e in b) for b in busies.values())
              / max(len(busies), 1))
    # idle intervals of the first device, attributed to host spans
    busy = next(iter(busies.values()), [])
    idle, t = [], w0
    for s, e in busy:
        if s > t:
            idle.append((t, s))
        t = max(t, e)
    if w1 > t:
        idle.append((t, w1))
    gaps = defaultdict(float)
    by_span = defaultdict(list)
    for name, s, e in spans:
        by_span[name].append((s, e))
    covered = busy_union(iv for v in by_span.values() for iv in v)
    for name, iv in by_span.items():
        gaps[name] += _overlap(idle, busy_union(iv))
    other = sum(e - s for s, e in idle) - _overlap(idle, covered)
    if other > 0:
        gaps["host.other"] += other
    return {
        "window_s": w1 - w0,
        "busy_s": busy_s,
        "device_ops": sorted(((k, v[0]) for k, v in modules.items()),
                             key=lambda kv: -kv[1])[:top],
        "modules": {k: tuple(v) for k, v in modules.items()},
        "idle_gaps": sorted(gaps.items(), key=lambda kv: -kv[1])[:top],
        "spans": spans,
        "busy": busy,
    }
