"""One run of one cell of ``BENCHMARK.json`` on the chip.

    python3 -m bench --workload <cell> --seed <n> --seconds <s> --trace <0|1>

The run makes its inputs from ``--seed``, warms every shape the cell's
traffic uses (set-up), measures for ``--seconds``, holds every answer of
the window against the plain reference, and prints one JSON line last on
standard output: ``correct``, ``attempted``, ``failed``, ``metrics``,
``device``, with ``--trace 1`` also ``breakdown``, and last ``checks``,
each number compared beside its limit.  The same numbers are the last
lines of standard error.  With ``--trace 0`` the metrics are the cell's
end-to-end metrics, with ``--trace 1`` its per-layer metrics: those of the
host clock from the measured window, those of the device from a profiler
trace of a second window of ``TRACE_SECONDS`` that follows it.

Without a TPU, or with fewer chips than the cell asks for, the run exits
with code 3 and prints no result.  JAX's persistent compilation cache is
kept in ``.bench_cache/jax`` inside the checkout.
"""
from __future__ import annotations

import argparse
import json
import os
import shutil
import sys
import time

from bench import manifest
from bench.manifest import ROOT

__all__ = ["main", "since_process_start"]

CACHE_DIR = ROOT / ".bench_cache" / "jax"
TRACE_DIR = ROOT / ".bench_cache" / "trace"
NO_CHIP = 3
#: length of the traced window that follows the measured one in a traced
#: run: the TPU's tracer records every op of every solver iteration (about
#: 22,000 per planner call, half a million a second), and collecting a
#: second of them takes the profiler tens of seconds
TRACE_SECONDS = 2.0


def since_process_start() -> float:
    """Seconds since this process started, from ``/proc`` (10 ms steps)."""
    with open("/proc/self/stat") as f:
        ticks = float(f.read().rsplit(")", 1)[1].split()[19])
    with open("/proc/uptime") as f:
        up = float(f.read().split()[0])
    return max(up - ticks / os.sysconf("SC_CLK_TCK"), 0.0)


def _log(msg: str) -> None:
    print(f"[bench] {msg}", file=sys.stderr, flush=True)


def _args(argv):
    ap = argparse.ArgumentParser(prog="python3 -m bench",
                                 description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    return ap.parse_args(argv)


def _use_cache() -> None:
    import jax

    jax.config.update("jax_compilation_cache_dir", str(CACHE_DIR))
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", -1)
    jax.config.update("jax_compilation_cache_max_size", -1)  # no eviction


def _count_compiles() -> dict:
    """Counts, from now on, of programs compiled or loaded
    (``compile``) and of those loaded from the persistent cache (``hit``)."""
    import jax

    counts = {"compile": 0, "hit": 0}

    def on_duration(event, duration, **kw):
        if event == "/jax/core/compile/backend_compile_duration":
            counts["compile"] += 1

    def on_event(event, **kw):
        if event == "/jax/compilation_cache/cache_hits":
            counts["hit"] += 1

    jax.monitoring.register_event_duration_secs_listener(on_duration)
    jax.monitoring.register_event_listener(on_event)
    return counts


def _traced_window(client, seconds: float):
    """A second, short window of the same traffic under the profiler; its
    record carries the reduced trace."""
    import jax

    from bench import trace

    shutil.rmtree(TRACE_DIR, ignore_errors=True)
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    opts.host_tracer_level = 1
    jax.profiler.start_trace(str(TRACE_DIR), profiler_options=opts)
    try:
        with jax.profiler.TraceAnnotation(trace.WINDOW_SPAN):
            rec = client.window(seconds)
    finally:
        jax.profiler.stop_trace()
    rec.trace = trace.reduce(trace.events(str(TRACE_DIR)))
    shutil.rmtree(TRACE_DIR, ignore_errors=True)
    return rec


def main(argv=None, *, started: float = 0.0, t0: float | None = None,
         require_tpu: bool = True, cell=None) -> int:
    """Run one cell once; return the exit code.  ``started`` is the
    process's age at ``t0`` (a ``perf_counter`` reading).  Tests pass
    ``require_tpu=False`` and a ``cell`` of their own."""
    t0 = time.perf_counter() if t0 is None else t0
    args = _args(argv)
    cell = cell or manifest.load(args.workload)
    src = str(ROOT / "src")
    if src not in sys.path:
        sys.path.insert(0, src)
    import jax

    _use_cache()
    devices = jax.devices()
    if require_tpu and devices[0].platform != "tpu":
        _log(f"no TPU: JAX found {devices[0].platform}; nothing measured")
        return NO_CHIP
    if len(devices) < cell.chips:
        _log(f"{cell.name} needs {cell.chips} chips, JAX found "
             f"{len(devices)}; nothing measured")
        return NO_CHIP
    _log(f"backend {devices[0].platform} ({devices[0].device_kind}) up at "
         f"{started + time.perf_counter() - t0:.3f}s")
    from bench import peaks, trace
    from bench.record import Record

    compiles = _count_compiles()
    kind = devices[0].device_kind
    chip_peaks = peaks.peaks(kind) if require_tpu else None
    client = cell.client().Client(cell.config, cell.traffic, args.seed, _log)
    client.setup(args.seconds)
    setup_s = started + time.perf_counter() - t0
    _log(f"set-up done at {setup_s:.3f}s: {compiles['compile']} programs "
         f"compiled or loaded, {compiles['hit']} of them from the cache")
    compiles.update(compile=0, hit=0)

    with jax.profiler.TraceAnnotation(trace.WINDOW_SPAN):
        rec: Record = client.window(args.seconds)
    _log(f"window: {compiles['compile']} programs compiled or loaded "
         "(there should be none)")
    if args.trace:
        rec.traced = _traced_window(client, min(args.seconds, TRACE_SECONDS))
        rec.traced.peaks = chip_peaks
    stats = devices[0].memory_stats() or {}
    device = dict(platform=devices[0].platform, kind=kind,
                  count=cell.chips,
                  memory_peak_bytes=int(stats.get("peak_bytes_in_use", 0)))
    client.close()
    rec.setup_s = setup_s
    if args.trace:
        device.update(busy_s=rec.traced.trace["busy_s"],
                      window_s=rec.traced.trace["window_s"])

    limits = cell.config["limits"]
    verdict = client.check(rec, limits)
    numbers = verdict["numbers"]
    correct = verdict["failed"] == 0 and all(
        numbers[k] <= limits[k] for k in numbers)
    metrics = {}
    for name, (entry, read) in cell.metrics(bool(args.trace)).items():
        value = read(rec)
        if value is not None:
            metrics[name] = {"value": value, "unit": entry["unit"]}
    out = dict(correct=bool(correct), attempted=len(rec.ops),
               failed=int(verdict["failed"]), metrics=metrics, device=device)
    if args.trace:
        out["breakdown"] = {
            "device_ops": [list(kv) for kv in rec.traced.trace["device_ops"]],
            "idle_gaps": [list(kv) for kv in rec.traced.trace["idle_gaps"]],
        }
    out["checks"] = {k: {"value": numbers[k], "limit": limits[k]}
                     for k in numbers}
    for k in numbers:
        _log(f"check {k} = {numbers[k]!r} (limit {limits[k]!r})")
    print(json.dumps(out), flush=True)
    return 0
