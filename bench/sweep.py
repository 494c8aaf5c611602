"""Find the knee of an open-loop cell: the highest offered rate that shows
no growing backlog over the window.

    python3 -m bench.sweep --workload planetlab8.plan_stream --seed <n> \
        --seconds 10 --rates 60 80 100 120 140

One process, one chip: for each rate the cell's traffic is made again at
that rate from the seed and served for ``--seconds``.  Each rate prints
one JSON line: offered and completed requests per second, the queue at
the window's start, middle and end, and the 95th-percentile latency from
due time.  The cell's ``rate_per_s`` is then written into its traffic file
by hand, at about four fifths of the knee.  Not part of a benchmark run.
"""
from __future__ import annotations

import argparse
import dataclasses
import json
import sys

from bench import manifest
from bench.record import percentile


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(prog="python3 -m bench.sweep")
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--rates", type=float, nargs="+", required=True)
    args = ap.parse_args(argv)
    cell = manifest.load(args.workload)
    sys.path.insert(0, str(manifest.ROOT / "src"))
    from bench import run

    run._use_cache()
    import jax

    if jax.devices()[0].platform != "tpu":
        print("no TPU; nothing measured", file=sys.stderr)
        return run.NO_CHIP
    log = lambda m: print(f"[sweep] {m}", file=sys.stderr, flush=True)
    for rate in args.rates:
        mix = dict(cell.traffic, rate_per_s=rate)
        c = dataclasses.replace(cell, traffic=mix)
        cli = c.client().Client(c.config, mix, args.seed, log)
        cli.setup(args.seconds)
        rec = cli.window(args.seconds)
        lat = [op["done"] - op["due"] for op in rec.ops]
        print(json.dumps(dict(
            rate_per_s=rate, offered=len(rec.ops),
            completed_per_s=len(rec.ops) / rec.window_s,
            window_s=rec.window_s, backlog_start=0,
            backlog_half=rec.backlog_at_half, backlog_end=rec.backlog_at_end,
            p95_ms=percentile(lat, 95) * 1e3,
            failed=cli.check(rec, c.config["limits"])["failed"],
            requests_per_dispatch=len(rec.ops) / len(rec.dispatches))),
            flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
