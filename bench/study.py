"""Readings that set the limits of ``correct``: the program on many seeds,
and the control, at a cell's own size and load.

    python3 -m bench.study --workload <cell> --seconds 5 --seeds 11 12 ...
    python3 -m bench.study --workload <cell> --seconds 5 --seeds 11 12 13 \
        --fault frozen_anneal

One process, one chip.  For each seed the cell's traffic is made from that
seed, served for ``--seconds`` exactly as a run serves it, and every
answer compared twice: as the program gave it, and with the control (the
reference one precision step down) in the program's place.  With
``--fault`` one fault of :mod:`bench.faults` is planted in the program
first.  Each seed prints one JSON line with both sets of numbers.  Not
part of a benchmark run.
"""
from __future__ import annotations

import argparse
import json
import sys

from bench import faults, manifest


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(prog="python3 -m bench.study")
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--seeds", type=int, nargs="+", required=True)
    ap.add_argument("--fault", choices=sorted({**faults.PLANNER,
                                               **faults.WORDCOUNT}))
    args = ap.parse_args(argv)
    cell = manifest.load(args.workload)
    sys.path.insert(0, str(manifest.ROOT / "src"))
    from bench import run

    run._use_cache()
    import jax

    if jax.devices()[0].platform != "tpu":
        print("no TPU; nothing measured", file=sys.stderr)
        return run.NO_CHIP
    log = lambda m: print(f"[study] {m}", file=sys.stderr, flush=True)
    if args.fault:
        {**faults.PLANNER, **faults.WORDCOUNT}[args.fault](setattr)
    limits = cell.config["limits"]
    for seed in args.seeds:
        cli = cell.client().Client(cell.config, cell.traffic, seed, log)
        cli.setup(args.seconds)
        rec = cli.window(args.seconds)
        sound = cli.check(rec, limits)
        control = cli.check(rec, limits, control=True)
        print(json.dumps(dict(workload=cell.name, seed=seed, fault=args.fault,
                              attempted=len(rec.ops), program=sound,
                              control=control)), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
