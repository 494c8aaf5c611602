"""Serving launcher: continuous-batching engine over a (reduced or full)
arch config.

    PYTHONPATH=src python -m repro.launch.serve --arch qwen3-1.7b --reduced \
        --requests 16 --slots 4 --max-new 24
"""
from __future__ import annotations

import argparse
import time
from typing import List

import jax
import numpy as np

from repro.compile_cache import use_compile_cache
from repro.configs import get_config
from repro.models import model as M
from repro.models.config import ArchConfig
from repro.serve.engine import Request, ServeConfig, ServeEngine


def make_requests(cfg: ArchConfig, n: int, max_new: int,
                  seed: int = 0) -> List[Request]:
    """``n`` requests with seeded random prompts of 4 to 23 tokens."""
    rng = np.random.default_rng(seed)
    reqs = []
    for i in range(n):
        length = int(rng.integers(4, 24))
        reqs.append(Request(
            rid=i,
            prompt=rng.integers(0, cfg.vocab, size=length).astype(np.int32),
            max_new_tokens=max_new,
        ))
    return reqs


def serve(engine: ServeEngine, requests: List[Request]):
    """Submit ``requests``, drive the engine until all finish, and return
    ``(finished requests, wall seconds)``."""
    t0 = time.perf_counter()
    for req in requests:
        engine.submit(req)
    done = engine.run()
    return done, time.perf_counter() - t0


def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", required=True)
    ap.add_argument("--reduced", action="store_true")
    ap.add_argument("--requests", type=int, default=8)
    ap.add_argument("--slots", type=int, default=4)
    ap.add_argument("--max-len", type=int, default=256)
    ap.add_argument("--max-new", type=int, default=16)
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args(argv)
    use_compile_cache()

    cfg = get_config(args.arch)
    if args.reduced:
        cfg = cfg.reduced()
    if cfg.frontend == "embed":
        raise SystemExit(f"{cfg.name} is a stub-frontend arch; serve a "
                         "token-in arch (e.g. qwen3-1.7b)")
    params = M.init(cfg, jax.random.PRNGKey(args.seed))
    eng = ServeEngine(cfg, params,
                      ServeConfig(slots=args.slots, max_len=args.max_len))
    done, dt = serve(eng, make_requests(cfg, args.requests, args.max_new,
                                        args.seed))
    toks = sum(len(r.output) for r in done)
    print(f"[serve] {len(done)} requests, {toks} tokens, "
          f"{toks/dt:.1f} tok/s, {eng.step_count} decode steps")
    for r in done[:3]:
        print(f"  rid={r.rid} ttft_steps={r.ttft_steps} out={r.output[:8]}...")


if __name__ == "__main__":
    main()
