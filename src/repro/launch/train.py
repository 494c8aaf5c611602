"""Training launcher: geo-planned data ingest, fault-tolerant checkpointing,
elastic restart.

    PYTHONPATH=src python -m repro.launch.train --arch qwen3-1.7b --reduced \
        --steps 200 --batch 8 --seq 128 --ckpt-dir /tmp/ckpt --resume auto

Production posture (what transfers to a real fleet):

* **--resume auto** restores the newest *committed* checkpoint (a crashed
  save can never be restored), and the data pipeline fast-forwards to the
  restored step — bitwise-identical batch order after recovery.
* checkpoints are written asynchronously off the training loop, with
  retention + milestones.
* **--mesh DxM / --multi-pod** lay the job out on (data, model[, pod]) and
  shard params/optimizer FSDP×TP via the same rules the dry-run proves at
  16×16 and 2×16×16.  A checkpoint taken on one mesh restores onto any
  other (elastic re-shard: arrays are stored unsharded).
* **--compression int8|bf16** enables error-feedback gradient compression
  for the cross-pod hop.
* **--geo-ingest** plans the corpus push with the paper's optimizer and
  logs the modeled ingest time vs a myopic plan.
"""
from __future__ import annotations

import argparse
import time

import jax
import jax.numpy as jnp

from repro.compile_cache import use_compile_cache
from repro.configs import get_config, padded_for_tp
from repro.core.platform import tpu_pod_platform
from repro.data.pipeline import GeoDataPipeline
from repro.launch.mesh import make_mesh
from repro.models import model as M
from repro.models.sharding import DEFAULT_RULES, axis_rules
from repro.train.checkpoint import CheckpointManager
from repro.train.optim import AdamWConfig, cosine_schedule
from repro.train.train_step import (
    TrainConfig, init_state, make_train_step, state_shardings,
)


def build_training(cfg, tcfg: TrainConfig, mesh=None, lr_fn=None,
                   seed: int = 0):
    """The train state's constructor, its shapes and shardings, and the
    jitted step.  Call inside ``axis_rules(mesh, DEFAULT_RULES)``.

    Returns ``(init, like, shardings, step)``.  ``init()`` makes the state
    where it lives: each device of ``mesh`` computes only its own shards,
    so a state larger than one device is never gathered on device 0.
    ``shardings`` is ``None`` without a mesh."""
    def make_state():
        params = M.init(cfg, jax.random.PRNGKey(seed),
                        tp=mesh.shape["model"] if mesh else 1)
        return init_state(cfg, params, seed=seed,
                          compression=tcfg.compression)

    like = jax.eval_shape(make_state)
    step = make_train_step(cfg, tcfg, mesh=mesh, lr_fn=lr_fn)
    if mesh is None:
        return jax.jit(make_state), like, None, jax.jit(step, donate_argnums=(0,))
    shardings = state_shardings(cfg, like, mesh)
    init = jax.jit(make_state, out_shardings=shardings)
    step = jax.jit(step, in_shardings=(shardings, None),
                   out_shardings=(shardings, None), donate_argnums=(0,))
    return init, like, shardings, step


def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", required=True)
    ap.add_argument("--reduced", action="store_true",
                    help="use the smoke-scale config (CPU-runnable)")
    ap.add_argument("--steps", type=int, default=100)
    ap.add_argument("--batch", type=int, default=8)
    ap.add_argument("--seq", type=int, default=128)
    ap.add_argument("--lr", type=float, default=3e-4)
    ap.add_argument("--warmup", type=int, default=20)
    ap.add_argument("--microbatches", type=int, default=1)
    ap.add_argument("--compression", default="none",
                    choices=["none", "bf16", "int8"])
    ap.add_argument("--mesh", default=None,
                    help="DxM, e.g. 2x2 (needs that many devices)")
    ap.add_argument("--ckpt-dir", default=None)
    ap.add_argument("--ckpt-every", type=int, default=50)
    ap.add_argument("--resume", default="no", choices=["no", "auto"])
    ap.add_argument("--geo-ingest", action="store_true")
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--log-every", type=int, default=10)
    ap.add_argument("--remat", action="store_true")
    ap.add_argument("--dtype", default="float32",
                    choices=["float32", "bfloat16"])
    args = ap.parse_args(argv)
    use_compile_cache()

    cfg = get_config(args.arch)
    if args.reduced:
        cfg = cfg.reduced()

    mesh = None
    if args.mesh:
        d, m = (int(v) for v in args.mesh.split("x"))
        mesh = make_mesh((d, m), ("data", "model"))
        cfg = padded_for_tp(cfg, m)

    dtype = jnp.bfloat16 if args.dtype == "bfloat16" else jnp.float32
    tcfg = TrainConfig(
        adamw=AdamWConfig(lr=args.lr),
        microbatches=args.microbatches,
        remat=args.remat,
        compute_dtype=dtype,
        compression=args.compression,
    )
    lr_fn = cosine_schedule(args.lr, args.warmup, args.steps)

    # --- geo-planned ingest -------------------------------------------------
    platform = tpu_pod_platform(n_pods=2, hosts_per_pod=4, compute_jitter=0.3,
                                seed=args.seed)
    pipe = GeoDataPipeline(
        platform, vocab=cfg.vocab, batch=args.batch, seq=args.seq,
        seed=args.seed, d_model=cfg.d_model, embeds=cfg.frontend == "embed",
        mode="e2e_push" if args.geo_ingest else "uniform",
    )
    if args.geo_ingest:
        from repro.core.optimize import optimize_plan

        myopic = optimize_plan(platform, "myopic_push", n_restarts=6, steps=200)
        print(f"[ingest] planned={pipe.modeled_ingest_time():.2f}s "
              f"myopic-push={myopic.breakdown['push']:.2f}s")

    # --- init / restore -------------------------------------------------------
    mgr = CheckpointManager(args.ckpt_dir, keep=3) if args.ckpt_dir else None
    start_step = 0

    with axis_rules(mesh, DEFAULT_RULES):
        init, like, shardings, step_fn = build_training(
            cfg, tcfg, mesh=mesh, lr_fn=lr_fn, seed=args.seed
        )
        if mgr and args.resume == "auto" and mgr.latest_step() is not None:
            state, extras, start_step = mgr.restore(None, like, shardings)
            print(f"[resume] restored committed step {start_step}")
        else:
            state = init()

        pipe.start(from_step=start_step)
        t_last = time.time()
        try:
            for s in range(start_step, args.steps):
                _, batch_np = next(pipe)
                batch = {k: jnp.asarray(v) for k, v in batch_np.items()}
                state, metrics = step_fn(state, batch)
                if (s + 1) % args.log_every == 0 or s + 1 == args.steps:
                    dt = time.time() - t_last
                    t_last = time.time()
                    tok_s = args.batch * args.seq * args.log_every / max(dt, 1e-9)
                    print(
                        f"step {s+1:5d} loss={float(metrics['loss']):.4f} "
                        f"gnorm={float(metrics['grad_norm']):.2f} "
                        f"lr={float(metrics['lr']):.2e} tok/s={tok_s:.0f}",
                        flush=True,
                    )
                if mgr and (s + 1) % args.ckpt_every == 0:
                    mgr.save_async(s + 1, state, extras={"arch": cfg.name})
            if mgr:
                mgr.save(args.steps, state, extras={"arch": cfg.name},
                         milestone=True)
        finally:
            pipe.stop()
            if mgr:
                mgr.wait()
    print("[train] done")


if __name__ == "__main__":
    main()
