"""Run GeoPlan's main path once on a TPU and check what comes out.

    python chip_smoke.py             # one chip
    python chip_smoke.py --chips 4   # one host with four chips

With one chip it runs, in one process and through the library's own entry
points:

1. the planner: a batch of paper-size (8-node) plan requests through
   ``SolverService``, a re-plan round, the same request solved again on the
   host CPU device, and ``GeoSchedule.run_online`` on the 996-node,
   100-job brownout of the scale tier;
2. the engine: ``GeoJob(...).calibrate().plan().execute()`` of word count
   over 10^7 records, reduced by the compiled Pallas ``segment_sum`` and
   checked exactly against a ``numpy.bincount`` of the corpus;
3. LM serving: qwen3-1.7b at its published widths with random weights,
   8 requests through ``ServeEngine`` in bfloat16, its first-token logits
   checked against a float32 forward at highest matmul precision.

With ``--chips 4`` it runs only what needs the four chips: full-width
qwen3-1.7b train steps sharded FSDP x TP over a 2x2 mesh, the sharded step
against the one-device step on a 2-layer full-width variant, and the
expert-parallel MoE ``shard_map`` against the one-device layer.

Every result is printed on its own line.  The last line of standard output
is one JSON object naming the device, printed only when every check
passed.  Without a TPU the script exits with code 2 and prints no such
line.  Compiled programs are cached where ``JAX_COMPILATION_CACHE_DIR``
says, else in ``<checkout>/.jax_cache``.
"""
from __future__ import annotations

import argparse
import dataclasses
import json
import sys
import time
from pathlib import Path

_ROOT = Path(__file__).resolve().parent
for _path in (str(_ROOT / "src"), str(_ROOT)):
    if _path not in sys.path:
        sys.path.insert(0, _path)

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402
import numpy as np  # noqa: E402
from jax.sharding import NamedSharding, PartitionSpec as P  # noqa: E402

from benchmarks.paper_figures import online_brownout  # noqa: E402
from repro.api import GeoJob, split_sources  # noqa: E402
from repro.compile_cache import use_compile_cache  # noqa: E402
from repro.configs import ARCHS, get_config, padded_for_tp  # noqa: E402
from repro.core.makespan import makespan  # noqa: E402
from repro.core.optimize import SolverService, solver_cache_stats  # noqa: E402
from repro.core.plan import uniform_plan  # noqa: E402
from repro.core.platform import planetlab_platform  # noqa: E402
from repro.kernels import ops as kops  # noqa: E402
from repro.kernels.segment_reduce import segment_sum  # noqa: E402
from repro.launch.mesh import make_mesh  # noqa: E402
from repro.launch.serve import make_requests, serve  # noqa: E402
from repro.launch.train import build_training  # noqa: E402
from repro.mapreduce.apps import generate_documents, word_count  # noqa: E402
from repro.models import layers as L  # noqa: E402
from repro.models import model as M  # noqa: E402
from repro.models.sharding import DEFAULT_RULES, axis_rules  # noqa: E402
from repro.serve.engine import ServeConfig, ServeEngine  # noqa: E402
from repro.train.train_step import TrainConfig  # noqa: E402

#: relative slack of a float64 comparison that must hold exactly
F64_SLACK = 1e-9
#: how much worse (relative, float64-priced) the chip's plan of one 8-node
#: request may be than the same request solved on the host CPU device
PLAN_DEVICE_RTOL = 1e-2
#: relative L2 distance allowed between the bfloat16 serving logits and the
#: float32 reference: bfloat16 keeps 8 significant bits (2^-8 = 3.9e-3 per
#: rounding), compounded over 28 layers of rounded activations
LOGITS_RTOL = 5e-2
#: sharded vs one-device first train step, both in bfloat16 compute:
#: partial sums reduced in another order.  A v5e 2x2 read 2.9e-6 (loss) and
#: 2.4e-5 (grad norm); a step whose gradient missed the all-reduce over the
#: data axis keeps the loss and moves the norms by percents
TRAIN_LOSS_RTOL = 1e-4
TRAIN_GNORM_RTOL = 1e-3
#: the same for each parameter leaf's clipped gradient.  The small norm
#: scales read 4.6e-3 on 4 CPU devices at reduced widths, where a gradient
#: of one data shard moved the worst leaf 2e-1
TRAIN_LEAF_RTOL = 2e-2
#: expert-parallel vs one-device MoE at highest matmul precision
MOE_TOL = 2e-4


class CheckFailed(RuntimeError):
    """A phase produced a wrong result."""


def check(ok, what: str) -> None:
    if not ok:
        raise CheckFailed(what)
    print(f"  ok  {what}", flush=True)


def log(phase: str, msg: str) -> None:
    print(f"[{phase}] {msg}", flush=True)


def _peak_bytes(device) -> int:
    stats = device.memory_stats() or {}
    return int(stats.get("peak_bytes_in_use", -1))


# ---------------------------------------------------------------------------
# one chip
# ---------------------------------------------------------------------------

def planner_phase(n_requests: int = 4, n_restarts: int = 16, steps: int = 400,
                  scenario: dict | None = None) -> dict:
    """Batched plan requests, a re-plan round, one request again on the
    host CPU device, and the online brownout run (``scenario`` passes
    sizes to :func:`benchmarks.paper_figures.online_brownout`)."""
    svc = SolverService(mode="e2e_multi", n_restarts=n_restarts, steps=steps)
    platforms = [planetlab_platform(8, seed=s) for s in range(n_requests)]
    t0 = time.perf_counter()
    results = svc.plan_many(platforms, seeds=list(range(n_requests)))
    log("planner", f"plan_many: {n_requests} 8-node requests in "
        f"{time.perf_counter() - t0:.2f}s (compiles included), makespans "
        + " ".join(f"{r.makespan:.6g}" for r in results))
    for g, (p, r) in enumerate(zip(platforms, results)):
        base = makespan(p, uniform_plan(p), r.barriers)
        check(r.makespan <= base * (1 + F64_SLACK),
              f"request {g}: plan {r.makespan:.6g}s <= uniform plan "
              f"{base:.6g}s (float64)")

    # a reducer browns out: re-plan each job with its incumbent competing
    degraded = [dataclasses.replace(p, C_r=p.C_r * np.r_[0.2, np.ones(p.nR - 1)])
                for p in platforms]
    replans = svc.replan_many(degraded, [r.plan for r in results],
                              seeds=list(range(n_requests)))
    for g, (p, r, new) in enumerate(zip(degraded, results, replans)):
        kept = makespan(p, r.plan, new.barriers)
        check(new.makespan <= kept * (1 + F64_SLACK),
              f"re-plan {g}: {new.makespan:.6g}s never modeled worse than "
              f"its incumbent {kept:.6g}s (float64)")

    # the same request solved on this device and on the host CPU device
    here = svc.plan(platforms[0], seed=0)
    with jax.default_device(jax.devices("cpu")[0]):
        host = svc.plan(platforms[0], seed=0)
    rel = (here.makespan - host.makespan) / host.makespan
    log("planner", f"8-node request 0: {jax.devices()[0].platform} "
        f"{here.makespan!r}s, cpu {host.makespan!r}s, relative {rel:+.3e}")
    check(rel <= PLAN_DEVICE_RTOL,
          f"plan on {jax.devices()[0].platform} is at most "
          f"{PLAN_DEVICE_RTOL:g} worse than on the host CPU device")

    sc = online_brownout(**(scenario or {}))
    t0 = time.perf_counter()
    report = sc["schedule"].run_online(
        policy="reactive_shared", arrivals=sc["arrivals"], cfg=sc["cfgs"],
        online=sc["online"], n_restarts=n_restarts, steps=steps,
    )
    wall = time.perf_counter() - t0
    log("planner", f"online brownout: {sc['n_nodes']} nodes, {sc['n_jobs']} "
        f"jobs, {len(report.decisions)} decisions, {len(report.swaps)} swaps, "
        f"{len(report.rejected)} rejected, {wall:.1f}s wall")
    log("planner", f"online makespan {report.makespan_online!r}s, frozen "
        f"{report.makespan_static!r}s, margin {report.improvement:.4f}")
    worse = [d for d in report.decisions
             if d.modeled_after > d.modeled_before * (1 + F64_SLACK)]
    check(not worse, f"all {len(report.decisions)} online decisions never "
          "modeled worse than their incumbent (float64)")
    check(report.makespan_online < report.makespan_static,
          "steered run finishes before the frozen plan")
    stats = solver_cache_stats()
    log("planner", f"solver_cache_stats {stats}")
    return {"plan_makespans": [r.makespan for r in results],
            "device_rel": rel, "online": report, "wall_s": wall,
            "cache": stats}


def engine_phase(n_docs: int = 100_000, words_per_doc: int = 100,
                 vocab: int = 10_000, n_restarts: int = 16, steps: int = 400,
                 seed: int = 0) -> dict:
    """Word count over ``n_docs * words_per_doc`` records on the 8-node
    PlanetLab platform: calibrate, plan, execute, and compare the counts
    with a ``numpy.bincount`` of the corpus."""
    keys, values = generate_documents(n_docs, words_per_doc, vocab=vocab,
                                      seed=seed)
    per_source = split_sources(keys, values, 8)
    app = word_count()
    reducers = []  # (rows, segments, path) per reducer call

    def recorded_reduce(k, v):
        reducers.append((len(k), len(np.unique(k)),
                         kops.segment_sum_path(len(k))))
        return app.reduce_fn(k, v)

    compiles0 = segment_sum._cache_size()
    t0 = time.perf_counter()
    job = GeoJob(planetlab_platform(8, seed=seed),
                 dataclasses.replace(app, reduce_fn=recorded_reduce))
    job = job.calibrate(per_source)
    n_probe = len(reducers)
    report = job.plan(mode="e2e_multi", n_restarts=n_restarts,
                      steps=steps).execute(per_source)
    wall = time.perf_counter() - t0
    compiles = segment_sum._cache_size() - compiles0
    log("engine", f"word count: {len(keys)} records, alpha "
        f"{job.platform.alpha:.4f}, {wall:.1f}s wall (calibrate + plan + "
        f"execute), {compiles} segment_sum compiles")
    for i, (rows, segs, path) in enumerate(reducers):
        run = "calibrate" if i < n_probe else "execute"
        log("engine", f"{run} reducer {i if i < n_probe else i - n_probe}: "
            f"{rows} rows, {segs} segments -> {path}")

    counts = np.zeros(vocab, np.int64)
    for k, v in report.outputs:
        np.add.at(counts, k, v)
    expected = np.bincount(values & ((1 << 20) - 1), minlength=vocab)
    check(np.array_equal(counts, expected),
          f"per-word counts equal numpy bincount exactly ({vocab} words, "
          f"{int(expected.sum())} records)")
    kernel = kops.segment_sum_path(kops._MIN_KERNEL_SEQ)
    paths = {path for _, _, path in reducers}
    check(kernel in paths and paths <= {kernel, "reference"},
          f"reducers ran {sorted(paths)}; the kernel path is {kernel}")
    fallbacks = sum(path == "reference" for _, _, path in reducers)
    log("engine", f"{fallbacks} reducer calls fell back to the reference "
        f"(fewer than {kops._MIN_KERNEL_SEQ} rows)")
    if kernel == "pallas":
        rows, segs, _ = max(r for r in reducers if r[2] == kernel)
        hlo = segment_sum.lower(
            jax.ShapeDtypeStruct((rows, 1), jnp.float32),
            jax.ShapeDtypeStruct((rows,), jnp.int32), segs,
        ).compile().as_text()
        check("tpu_custom_call" in hlo,
              f"segment_sum at {rows} rows x {segs} segments compiles to a "
              "Mosaic tpu_custom_call")
    return {"reducers": reducers, "compiles": compiles, "wall_s": wall}


def serve_phase(cfg=None, n_requests: int = 8, slots: int = 4,
                max_len: int = 256, max_new: int = 16, seed: int = 0) -> dict:
    """Serve ``n_requests`` through ``ServeEngine`` in bfloat16 (a cold pass
    that compiles, then the same requests warm) and compare one request's
    first-token logits with a float32 forward."""
    cfg = cfg or get_config("qwen3-1.7b")
    device = jax.devices()[0]
    params = M.init(cfg, jax.random.PRNGKey(seed))
    n_params = sum(int(a.size) for a in jax.tree.leaves(params))
    log("serve", f"{cfg.name}: {cfg.n_layers} layers, d_model {cfg.d_model}, "
        f"{cfg.n_heads}/{cfg.n_kv_heads} heads, vocab {cfg.vocab}, "
        f"{n_params} parameters (float32)")
    eng = ServeEngine(cfg, params, ServeConfig(
        slots=slots, max_len=max_len, compute_dtype=jnp.bfloat16))
    outputs = {}
    for run in ("cold", "warm"):
        steps0 = eng.step_count
        reqs = make_requests(cfg, n_requests, max_new, seed)
        done, wall = serve(eng, reqs)
        toks = sum(len(r.output) for r in done)
        log("serve", f"{run}: {len(done)} requests, {toks} tokens, "
            f"{eng.step_count - steps0} decode steps, {wall:.2f}s, "
            f"{toks / wall:.1f} tok/s"
            + (" (compiles included)" if run == "cold" else ""))
        check(len(done) == n_requests
              and all(len(r.output) == max_new for r in done),
              f"{run}: every request got its {max_new} tokens")
        outputs[run] = {r.rid: list(r.output) for r in done}
    check(outputs["cold"] == outputs["warm"],
          "greedy outputs repeat exactly on the warm pass")

    prompt = jnp.asarray(reqs[0].prompt[None])
    logits, _, _ = M.prefill(cfg, params, {"tokens": prompt},
                             max_cache_len=max_len,
                             compute_dtype=jnp.bfloat16)
    got = np.asarray(logits[0, -1], np.float32)
    check(int(np.argmax(got)) == outputs["warm"][0][0],
          "the engine's first token is the argmax of the bfloat16 prefill")
    with jax.default_matmul_precision("highest"):
        ref_logits, _, _ = M.forward(cfg, params, {"tokens": prompt},
                                     compute_dtype=jnp.float32)
    ref = np.asarray(ref_logits[0, -1], np.float64)
    rel = float(np.linalg.norm(got - ref) / np.linalg.norm(ref))
    log("serve", f"first-token logits vs float32 reference: relative L2 "
        f"{rel:.3e}, max |diff| {np.abs(got - ref).max():.3e}, "
        f"|ref| max {np.abs(ref).max():.3e}, top-1 "
        f"{'same' if np.argmax(got) == np.argmax(ref) else 'differs'}")
    check(rel <= LOGITS_RTOL,
          f"bfloat16 logits within relative L2 {LOGITS_RTOL:g} of float32")
    peak = _peak_bytes(device)
    log("serve", f"peak_bytes_in_use {peak}")
    return {"logits_rel": rel, "peak_bytes": peak, "outputs": outputs}


# ---------------------------------------------------------------------------
# four chips
# ---------------------------------------------------------------------------

def _lm_batch(cfg, batch: int, seq: int, seed: int = 0) -> dict:
    rng = np.random.default_rng(seed)
    tokens = rng.integers(0, cfg.vocab, size=(batch, seq + 1)).astype(np.int32)
    return {"tokens": tokens[:, :-1], "labels": tokens[:, 1:]}


def _leaf_norms(tree) -> dict:
    norms = jax.jit(lambda t: jax.tree.map(
        lambda a: jnp.linalg.norm(a.astype(jnp.float32)), t))(tree)
    return {jax.tree_util.keystr(path): float(v)
            for path, v in jax.tree_util.tree_leaves_with_path(norms)}


def _train_step_metrics(cfg, mesh, data: dict, steps: int, seed: int = 0):
    """Build the launcher's state and step (bfloat16 compute, remat) on
    ``mesh`` (``None`` = the default device) and run ``steps`` steps on the
    fixed batch ``data``.  Returns the per-step loss and grad norm, and the
    first step's per-leaf norms of the Adam first moment, which after one
    step is the clipped gradient times ``1 - beta1``."""
    tcfg = TrainConfig(compute_dtype=jnp.bfloat16, remat=True)
    sharding = (NamedSharding(mesh, P("data", None)) if mesh is not None
                else jax.devices()[0])
    with axis_rules(mesh, DEFAULT_RULES):
        init, _, _, step = build_training(cfg, tcfg, mesh=mesh, seed=seed)
        state = init()
        data = {k: jax.device_put(v, sharding) for k, v in data.items()}
        out, leaves = [], None
        for _ in range(steps):
            state, metrics = step(state, data)
            out.append({k: float(metrics[k]) for k in ("loss", "grad_norm")})
            leaves = leaves or _leaf_norms(state.opt.m)
    return out, leaves


def train_sharded_phase(cfg=None, mesh_shape=(2, 2), steps: int = 3,
                        batch: int = 8, seq: int = 512) -> dict:
    """Full-width train steps sharded FSDP x TP over a (data, model) mesh."""
    cfg = padded_for_tp(cfg or get_config("qwen3-1.7b"), mesh_shape[1])
    mesh = make_mesh(mesh_shape, ("data", "model"))
    t0 = time.perf_counter()
    metrics, _ = _train_step_metrics(cfg, mesh, _lm_batch(cfg, batch, seq),
                                     steps)
    wall = time.perf_counter() - t0
    log("train", f"{cfg.name} {cfg.n_layers} layers on a {mesh_shape[0]}x"
        f"{mesh_shape[1]} mesh, batch {batch} x {seq}: {steps} steps in "
        f"{wall:.1f}s (compiles included)")
    for s, m in enumerate(metrics):
        log("train", f"step {s + 1}: loss {m['loss']!r} grad_norm "
            f"{m['grad_norm']!r}")
    check(all(np.isfinite(m["loss"]) and np.isfinite(m["grad_norm"])
              for m in metrics), "losses and gradient norms are finite")
    check(metrics[-1]["loss"] < metrics[0]["loss"],
          "the loss falls over the steps on a fixed batch")
    peaks = [_peak_bytes(d) for d in mesh.devices.flat]
    log("train", f"peak_bytes_in_use per device {peaks}")
    return {"metrics": metrics, "peak_bytes": peaks}


def compare_train_steps(sharded: dict, single: dict) -> dict:
    """Check a sharded first train step against the one-device step: loss,
    grad norm and each leaf's clipped-gradient norm (``leaves``) must agree.
    Returns the relative differences."""
    def rel(a, b):
        return abs(a - b) / abs(b)

    diffs = {k: rel(sharded[k], single[k]) for k in ("loss", "grad_norm")}
    leaf_rel = {k: rel(sharded["leaves"][k], v)
                for k, v in single["leaves"].items()}
    worst = max(leaf_rel, key=leaf_rel.get)
    diffs["worst_leaf"] = leaf_rel[worst]
    log("train", f"sharded vs one device: loss {sharded['loss']!r} vs "
        f"{single['loss']!r} ({diffs['loss']:.3e}), grad_norm "
        f"{sharded['grad_norm']!r} vs {single['grad_norm']!r} "
        f"({diffs['grad_norm']:.3e}), worst of {len(leaf_rel)} leaves "
        f"{worst} ({diffs['worst_leaf']:.3e})")
    check(diffs["loss"] <= TRAIN_LOSS_RTOL,
          f"first-step loss agrees within {TRAIN_LOSS_RTOL:g}")
    check(diffs["grad_norm"] <= TRAIN_GNORM_RTOL,
          f"first-step grad norm agrees within {TRAIN_GNORM_RTOL:g}")
    check(diffs["worst_leaf"] <= TRAIN_LEAF_RTOL,
          f"every leaf's first-step gradient norm agrees within "
          f"{TRAIN_LEAF_RTOL:g}")
    return diffs


def train_compare_phase(cfg=None, mesh_shape=(2, 2), batch: int = 8,
                        seq: int = 256, n_layers: int = 2) -> dict:
    """One train step sharded over the mesh and on one device, for a
    ``n_layers`` variant at full widths: see :func:`compare_train_steps`."""
    cfg = cfg or get_config("qwen3-1.7b")
    cfg = padded_for_tp(dataclasses.replace(cfg, n_layers=n_layers),
                        mesh_shape[1])
    mesh = make_mesh(mesh_shape, ("data", "model"))
    data = _lm_batch(cfg, batch, seq)
    runs = {}
    for name, where in (("sharded", mesh), ("single", None)):
        (metrics,), leaves = _train_step_metrics(cfg, where, data, 1)
        runs[name] = dict(metrics, leaves=leaves)
    log("train", f"{n_layers}-layer first step, batch {batch} x {seq}")
    return dict(runs, diffs=compare_train_steps(runs["sharded"],
                                                runs["single"]))


def moe_phase(cfg=None, mesh_shape=(2, 2), batch: int = 4,
              seq: int = 16) -> dict:
    """The expert-parallel ``shard_map`` MoE layer against ``mesh=None``, at
    a capacity with no dropped tokens."""
    cfg = dataclasses.replace(cfg or ARCHS["granite-moe-3b-a800m"],
                              capacity_factor=8.0)
    p = L.init_moe(cfg, jax.random.PRNGKey(0), tp=mesh_shape[1])
    x = jax.random.normal(jax.random.PRNGKey(1), (batch, seq, cfg.d_model))
    mesh = make_mesh(mesh_shape, ("data", "model"))
    with jax.default_matmul_precision("highest"):
        y_ref, aux_ref = L.moe_fwd(cfg, p, x, mesh=None)
        xs = jax.device_put(x, NamedSharding(mesh, P("data", None, None)))
        y_ep, aux_ep = jax.jit(
            lambda pp, xx: L.moe_fwd(cfg, pp, xx, mesh=mesh))(p, xs)
    y_ref, y_ep = np.asarray(y_ref), np.asarray(y_ep)
    err = float(np.abs(y_ep - y_ref).max())
    log("moe", f"{cfg.name}: {cfg.n_experts} experts top-{cfg.top_k}, "
        f"d_model {cfg.d_model}; max |y_ep - y_ref| {err:.3e}, aux "
        f"{float(aux_ep)!r} vs {float(aux_ref)!r}")
    check(np.allclose(y_ep, y_ref, atol=MOE_TOL, rtol=MOE_TOL),
          f"expert-parallel output matches one device within {MOE_TOL:g}")
    check(abs(float(aux_ep) - float(aux_ref)) <= 1e-4 * abs(float(aux_ref)),
          "router aux loss matches")
    return {"max_err": err}


# ---------------------------------------------------------------------------

def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1,
                    help="4: run only the sharded training and MoE checks")
    args = ap.parse_args(argv)

    devices = jax.devices()
    if devices[0].platform != "tpu" or len(devices) < args.chips:
        print(f"chip_smoke: needs {args.chips} TPU chip(s); JAX found "
              f"{len(devices)} {devices[0].platform} device(s)",
              file=sys.stderr)
        return 2
    cache_events = {"hits": 0, "misses": 0}

    def count_cache(event, **_):
        for key in cache_events:
            if event == f"/jax/compilation_cache/cache_{key}":
                cache_events[key] += 1

    jax.monitoring.register_event_listener(count_cache)
    print(f"device: {devices[0].device_kind}, {len(devices)} chip(s); "
          f"jax {jax.__version__}; compile cache {use_compile_cache()}",
          flush=True)

    t0 = time.perf_counter()
    if args.chips == 4:
        train_sharded_phase()
        train_compare_phase()
        moe_phase()
    else:
        planner_phase()
        engine_phase()
        serve_phase()
    print(f"all phases passed in {time.perf_counter() - t0:.1f}s; "
          f"persistent cache hits {cache_events['hits']}, misses "
          f"{cache_events['misses']}", flush=True)
    print(json.dumps({"ok": True, "device": {
        "platform": devices[0].platform,
        "kind": devices[0].device_kind,
        "count": len(devices),
    }}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
