"""Multi-pod dry-run: ``.lower().compile()`` every (arch × shape × mesh).

For each cell this script

  1. builds the production mesh (16×16 single pod / 2×16×16 multi-pod),
  2. constructs ShapeDtypeStruct stand-ins for the inputs (and the decode
     cache / train state) — no device allocation ever happens,
  3. jits the right step function (train_step / prefill / serve_step) with
     explicit in/out shardings,
  4. ``lower().compile()`` — a sharding mismatch, compile-time OOM or
     unsupported collective here is a bug in the framework,
  5. records ``memory_analysis()`` / ``cost_analysis()`` and the collective
     traffic parsed from the partitioned HLO into a JSON report that the
     roofline analysis (benchmarks/roofline.py, EXPERIMENTS.md) consumes.

Usage::

    python -m repro.launch.dryrun --arch qwen3-1.7b --shape train_4k
    python -m repro.launch.dryrun --all --multi-pod both --out reports/
"""
import argparse
import functools
import json
import os
import re
import time
import traceback
from typing import Any, Dict

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import NamedSharding, PartitionSpec as P

from repro.configs import (
    SHAPES, cache_specs, cells, get_config, input_specs, padded_for_tp,
)
from repro.launch.mesh import batch_axes, make_production_mesh
from repro.models import model as M
from repro.models.sharding import DEFAULT_RULES, axis_rules
from repro.train.train_step import TrainConfig, init_state, make_train_step, state_shardings

__all__ = ["run_cell", "collective_bytes_from_hlo"]

_COLL_RE = re.compile(
    r"(?P<shapes>(?:\(?\s*(?:[a-z0-9]+)\[[0-9,]*\][^=]*?)) "
    r"(?P<op>all-reduce|all-gather|reduce-scatter|all-to-all|collective-permute)"
    r"(?:-start)?\(",
)
_SHAPE_RE = re.compile(r"([a-z][a-z0-9]*)\[([0-9,]*)\]")
_GROUP_RE = re.compile(r"replica_groups=\{\{([0-9,]+)\}")
_GROUP_RE2 = re.compile(r"replica_groups=\[(\d+),(\d+)\]")

_DTYPE_BYTES = {
    "f64": 8, "f32": 4, "f16": 2, "bf16": 2, "f8e4m3fn": 1, "f8e5m2": 1,
    "s64": 8, "u64": 8, "s32": 4, "u32": 4, "s16": 2, "u16": 2,
    "s8": 1, "u8": 1, "pred": 1,
}


def _shape_bytes(dtype: str, dims: str) -> int:
    n = 1
    for d in dims.split(","):
        if d:
            n *= int(d)
    return n * _DTYPE_BYTES.get(dtype, 4)


def collective_bytes_from_hlo(hlo: str) -> Dict[str, float]:
    """Per-device bytes transported by each collective kind, from the
    *partitioned* HLO (shapes in the SPMD module are per-partition).

    Ring-model accounting per op (S = per-partition result bytes, G =
    replica-group size): all-reduce 2·S·(G−1)/G, all-gather S·(G−1)/G,
    reduce-scatter S·(G−1) (operand = G·S), all-to-all S·(G−1)/G,
    collective-permute S.
    """
    out: Dict[str, float] = {
        "all-reduce": 0.0, "all-gather": 0.0, "reduce-scatter": 0.0,
        "all-to-all": 0.0, "collective-permute": 0.0,
    }
    for line in hlo.splitlines():
        line = line.strip()
        if "=" not in line:
            continue
        _, _, rhs = line.partition("=")
        # HLO: %name = <result-type> <opcode>(operands...); the result type
        # may itself be a tuple "(f32[..], ..)" so locate the opcode token
        # directly and take every shape that precedes it.
        om = re.match(
            r"(?P<res>[^=]*?)\s(?P<op>all-reduce|all-gather|reduce-scatter|"
            r"all-to-all|collective-permute)(?P<start>-start)?\(",
            rhs,
        )
        if om is None:
            continue
        m = om.group("op")
        shapes = _SHAPE_RE.findall(om.group("res"))
        if not shapes:
            continue
        size = sum(_shape_bytes(dt, dims) for dt, dims in shapes)
        g = 1
        gm = _GROUP_RE.search(line)
        if gm:
            g = len(gm.group(1).split(","))
        else:
            gm2 = _GROUP_RE2.search(line)
            if gm2:
                g = int(gm2.group(2))
        if g <= 1:
            continue
        if m == "all-reduce":
            out[m] += 2.0 * size * (g - 1) / g
        elif m == "all-gather":
            out[m] += size * (g - 1) / g
        elif m == "reduce-scatter":
            out[m] += float(size) * (g - 1)
        elif m == "all-to-all":
            out[m] += size * (g - 1) / g
        else:  # collective-permute
            out[m] += float(size)
    out["total"] = sum(v for k, v in out.items() if k != "total")
    return out


def _batch_shardings(specs: Dict[str, jax.ShapeDtypeStruct], mesh):
    """Input shardings: batch dim over (pod, data) when divisible."""
    baxes = batch_axes(mesh)
    nb = int(np.prod([mesh.shape[a] for a in baxes])) if baxes else 1
    bspec = baxes if len(baxes) > 1 else (baxes[0] if baxes else None)

    def sh(s):
        dims: list = [None] * len(s.shape)
        if len(s.shape) >= 1 and s.shape[0] % nb == 0 and nb > 1:
            dims[0] = bspec
        return NamedSharding(mesh, P(*dims))

    return {k: sh(v) for k, v in specs.items()}


def _cache_shardings(cache_shape, mesh, B: int):
    """Decode-cache shardings: batch over (pod, data) when divisible, the
    head/feature dim over 'model'."""
    baxes = batch_axes(mesh)
    nb = int(np.prod([mesh.shape[a] for a in baxes])) if baxes else 1
    bspec = baxes if len(baxes) > 1 else (baxes[0] if baxes else None)

    def sh(leaf):
        dims = [None] * len(leaf.shape)
        # leaves: k/v (G,B,H,S,D); ssm h (G,B,Di,Ds); conv (G,B,K,Di|w)
        if len(leaf.shape) >= 2 and leaf.shape[1] == B and B % nb == 0 and nb > 1:
            dims[1] = bspec
        if len(leaf.shape) == 5:  # attn kv: shard heads over model
            if leaf.shape[2] % mesh.shape["model"] == 0:
                dims[2] = "model"
        elif len(leaf.shape) == 4:  # ssm h: (G,B,Di,Ds) — Di over model
            if leaf.shape[2] % mesh.shape["model"] == 0:
                dims[2] = "model"
        elif len(leaf.shape) == 3:  # conv (G?,B,..) fallback replicate tail
            pass
        return NamedSharding(mesh, P(*dims))

    return jax.tree.map(sh, cache_shape)


def run_cell(
    arch: str,
    shape: str,
    multi_pod: bool,
    compute_dtype=jnp.bfloat16,
    donate: bool = True,
    mesh=None,
    reduced: bool = False,
    analysis: bool = True,
    variant: str = "baseline",  # baseline | infer_tp | kv_int8 | infer_tp+kv_int8
    microbatches: int = 1,
) -> Dict[str, Any]:
    """Lower + compile one (arch, shape, mesh) cell; return the report.

    ``mesh``/``reduced`` exist for the CI-scale smoke path (tiny mesh on a
    handful of fake devices); the deliverable sweep uses the production
    meshes."""
    cfg_orig = get_config(arch)
    if reduced:
        cfg_orig = cfg_orig.reduced()
    if mesh is None:
        mesh = make_production_mesh(multi_pod=multi_pod)
    # TP-divisibility padding (exact semantics; waste shows up in the
    # MODEL_FLOPS/HLO_FLOPS roofline ratio, which uses the ORIGINAL config).
    cfg = padded_for_tp(cfg_orig, mesh.shape["model"])
    spec = SHAPES[shape]
    report: Dict[str, Any] = {
        "arch": arch, "shape": shape,
        "mesh": "2x16x16" if multi_pod else "16x16",
        "n_devices": int(np.prod(list(mesh.shape.values()))),
        "kind": spec.kind,
        "model_params": cfg_orig.n_params(),
        "model_active_params": cfg_orig.n_active_params(),
        "padded_params": cfg.n_params(),
        "padded_active_params": cfg.n_active_params(),
    }
    from repro.launch.analysis import attention_flops

    report["attn_flops_total"] = attention_flops(
        cfg, spec.kind,
        B=spec.global_batch,
        T=spec.seq_len if spec.kind != "decode" else 1,
        cache_len=spec.seq_len if spec.kind == "decode" else 0,
    )
    report["variant"] = variant
    report["microbatches"] = microbatches
    kv_int8 = "kv_int8" in variant
    rules = DEFAULT_RULES
    if "infer_tp" in variant and spec.kind != "train":
        from repro.models.sharding import INFERENCE_RULES

        rules = INFERENCE_RULES
    t0 = time.time()
    with axis_rules(mesh, rules):
        specs = input_specs(cfg, shape, dtype=compute_dtype)
        in_sh_batch = _batch_shardings(specs, mesh)

        def build(unroll: bool):
            if spec.kind == "train":
                tcfg = TrainConfig(compute_dtype=compute_dtype, remat=True,
                                   use_kernels=False, unroll_groups=unroll,
                                   microbatches=microbatches)
                step = make_train_step(cfg, tcfg, mesh=mesh)
                params_shape = jax.eval_shape(
                    functools.partial(M.init, cfg, tp=mesh.shape["model"]),
                    jax.random.PRNGKey(0),
                )
                state_shape = jax.eval_shape(
                    functools.partial(init_state, cfg), params_shape
                )
                st_sh = state_shardings(cfg, state_shape, mesh)
                return jax.jit(
                    step,
                    in_shardings=(st_sh, in_sh_batch),
                    donate_argnums=(0,) if donate else (),
                ).lower(state_shape, specs)
            params_shape = jax.eval_shape(
                functools.partial(M.init, cfg, tp=mesh.shape["model"]),
                jax.random.PRNGKey(0),
            )
            p_sh = jax.tree.map(
                lambda s: NamedSharding(mesh, s),
                M.param_shardings(cfg, params_shape),
            )
            if spec.kind == "prefill":
                last_only = "last_only" in variant

                def prefill_fn(params, batch):
                    logits, cache, _ = M.prefill(
                        cfg, params, batch, max_cache_len=spec.seq_len,
                        mesh=mesh, compute_dtype=compute_dtype,
                        unroll_groups=unroll, last_only=last_only,
                    )
                    return logits[:, -1], cache

                return jax.jit(
                    prefill_fn, in_shardings=(p_sh, in_sh_batch)
                ).lower(params_shape, specs)
            # decode (serve_step: one token against a seq_len cache)
            cache_shape = cache_specs(cfg, shape, dtype=compute_dtype,
                                      kv_int8=kv_int8)
            c_sh = _cache_shardings(cache_shape, mesh, spec.global_batch)

            def serve_step(params, batch, cache):
                logits, new_cache, _ = M.decode_step(
                    cfg, params, batch, cache, mesh=mesh,
                    compute_dtype=compute_dtype, unroll_groups=unroll,
                )
                return logits[:, -1], new_cache

            return jax.jit(
                serve_step,
                in_shardings=(p_sh, in_sh_batch, c_sh),
                donate_argnums=(2,) if donate else (),
            ).lower(params_shape, specs, cache_shape)

        # --- production build (rolled scan): memory truth --------------------
        lowered = build(unroll=False)
        report["lower_s"] = round(time.time() - t0, 2)
        t1 = time.time()
        compiled = lowered.compile()
        report["compile_s"] = round(time.time() - t1, 2)

        mem = compiled.memory_analysis()
        if mem is not None:
            for attr in (
                "temp_size_in_bytes", "argument_size_in_bytes",
                "output_size_in_bytes", "alias_size_in_bytes",
                "generated_code_size_in_bytes",
            ):
                v = getattr(mem, attr, None)
                if v is not None:
                    report[attr] = int(v)
            total = sum(
                report.get(k, 0)
                for k in ("temp_size_in_bytes", "argument_size_in_bytes",
                          "output_size_in_bytes")
            ) - report.get("alias_size_in_bytes", 0)
            report["per_device_bytes"] = int(total)
        cost = compiled.cost_analysis()
        if cost:
            report["hlo_flops_per_device_rolled"] = float(cost.get("flops", -1))
            report["hlo_bytes_per_device_rolled"] = float(
                cost.get("bytes accessed", -1)
            )
        hlo = compiled.as_text()
        report["collectives_per_device_bytes_rolled"] = (
            collective_bytes_from_hlo(hlo)
        )
        report["hlo_size_chars"] = len(hlo)

        # --- analysis build (group scan unrolled): flop/traffic truth --------
        # XLA's cost_analysis counts while-loop bodies ONCE (verified in
        # EXPERIMENTS.md §Dry-run); unrolling the layer-group scan makes
        # FLOPs/bytes/collectives per-layer-correct.  The chunked-attention
        # inner scans remain rolled; their matmul FLOPs are added
        # analytically by benchmarks/roofline.py.
        if analysis:
            t2 = time.time()
            compiled_u = build(unroll=True).compile()
            report["analysis_compile_s"] = round(time.time() - t2, 2)
            cost_u = compiled_u.cost_analysis()
            if cost_u:
                report["hlo_flops_per_device"] = float(cost_u.get("flops", -1))
                report["hlo_bytes_per_device"] = float(
                    cost_u.get("bytes accessed", -1)
                )
            report["collectives_per_device_bytes"] = collective_bytes_from_hlo(
                compiled_u.as_text()
            )
    return report


def use_fake_host_devices(n: int = 512) -> None:
    """Give the CPU backend ``n`` devices, enough for the production meshes.
    Call before anything touches a JAX device: the count locks on first
    use."""
    os.environ["XLA_FLAGS"] = (
        f"--xla_force_host_platform_device_count={n} "
        + os.environ.get("XLA_FLAGS", "")
    )


def main():
    use_fake_host_devices()
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default=None)
    ap.add_argument("--shape", default=None)
    ap.add_argument("--all", action="store_true")
    ap.add_argument("--multi-pod", default="single",
                    choices=["single", "multi", "both"])
    ap.add_argument("--out", default="reports/dryrun")
    ap.add_argument("--skip-done", action="store_true")
    args = ap.parse_args()

    os.makedirs(args.out, exist_ok=True)
    if args.all:
        todo = cells()
    else:
        assert args.arch and args.shape, "--arch/--shape or --all"
        todo = [(args.arch, args.shape)]
    meshes = {"single": [False], "multi": [True], "both": [False, True]}[
        args.multi_pod
    ]

    failures = []
    for arch, shape in todo:
        for mp in meshes:
            tag = f"{arch}__{shape}__{'multi' if mp else 'single'}"
            path = os.path.join(args.out, tag + ".json")
            if args.skip_done and os.path.exists(path):
                print(f"[skip] {tag}")
                continue
            print(f"[dryrun] {tag} ...", flush=True)
            try:
                # the unrolled analysis build feeds the (single-pod-only)
                # roofline table; multi-pod cells prove sharding + memory.
                rep = run_cell(arch, shape, mp, analysis=not mp)
                with open(path, "w") as f:
                    json.dump(rep, f, indent=1)
                print(
                    f"  ok: compile={rep['compile_s']}s "
                    f"mem/dev={rep.get('per_device_bytes', -1)/2**30:.2f}GiB "
                    f"flops/dev={rep.get('hlo_flops_per_device', -1):.3g} "
                    f"coll/dev={rep['collectives_per_device_bytes']['total']/2**20:.1f}MiB",
                    flush=True,
                )
            except Exception as e:  # a failing cell is a framework bug
                failures.append((tag, repr(e)))
                with open(path + ".FAILED", "w") as f:
                    f.write(traceback.format_exc())
                print(f"  FAILED: {e}", flush=True)
    if failures:
        print(f"\n{len(failures)} FAILURES:")
        for tag, err in failures:
            print(" ", tag, err[:200])
        raise SystemExit(1)
    print("\nall cells compiled.")


if __name__ == "__main__":
    main()
