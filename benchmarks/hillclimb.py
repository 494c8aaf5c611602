"""§Perf hillclimb driver: for each selected cell, compile baseline and
candidate variants, record the roofline-relevant deltas.

    PYTHONPATH=src python -m benchmarks.hillclimb --cell nemo_prefill
"""
import argparse
import json
import os

from repro.launch import dryrun as dr

CELLS = {
    # memory-dominated, paper-representative (MoE): microbatch accumulation
    "llama4_train": [
        ("llama4-scout-17b-a16e", "train_4k", dict(variant="baseline", analysis=False)),
        ("llama4-scout-17b-a16e", "train_4k", dict(variant="baseline_mb8", analysis=False)),
        ("llama4-scout-17b-a16e", "train_4k", dict(variant="baseline_mb16", analysis=False)),
    ],
    # iteration 2+3: expert FSDP (2D expert sharding) × microbatching.
    # NOTE: run after the DEFAULT_RULES expert_in="data" change; the
    # "baseline" files above were captured with model-only expert sharding.
    "llama4_train_opt": [
        ("llama4-scout-17b-a16e", "train_4k", dict(variant="expert_fsdp", analysis=False)),
        ("llama4-scout-17b-a16e", "train_4k", dict(variant="expert_fsdp_mb8", analysis=False)),
        ("llama4-scout-17b-a16e", "train_4k", dict(variant="expert_fsdp_mb16", analysis=False)),
    ],
    # most collective-bound dense cell: pure-TP inference resharding
    "nemo_prefill": [
        ("mistral-nemo-12b", "prefill_32k", dict(variant="baseline")),
        ("mistral-nemo-12b", "prefill_32k", dict(variant="infer_tp")),
    ],
    # worst memory posture: int8 KV cache (+ pure-TP params)
    "musicgen_decode": [
        ("musicgen-large", "decode_32k", dict(variant="baseline")),
        ("musicgen-large", "decode_32k", dict(variant="kv_int8")),
        ("musicgen-large", "decode_32k", dict(variant="infer_tp+kv_int8")),
    ],
    # qwen3 microbatch ladder (methodology cross-check, cheap)
    "qwen3_train_mb": [
        ("qwen3-1.7b", "train_4k", dict(variant="baseline", analysis=False)),
        ("qwen3-1.7b", "train_4k", dict(variant="baseline_mb4", analysis=False)),
        ("qwen3-1.7b", "train_4k", dict(variant="baseline_mb8", analysis=False)),
    ],
}


def run(cell: str, out_dir: str):
    os.makedirs(out_dir, exist_ok=True)
    for arch, shape, kw in CELLS[cell]:
        variant = kw.pop("variant")
        mb = 1
        if "_mb" in variant:
            mb = int(variant.rsplit("_mb", 1)[1])
        tag = f"{arch}__{shape}__{variant}"
        path = os.path.join(out_dir, tag + ".json")
        if os.path.exists(path):
            print(f"[skip] {tag}")
            continue
        print(f"[hillclimb] {tag}", flush=True)
        mesh = None
        if "mesh_shape" in kw:
            from repro.launch.mesh import make_mesh

            d, m = kw.pop("mesh_shape")
            mesh = make_mesh((d, m), ("data", "model"))
        rep = dr.run_cell(
            arch, shape, multi_pod=False,
            variant=variant.split("_mb")[0],
            microbatches=mb, mesh=mesh,
            **kw,
        )
        with open(path, "w") as f:
            json.dump(rep, f, indent=1)
        print(
            f"  mem/dev={rep.get('per_device_bytes', -1)/2**30:.2f}GiB "
            f"coll/dev={rep.get('collectives_per_device_bytes', rep.get('collectives_per_device_bytes_rolled'))['total']/2**30:.3f}GiB",
            flush=True,
        )


CELLS["final_iters"] = [
    # nemo prefill: TP16->TP8 mesh reshape (tokens per TP group halve ->
    # per-device AR traffic halves; kv=8 and 32 q-heads divide evenly: no
    # head padding)
    ("mistral-nemo-12b", "prefill_32k",
     dict(variant="infer_tp+last_only+tp8", mesh_shape=(32, 8))),
    # llama4: push microbatching one more step
    ("llama4-scout-17b-a16e", "train_4k",
     dict(variant="expert_fsdp_mb32", analysis=False)),
]

CELLS["nemo_prefill_opt"] = [
    ("mistral-nemo-12b", "prefill_32k", dict(variant="infer_tp+last_only")),
]

if __name__ == "__main__":
    ap = argparse.ArgumentParser()
    ap.add_argument("--cell", required=True, choices=list(CELLS) + ["all"])
    ap.add_argument("--out", default="reports/hillclimb")
    a = ap.parse_args()
    dr.use_fake_host_devices()
    for c in (CELLS if a.cell == "all" else [a.cell]):
        run(c, a.out)

