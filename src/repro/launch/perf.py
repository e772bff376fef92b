import os
os.environ["JAX_PLATFORMS"] = "cpu"  # models 512 fake CPU devices; never takes a TPU
os.environ.setdefault("XLA_FLAGS", "--xla_force_host_platform_device_count=512")

"""§Perf hillclimbing runner: named variants over the dry-run pipeline.

Each variant re-lowers a (arch, shape) pair with one change and writes a
tagged JSON next to the baseline so `roofline.py --tag <variant>` and the
EXPERIMENTS.md §Perf log can diff before/after.

  python -m repro.launch.perf --arch phi4-mini-3.8b --shape train_4k \
      --variant vmap_stats

Variants:
  donate       train step donates the input state (aliases old/new state)
               [now the default step builder; tag isolates its effect]
  vmap_stats   GradStats via one vmapped backward over the k groups
               (shares FSDP param gathers across groups)
  bf16_state   optimizer moments m/v/p stored in bfloat16 (f32 math)
  bf16_params  master params stored bf16 (dry-run-only what-if)
  cache_tp     decode KV caches shard their sequence dim over the mesh axes
               the batch left unused (flash-decode layout)
  k4 / k16 / k32  paper's k sensitivity at system level (collective cost)
  nofsdp       params replicated over data axis (TP only)
"""
import argparse  # noqa: E402
import dataclasses  # noqa: E402
import json  # noqa: E402

from repro.configs import ARCH_MODULES, INPUT_SHAPES  # noqa: E402
from repro.launch.dryrun import run_one  # noqa: E402


def _opt(cfg, **kw):
    return cfg.replace(optimizer=dataclasses.replace(cfg.optimizer, **kw))


# variant -> (config override, rules kwargs, mesh shape)
VARIANTS = {
    "donate": (None, None),
    "vmap_stats": (lambda c: _opt(c, stats_method="vmap"), None),
    "bf16_state": (lambda c: _opt(c, state_dtype="bfloat16"), None),
    "cache_tp": (None, {"cache_seq_tp": True}),
    "k4": (lambda c: _opt(c, k=4), None),
    "k16": (lambda c: _opt(c, k=16), None),
    "k32": (lambda c: _opt(c, k=32), None),
    "nofsdp": (None, {"fsdp": False}),
    "vmap_bf16": (lambda c: _opt(c, stats_method="vmap", state_dtype="bfloat16"), None),
    "fsdp_pod": (None, {"fsdp_over_pod": True}),
    "amortized": (lambda c: _opt(c, gsnr_refresh=4), None),
    "best_moe": (lambda c: _opt(c, state_dtype="bfloat16"), {"fsdp_over_pod": True}),
    "tp8": (None, None, (32, 8)),
    "tp4": (None, None, (64, 4)),
    "tp32": (None, None, (8, 32)),
}


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", required=True, choices=sorted(ARCH_MODULES))
    ap.add_argument("--shape", required=True, choices=sorted(INPUT_SHAPES))
    ap.add_argument("--variant", required=True, choices=sorted(VARIANTS))
    ap.add_argument("--multi-pod", action="store_true")
    ap.add_argument("--out-dir", default="experiments/dryrun")
    args = ap.parse_args()
    spec = VARIANTS[args.variant]
    overrides, rules_kw = spec[0], spec[1]
    mesh_shape = spec[2] if len(spec) > 2 else None
    rec = run_one(
        args.arch, args.shape, args.multi_pod, args.out_dir,
        overrides=overrides, rules_kw=rules_kw, mesh_shape=mesh_shape,
    )
    if mesh_shape is not None:
        rec["mesh"] = "x".join(map(str, mesh_shape))
    rec["variant"] = args.variant
    mesh_name = rec["mesh"]
    path = os.path.join(
        args.out_dir, f"{args.arch}__{args.shape}__{mesh_name}__{args.variant}.json"
    )
    os.makedirs(args.out_dir, exist_ok=True)
    with open(path, "w") as f:
        json.dump(rec, f, indent=1)
    if rec["ok"]:
        mem = rec["memory"]["peak_device_bytes"] / 2**30
        print(
            f"[{args.variant}] {args.arch} {args.shape} OK compile={rec['compile_s']}s "
            f"peak/dev={mem:.2f}GiB flops={rec['hlo']['flops']:.3e} "
            f"traffic={rec['hlo']['traffic_bytes']:.3e} "
            f"coll={rec['hlo']['total_collective_bytes']:.3e}B"
        )
    else:
        print(f"[{args.variant}] FAIL {rec.get('error')}")


if __name__ == "__main__":
    main()
