import os
os.environ["JAX_PLATFORMS"] = "cpu"
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=512"

"""Multi-pod dry-run: lower + compile every (architecture x input shape) on
the production mesh, with memory/cost/collective analysis.

The lines above MUST precede every other import (jax locks the platform and
the device count at first init).  The dry-run models 512 fake CPU devices,
so it pins itself to the CPU: it never takes a TPU another process may
hold.  Do not set the device-count flag globally — smoke tests and
benchmarks must see one device.  Exits non-zero when any combination fails.

Usage:
  python -m repro.launch.dryrun --arch mixtral-8x22b --shape train_4k
  python -m repro.launch.dryrun --all [--multi-pod] [--out-dir experiments/dryrun]
"""
import argparse  # noqa: E402
import json  # noqa: E402
import time  # noqa: E402
import traceback  # noqa: E402

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402

from repro.configs import (  # noqa: E402
    ARCH_MODULES,
    INPUT_SHAPES,
    get_config,
    shape_supported,
    skip_reason,
)
from repro.core import grad_stats, make_optimizer  # noqa: E402
from repro.launch.hlo_analysis import analyze  # noqa: E402
from repro.launch.mesh import make_production_mesh  # noqa: E402
from repro.launch.specs import (  # noqa: E402
    batch_shardings,
    decode_specs,
    prefill_specs,
    train_specs,
)
from repro.models import decode_step, params_shapes, prefill  # noqa: E402
from repro.sharding import activate, param_shardings  # noqa: E402
from repro.train import make_loss_fn  # noqa: E402
from repro.train.train_state import TrainState  # noqa: E402


def count_params(tree) -> int:
    return sum(int(x.size) for x in jax.tree_util.tree_leaves(tree))


def active_params(tree, cfg) -> int:
    """Total minus inactive expert weight (MoE top-k routing)."""
    total = count_params(tree)
    if cfg.model.moe is None:
        return total
    m = cfg.model.moe
    expert = 0
    for path, leaf in jax.tree_util.tree_flatten_with_path(tree)[0]:
        name = "/".join(str(getattr(k, "key", getattr(k, "idx", k))) for k in path)
        if "expert_" in name:
            expert += int(leaf.size)
    return total - int(expert * (1 - m.top_k / m.n_experts))


def build_lowered(cfg, shape, mesh, rules):
    """Returns the jax.stages.Lowered for the right step function."""
    m, pc = cfg.model, cfg.parallel
    psds = params_shapes(m, pc)
    pshard = param_shardings(psds, rules)

    if shape.mode == "train":
        from repro.backend import resolve_backend

        loss_fn = make_loss_fn(cfg)
        bk = resolve_backend(cfg.parallel, where="dryrun")
        # a fused-optimizer plan lowers with FlatBuffer optimizer state —
        # eval_shape sees the packed (rows, 128) buffers — and the shard
        # plan routes the flat pallas_calls per-shard over the FSDP rows
        spmd = bk.shard(mesh, rules)
        opt = make_optimizer(cfg.optimizer, backend=bk, spmd=spmd)
        opt_sds = jax.eval_shape(opt.init, psds)
        opt_shard = param_shardings(opt_sds, rules)
        batch_sds = train_specs(cfg, shape)
        bshard = batch_shardings(batch_sds, rules, shape.global_batch)
        k = cfg.optimizer.k

        def step(state, batch):
            loss, aux, stats = grad_stats(
                loss_fn, state.params, batch, k, has_aux=True, backend=bk, spmd=spmd,
            )
            upd, opt_state = opt.update(stats.mean, state.opt_state, state.params, stats=stats)
            params = jax.tree_util.tree_map(lambda p, u: (p + u).astype(p.dtype), state.params, upd)
            return TrainState(params, opt_state, opt_state["step"]), loss

        state_sds = TrainState(psds, opt_sds, jax.ShapeDtypeStruct((), jnp.int32))
        state_shard = TrainState(
            pshard, opt_shard, jax.sharding.NamedSharding(mesh, jax.sharding.PartitionSpec())
        )
        # donate the input state: new state aliases old (halves train memory)
        return jax.jit(
            step, in_shardings=(state_shard, bshard), donate_argnums=(0,)
        ).lower(state_sds, batch_sds)

    if shape.mode == "prefill":
        batch_sds = prefill_specs(cfg, shape)
        bshard = batch_shardings(batch_sds, rules, shape.global_batch)

        def step(params, batch):
            extra = {k_: v for k_, v in batch.items() if k_ != "tokens"}
            return prefill(
                m, pc, params, batch["tokens"], extra=extra or None, cache_len=shape.seq_len
            )

        return jax.jit(step, in_shardings=(pshard, bshard)).lower(psds, batch_sds)

    # decode
    token, positions, cache = decode_specs(cfg, shape)
    cshard = batch_shardings(cache, rules, shape.global_batch, kind="cache")
    tshard = batch_shardings({"t": token, "p": positions}, rules, shape.global_batch)

    def step(params, cache, tok, pos):
        return decode_step(m, pc, params, cache, tok, pos)

    return jax.jit(
        step, in_shardings=(pshard, cshard, tshard["t"], tshard["p"]), donate_argnums=(1,)
    ).lower(psds, cache, token, positions)


def run_one(arch: str, shape_name: str, multi_pod: bool, out_dir: str, save_hlo: bool = False,
            overrides=None, rules_kw=None, mesh_shape=None) -> dict:
    shape = INPUT_SHAPES[shape_name]
    mesh_name = "2x16x16" if multi_pod else "16x16"
    rec = {
        "arch": arch,
        "shape": shape_name,
        "mesh": mesh_name,
        "mode": shape.mode,
        "ok": False,
    }
    if not shape_supported(arch, shape_name):
        rec["skipped"] = skip_reason(arch, shape_name)
        return rec
    try:
        cfg = get_config(arch).replace(global_batch=shape.global_batch, seq_len=shape.seq_len)
        if overrides:
            cfg = overrides(cfg)
        if mesh_shape is not None:
            from repro.launch.mesh import make_mesh

            axes = ("pod", "data", "model")[-len(mesh_shape):]
            mesh = make_mesh(mesh_shape, axes)
        else:
            mesh = make_production_mesh(multi_pod=multi_pod)
        with activate(mesh, **(rules_kw or {})) as rules:
            t0 = time.time()
            lowered = build_lowered(cfg, shape, mesh, rules)
            rec["lower_s"] = round(time.time() - t0, 2)
            t0 = time.time()
            compiled = lowered.compile()
            rec["compile_s"] = round(time.time() - t0, 2)

        ma = compiled.memory_analysis()
        rec["memory"] = {
            "argument_bytes": ma.argument_size_in_bytes,
            "output_bytes": ma.output_size_in_bytes,
            "temp_bytes": ma.temp_size_in_bytes,
            "alias_bytes": ma.alias_size_in_bytes,
            "peak_device_bytes": ma.argument_size_in_bytes
            + ma.output_size_in_bytes
            + ma.temp_size_in_bytes
            - ma.alias_size_in_bytes,
        }
        ca = compiled.cost_analysis()
        rec["cost_raw"] = {
            "flops": ca.get("flops", 0.0),
            "bytes_accessed": ca.get("bytes accessed", 0.0),
        }
        txt = compiled.as_text()
        rec["hlo"] = analyze(txt)
        rec["hlo"].pop("entry", None)
        psds = params_shapes(cfg.model, cfg.parallel)
        rec["params_total"] = count_params(psds)
        rec["params_active"] = active_params(psds, cfg)
        rec["n_chips"] = 512 if multi_pod else 256
        rec["tokens"] = shape.global_batch * (shape.seq_len if shape.mode != "decode" else 1)
        rec["ok"] = True
        if save_hlo:
            with open(os.path.join(out_dir, f"{arch}__{shape_name}__{mesh_name}.hlo.txt"), "w") as f:
                f.write(txt)
    except Exception as e:  # noqa: BLE001 — a dry-run failure is a finding, not a crash
        rec["error"] = f"{type(e).__name__}: {e}"
        rec["traceback"] = traceback.format_exc()[-2000:]
    return rec


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default=None, choices=sorted(ARCH_MODULES))
    ap.add_argument("--shape", default=None, choices=sorted(INPUT_SHAPES))
    ap.add_argument("--all", action="store_true")
    ap.add_argument("--multi-pod", action="store_true")
    ap.add_argument("--out-dir", default="experiments/dryrun")
    ap.add_argument("--save-hlo", action="store_true")
    ap.add_argument("--tag", default="")
    args = ap.parse_args()
    os.makedirs(args.out_dir, exist_ok=True)

    combos = []
    if args.all:
        for a in ARCH_MODULES:
            for s in INPUT_SHAPES:
                combos.append((a, s))
    else:
        if not (args.arch and args.shape):
            ap.error("--arch and --shape required unless --all")
        combos = [(args.arch, args.shape)]

    failed = []
    for arch, shape in combos:
        rec = run_one(arch, shape, args.multi_pod, args.out_dir, args.save_hlo)
        mesh_name = rec["mesh"]
        tag = f"__{args.tag}" if args.tag else ""
        path = os.path.join(args.out_dir, f"{arch}__{shape}__{mesh_name}{tag}.json")
        with open(path, "w") as f:
            json.dump(rec, f, indent=1)
        if rec.get("skipped"):
            status = f"SKIP ({rec['skipped']})"
        elif rec["ok"]:
            mem = rec["memory"]["peak_device_bytes"] / 2**30
            status = (
                f"OK lower={rec['lower_s']}s compile={rec['compile_s']}s "
                f"peak/dev={mem:.2f}GiB flops/dev={rec['hlo']['flops']:.3e} "
                f"coll={rec['hlo']['total_collective_bytes']:.3e}B"
            )
        else:
            status = f"FAIL {rec['error']}"
            failed.append(f"{arch}/{shape}")
        print(f"[{mesh_name}] {arch:28s} {shape:12s} {status}", flush=True)
    if failed:
        raise SystemExit(f"dry-run failed for {len(failed)} combination(s): {failed}")


if __name__ == "__main__":
    main()
