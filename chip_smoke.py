"""Smoke check of the VR-LAMB training path on TPU chips.

One chip (the default) runs two phases.  Attention: the fused flash
attention forward and backward at granite-3-2b widths (GQA 32:8, head dim
64; one-pass backward) and granite-20b widths (MQA 48:1, head dim 128;
split dk/dv + dq backward), causal over packed rows at seq 2048 — 16 q and
16 kv blocks — against the jnp replicas ``attention_fwd_ref`` /
``attention_bwd_ref``.  Training: full-width BERT-large (24 layers,
d_model 1024, 16 heads, vocab 30522; random weights from SEED) takes
STEPS VR-LAMB steps through ``make_train_step`` on packed synthetic
batches (``repro.data.packed_lm_batches``), seq 128, global batch 64 in
k=8 microbatches.  It runs first under the default execution plan, which on
a TPU must resolve every subsystem to the fused Pallas kernels compiled by
Mosaic, then — after that state is freed — under
``Backend.all_reference()`` on the same batches.  Every value must be
finite, and the fused and reference results must agree within the printed
tolerances.

``--chips 4`` runs only the data-parallel path: the same model on a
(data=4) mesh with ``gsnr_source="data_axis"`` (k = 4 = the device count)
and the sharded flat update, against the one-device microbatch scan with
k=4 at the same global batch.  It checks that the optimizer state and the
batches span all four devices, and that loss, GSNR mean and update norm
agree.

Both phases compute in float32 with float32 matmuls, so the fused kernels
and the jnp reference differ only in summation order.  The last line of
standard output is one JSON object, printed only when every check passed:
``{"ok": true, "device": {"platform": "tpu", "kind": ..., "count": N}}``.
Without a TPU it exits non-zero before any phase runs.

  python chip_smoke.py
  python chip_smoke.py --chips 4
"""
from __future__ import annotations

import argparse
import dataclasses
import gc
import json
import math
import os
import sys
import time

sys.path.insert(0, os.path.join(os.path.dirname(os.path.abspath(__file__)), "src"))

SEQ = 128
STEPS = 5
# 64 rows = 8192 tokens: the fused step compiles to 5.43 GiB arguments +
# 7.90 GiB temporaries on one 16 GB chip (memory_analysis)
BATCH = 64
SEED = 0
# per step, |a - b| <= rtol * max(|a|, |b|).  Both sides share every input
# and differ only in summation order; each bound is about ten times the
# largest difference read on a TPU v5 lite (PERF.md, PR 11): fused vs
# reference loss 8.9e-8, grad norm 8.4e-7, update norm 2.7e-7; data axis
# vs microbatch scan GSNR mean 2.7e-6
TOLERANCE = {"loss": 1e-6, "grad_norm": 1e-5, "update_norm": 1e-5, "gsnr/mean": 3e-5}
# (label, B, S, H, KV, D): causal, packed; 128-row blocks
ATTENTION = (("granite-3-2b", 2, 2048, 32, 8, 64), ("granite-20b", 1, 2048, 48, 1, 128))
# max |fused - reference| / max |reference| per tensor (out, dq, dk, dv):
# about ten times the largest reading on a TPU v5 lite (PERF.md, PR 11),
# dk 1.9e-6 at granite-20b widths
ATTN_TOLERANCE = 2e-5


def fail(msg: str) -> None:
    print(f"chip_smoke: FAILED: {msg}", file=sys.stderr, flush=True)
    sys.exit(1)


def make_cfg(backend, *, k: int, gsnr_source: str = "microbatch"):
    from repro.configs import get_config

    cfg = get_config("bert-large").replace(global_batch=BATCH, seq_len=SEQ, seed=SEED)
    return cfg.replace(
        optimizer=dataclasses.replace(
            cfg.optimizer, k=k, gsnr_source=gsnr_source, warmup_steps=0,
            total_steps=STEPS),
        parallel=dataclasses.replace(cfg.parallel, backend=backend,
                                     compute_dtype="float32"),
    )


def host_batches(cfg):
    from repro.data import packed_lm_batches

    it = packed_lm_batches(cfg.model.vocab_size, cfg.global_batch, cfg.seq_len, seed=SEED)
    return [next(it) for _ in range(STEPS)]


def run(label, cfg, batches, *, mesh=None, keys=("loss", "grad_norm", "update_norm")):
    """Compile (timed apart) and run one plan; returns per-step metrics."""
    import jax

    from repro.train import init_state, make_train_step

    step_fn, _ = make_train_step(cfg, mesh=mesh, log_gsnr="gsnr/mean" in keys)
    state = init_state(cfg)
    if mesh is not None:
        from jax.sharding import NamedSharding, PartitionSpec as P

        state, batches = place_on_mesh(state, batches, mesh)
        # the next step's state keeps the placement this one was compiled for
        out = (jax.tree_util.tree_map(lambda x: x.sharding, state), NamedSharding(mesh, P()))
        jitted = jax.jit(lambda s, b: step_fn(s, b, True), donate_argnums=0,
                         out_shardings=out)
    else:
        batches = [jax.device_put(b) for b in batches]
        jitted = jax.jit(lambda s, b: step_fn(s, b, True), donate_argnums=0)
    t0 = time.perf_counter()
    compiled = jitted.lower(state, batches[0]).compile()
    compile_s = time.perf_counter() - t0
    ma = compiled.memory_analysis()
    print(f"[{label}] compile {compile_s:.2f} s; compiled memory: arguments "
          f"{ma.argument_size_in_bytes / 2**30:.2f} GiB, temporaries "
          f"{ma.temp_size_in_bytes / 2**30:.2f} GiB", flush=True)
    rows = []
    t0 = time.perf_counter()
    for b in batches:
        state, m = compiled(state, b)
        rows.append({key: float(m[key]) for key in keys})
    steps_s = time.perf_counter() - t0
    for i, r in enumerate(rows):
        print(f"[{label}] step {i} " + " ".join(f"{k}={v:.6f}" for k, v in r.items()))
    print(f"[{label}] {len(rows)} steps in {steps_s:.2f} s (after compile)", flush=True)
    if mesh is not None:
        check_spans(label, state, batches, mesh)
    del state, compiled, batches
    gc.collect()
    return rows


def place_on_mesh(state, batches, mesh):
    import jax
    from jax.sharding import NamedSharding, PartitionSpec as P

    from repro.sharding import activate, param_shardings

    with activate(mesh) as rules:
        state = jax.device_put(state, param_shardings(state, rules))
    data = NamedSharding(mesh, P("data"))
    return state, [jax.device_put(b, data) for b in batches]


def check_spans(label, state, batches, mesh):
    """The optimizer state and the batch really live on every device."""
    devices = set(mesh.devices.flat)
    m = state.opt_state["m"].data
    if m.sharding.device_set != devices or len({s.device for s in m.addressable_shards}) != 4:
        fail(f"{label}: optimizer state m spans {m.sharding.device_set}, not {devices}")
    shard_rows = {s.data.shape[0] for s in m.addressable_shards}
    if shard_rows != {m.shape[0] // 4}:
        fail(f"{label}: m is not split four ways by rows: shard rows {shard_rows}")
    tokens = batches[0]["tokens"]
    if tokens.sharding.device_set != devices or {
            s.data.shape[0] for s in tokens.addressable_shards} != {tokens.shape[0] // 4}:
        fail(f"{label}: the batch does not split across the four devices")
    in_use = bytes_in_use(sorted(devices, key=lambda d: d.id))
    print(f"[{label}] m shards: {len(m.addressable_shards)} x {sorted(shard_rows)[0]} rows; "
          f"bytes_in_use per device (GiB): "
          + ", ".join(f"{b / 2**30:.2f}" for b in in_use), flush=True)
    if min(in_use) < 0.5 * max(in_use):
        fail(f"{label}: device memory is not balanced across the mesh: {in_use}")


def bytes_in_use(devices):
    return [d.memory_stats()["bytes_in_use"] for d in devices]


def compare(name_a, a, name_b, b):
    """Every value finite; a and b within TOLERANCE per step."""
    worst = {}
    for i, (ra, rb) in enumerate(zip(a, b)):
        for key, va in ra.items():
            vb = rb[key]
            if not (math.isfinite(va) and math.isfinite(vb)):
                fail(f"step {i} {key}: non-finite ({name_a} {va}, {name_b} {vb})")
            rel = abs(va - vb) / max(abs(va), abs(vb), 1e-30)
            worst[key] = max(worst.get(key, 0.0), rel)
    for key, rel in worst.items():
        print(f"[compare] {name_a} vs {name_b}: largest relative difference in {key} "
              f"{rel:.3e} (tolerance {TOLERANCE[key]:.0e})", flush=True)
    bad = {k: v for k, v in worst.items() if v > TOLERANCE[k]}
    if bad:
        fail(f"{name_a} and {name_b} disagree beyond tolerance: {bad}")


def peak_gib(device) -> float:
    return device.memory_stats()["peak_bytes_in_use"] / 2**30


def attention(label, b, s, h, kv, d):
    """Fused flash attention fwd + bwd vs the jnp replicas on packed rows."""
    import jax
    import jax.numpy as jnp

    from repro.data import packed_lm_batches
    from repro.kernels import flash_attention_bwd as fab
    from repro.kernels.flash_attention import bhsd, flash_attention, resolve_positions
    from repro.kernels.ref import attention_fwd_ref

    pos = jnp.asarray(next(packed_lm_batches(1000, b, s, seed=SEED))["positions"])
    keys = jax.random.split(jax.random.PRNGKey(SEED), 4)
    q = jax.random.normal(keys[0], (b, s, h, d))
    k, v = (jax.random.normal(x, (b, s, kv, d)) for x in keys[1:3])
    do = jax.random.normal(keys[3], (b, s, h, d))

    def fused(q, k, v, do):
        out, vjp = jax.vjp(lambda *a: flash_attention(*a, pos, pos, causal=True), q, k, v)
        return (out, *vjp(do))

    def reference(q, k, v, do):
        qp, kp, qs, ks = resolve_positions(pos, pos, s, s)
        out, lse = attention_fwd_ref(q, k, v, causal=True, q_pos=qp, k_pos=kp,
                                     q_seg=qs, k_seg=ks)
        delta = jnp.sum(bhsd(do) * bhsd(out), axis=-1, keepdims=True)
        grads = fab.attention_bwd_ref(bhsd(q), bhsd(k), bhsd(v), lse[..., None], delta,
                                      bhsd(do), causal=True, q_pos=qp, k_pos=kp,
                                      q_seg=qs, k_seg=ks)
        return (out, *map(bhsd, grads))

    hb, bq, bk = fab.bwd_blocks(s, s, h, kv, d, 4)
    path = "one-pass" if fab.use_fused_dq(h // kv, -(-s // bq), hb, bq, bk, d, 4) else "split"
    docs = int(jnp.sum(pos == 0))
    got = jax.block_until_ready(jax.jit(fused)(q, k, v, do))
    want = jax.block_until_ready(jax.jit(reference)(q, k, v, do))
    for name, a, w in zip(("out", "dq", "dk", "dv"), got, want):
        if not bool(jnp.all(jnp.isfinite(a))):
            fail(f"attention {label}: fused {name} is not finite")
        rel = float(jnp.max(jnp.abs(a - w)) / jnp.max(jnp.abs(w)))
        print(f"[attention] {label} B={b} S={s} H={h} KV={kv} D={d} ({docs} documents, "
              f"{path} backward): {name} max |fused - ref| / max |ref| {rel:.3e} "
              f"(tolerance {ATTN_TOLERANCE:.0e})", flush=True)
        if not rel <= ATTN_TOLERANCE:
            fail(f"attention {label}: fused {name} disagrees with the reference")
    del got, want
    gc.collect()


def one_chip(device):
    from repro.backend import Backend

    plan = Backend().describe()
    print(f"[plan] default execution plan: {plan}", flush=True)
    if any(plan[s] != "fused" for s in ("optimizer", "stats", "attention")) or plan["interpret"]:
        fail(f"the default plan on this chip is not fully fused and compiled: {plan}")
    for case in ATTENTION:
        attention(*case)
    fused_cfg = make_cfg(Backend(), k=8)
    batches = host_batches(fused_cfg)
    fused = run("fused", fused_cfg, batches)
    print(f"[fused] peak_bytes_in_use {peak_gib(device):.2f} GiB", flush=True)
    ref = run("reference", make_cfg(Backend.all_reference(), k=8), batches)
    print(f"[reference] peak_bytes_in_use (process) {peak_gib(device):.2f} GiB", flush=True)
    compare("fused", fused, "reference", ref)


def four_chips(devices):
    from repro.backend import Backend
    from repro.launch.mesh import make_mesh

    if len(devices) != 4:
        fail(f"--chips 4 needs four devices, found {len(devices)}")
    mesh = make_mesh((4,), ("data",))
    keys = ("loss", "update_norm", "gsnr/mean")
    dp_cfg = make_cfg(Backend(), k=4, gsnr_source="data_axis")
    batches = host_batches(dp_cfg)
    dp = run("data_axis", dp_cfg, batches, mesh=mesh, keys=keys)
    mb = run("microbatch", make_cfg(Backend(), k=4), batches, keys=keys)
    print(f"[microbatch] peak_bytes_in_use (device 0) {peak_gib(devices[0]):.2f} GiB")
    compare("data_axis", dp, "microbatch", mb)


def main() -> None:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1)
    args = ap.parse_args()

    import jax

    from repro.launch.cache import enable_compile_cache

    devices = jax.devices()
    if devices[0].platform != "tpu":
        fail(f"no TPU found: JAX's first device is {devices[0].platform!r}; "
             "this check runs only on the chip")
    print(f"[device] {devices[0].device_kind} x {len(devices)}; compile cache "
          f"{enable_compile_cache()}", flush=True)
    jax.config.update("jax_default_matmul_precision", "float32")
    if args.chips == 1:
        one_chip(devices[0])
    else:
        four_chips(devices)
    print(json.dumps({"ok": True, "device": {
        "platform": devices[0].platform, "kind": devices[0].device_kind,
        "count": len(devices)}}))


if __name__ == "__main__":
    main()
