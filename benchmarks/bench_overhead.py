"""VRGD systems cost (not a paper table; the deployment question the paper
leaves implicit): step-time overhead of GSNR statistics + the fused-kernel
win on the moment accumulation.

  a) trainer overhead: base optimizer vs VR at equal k-microbatch structure
     (isolates the Σg² accumulation + GSNR pipeline cost),
  b) update-math microbench: the jnp GSNR pipeline,
  c) accumulation microbench: the paper scan body's two jnp moment tree
     passes vs the fused Pallas sweep (kernels/flat_stats.py), end to end
     through grad_stats under a fused-stats Backend plan, reporting the
     fused/unfused delta.
  d) packed vs unpacked fused attention, fwd and grad, with launch counts;
  e) structural cost model (benchmarks/cost_model.py): per-step block
     visits, HBM bytes, and MXU FLOPs counted by replaying the kernels'
     real grid specs and index maps, with the superseded geometries
     (split backward, identity fetch, phase-blind updates) as baselines —
     hardware-independent, and gated on the claimed reductions.

d) and e) are written machine-readable to BENCH_flat_state.json.

Every machine-readable record carries the fully-resolved backend ``plan``
(Backend.describe(): per-subsystem fused/reference + interpret + platform)
and its measurement ``config``; merging records with disagreeing plans or
key-wise conflicting configs is refused (benchmarks/common.py) — TPU fused
numbers can never silently mix with CPU-interpret ones, nor an S=256 sweep
with an S=512 cost record.
"""
from __future__ import annotations

import json
import os
import time

import jax
import jax.numpy as jnp

from benchmarks.common import emit, merge_bench_records
from repro.backend import Backend
from repro.configs import get_smoke
from repro.core import GradStats, gsnr_scale
from repro.data import lm_batches
from repro.train import init_state, make_loss_fn, make_train_step


def timed(fn, *args, warmup=2, iters=8):
    for _ in range(warmup):
        jax.block_until_ready(fn(*args))
    t0 = time.time()
    for _ in range(iters):
        out = jax.block_until_ready(fn(*args))
    return (time.time() - t0) / iters, out


def trainer_overhead(fast: bool) -> None:
    cfg0 = get_smoke("granite-3-2b").replace(global_batch=16, seq_len=64)
    stream = lm_batches(cfg0.model.vocab_size, 16, 64, seed=0)
    batch = next(iter(stream))
    times = {}
    import dataclasses

    for name in ("adam", "vr_adam"):
        cfg = cfg0.replace(optimizer=dataclasses.replace(cfg0.optimizer, name=name, k=8))
        state = init_state(cfg)
        step_fn, _ = make_train_step(cfg, make_loss_fn(cfg))
        jstep = jax.jit(step_fn)
        dt, _ = timed(lambda s=state, b=batch, f=jstep: f(s, b), iters=4)
        times[name] = dt
        emit(f"overhead_step_{name}", dt * 1e6, f"k=8")
    emit(
        "overhead_vr_ratio",
        0.0,
        f"vr/base={times['vr_adam']/times['adam']:.3f}",
    )


def update_math(fast: bool) -> None:
    n = 1 << 20 if not fast else 1 << 18
    key = jax.random.PRNGKey(0)
    g = jax.random.normal(key, (n,)) * 0.1
    g2 = jnp.square(g) + jax.random.uniform(jax.random.fold_in(key, 1), (n,)) * 0.01
    stats = GradStats(mean={"w": g}, sq_mean={"w": g2}, k=8)

    @jax.jit
    def jnp_path(stats):
        r = gsnr_scale(stats, 0.1)
        return jax.tree_util.tree_map(lambda r_, g_: r_ * g_, r, stats.mean)

    dt_j, _ = timed(jnp_path, stats)
    emit("update_math_jnp", dt_j * 1e6, f"n={n}")


def accumulation(fast: bool) -> None:
    """Fused vs unfused k-group moment accumulation (the scan-body Σg/Σg²).

    Runs the same grad_stats call both ways so the delta isolates the
    accumulation sweeps.  Interpret mode on CPU: the absolute Pallas number
    carries interpreter overhead — the structural check is that the fused
    path produces identical statistics in a single sweep per leaf (the
    HBM-pass win is a TPU measurement).
    """
    from repro.core import grad_stats

    n = 1 << 12 if fast else 1 << 14
    k = 8
    key = jax.random.PRNGKey(0)
    X = jax.random.normal(key, (32, n))
    Y = X @ jax.random.normal(jax.random.fold_in(key, 1), (n,))
    params = {"w": jnp.zeros((n,)), "b": jnp.zeros(())}

    def loss_fn(p, b):
        x, y = b
        return jnp.mean((x @ p["w"] + p["b"] - y) ** 2)

    times = {}
    for pallas in (False, True):
        plan = Backend.all_fused() if pallas else Backend.all_reference()
        fn = jax.jit(
            lambda p, b, bk=plan: grad_stats(loss_fn, p, b, k, backend=bk)[2]
        )
        dt, stats = timed(fn, params, (X, Y), iters=4)
        times[pallas] = dt
        emit(
            f"accum_{'fused' if pallas else 'unfused'}",
            dt * 1e6,
            f"n={n};k={k}" + (";note=CPU-interpret" if pallas else ""),
        )
    emit(
        "accum_fused_ratio",
        0.0,
        f"fused/unfused={times[True]/times[False]:.3f} (TPU is the real number)",
    )


def packed_attention(fast: bool) -> dict:
    """Packed (explicit positions + segments) vs unpacked (implicit arange)
    fused attention, fwd + grad.

    Both run the SAME kernels since the position-aware refactor — the delta
    isolates the cost of the pos/seg operands (4 extra int32 row streams +
    the in-kernel bound reductions) against the dead-tile skips they enable
    (a packed row's cross-document and padded-tail tiles are pl.when-dead).
    CPU interpret mode: latencies are structural only (the interpreter runs
    dead tiles' pl.when scaffolding too); launch counts and the TPU rerun
    are the real story.
    """
    from repro.kernels.flash_attention import flash_attention
    from repro.kernels.ops import count_pallas_calls

    b, s, h, kvh, d = (1, 256, 4, 2, 32) if fast else (2, 512, 8, 2, 64)
    key = jax.random.PRNGKey(0)
    ks = jax.random.split(key, 3)
    q = jax.random.normal(ks[0], (b, s, h, d))
    k = jax.random.normal(ks[1], (b, s, kvh, d))
    v = jax.random.normal(ks[2], (b, s, kvh, d))
    # 3 documents per row, boundaries off the block grid, short padded tail
    import numpy as np

    lens = (s // 2, s // 3, s - s // 2 - s // 3 - s // 16)
    pos_row = np.full(s, -1, np.int32)
    o = 0
    for n in lens:
        pos_row[o : o + n] = np.arange(n)
        o += n
    pos = jnp.asarray(pos_row)[None, :].repeat(b, 0)

    variants = {
        "unpacked": lambda q_: flash_attention(q_, k, v, causal=True),
        "packed": lambda q_: flash_attention(q_, k, v, pos, pos, causal=True),
    }
    rec = {}
    iters = 2 if fast else 4
    for name, fn in variants.items():
        fwd = jax.jit(fn)
        grad = jax.jit(jax.grad(lambda q_: jnp.sum(fn(q_))))
        n_fwd = count_pallas_calls(jax.make_jaxpr(fwd)(q))
        n_grad = count_pallas_calls(jax.make_jaxpr(grad)(q))
        dt_f, _ = timed(fwd, q, warmup=1, iters=iters)
        dt_g, _ = timed(grad, q, warmup=1, iters=iters)
        emit(f"attn_{name}_fwd", dt_f * 1e6, f"S={s};launches={n_fwd};note=CPU-interpret")
        emit(f"attn_{name}_grad", dt_g * 1e6, f"S={s};launches={n_grad};note=CPU-interpret")
        rec[name] = {
            "fwd_launches": n_fwd, "grad_launches": n_grad,
            "fwd_us": dt_f * 1e6, "grad_us": dt_g * 1e6,
        }
    plan = Backend.all_fused()
    return {
        "shape": {"B": b, "S": s, "H": h, "KV": kvh, "D": d, "docs": list(lens)},
        # the keys shared with cost_model's config.attn must agree key-wise
        # (check_configs_agree) — the structural counts describe THIS shape
        "config": {"attn": {"B": b, "S": s, "H": h, "KV": kvh, "D": d,
                            "docs": list(lens)}},
        "plan": plan.describe(),
        "interpret": plan.interpret_mode(),
        "backend": jax.default_backend(),
        **rec,
        "note": "packed == explicit pos/seg operands; launch counts must match unpacked",
    }


def main(fast: bool = False) -> None:
    t0 = time.time()
    trainer_overhead(fast)
    update_math(fast)
    accumulation(fast)
    from benchmarks.cost_model import compute as cost_compute

    # merge refuses sub-records whose resolved plans disagree or whose
    # measurement configs conflict key-wise (common.py); cost_compute also
    # gates the PR's claimed structural reductions (cost_model.check_claims)
    rec = merge_bench_records(
        {},
        packed_attention=packed_attention(fast),
        cost_model=cost_compute(fast=fast),
    )
    out = os.path.join(os.path.dirname(__file__), "..", "BENCH_flat_state.json")
    with open(out, "w") as f:
        json.dump(rec, f, indent=1)
    print(f"# wrote {os.path.abspath(out)}")
    print(f"# bench_overhead done in {time.time()-t0:.1f}s")


if __name__ == "__main__":
    main()
