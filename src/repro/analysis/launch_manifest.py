"""Declarative launch-count manifest: compiled fn -> expected pallas_call
count, consumed by BOTH the tests (tests/test_layout.py, test_backend.py,
test_spmd_flat.py assert against these names instead of scattered literals)
and the analyzer (rules.LAUNCH-COUNT traces the cheap entries and compares).

When the next kernel fusion changes a count, update THIS table — the tests
and the analyzer follow.  Keys group by surface:

  flat_update            one fused optimizer launch per VRGD step
  grad_stats_scan        scan accum + finalize
  attention_*            custom-VJP structure: 1 primal, 2 under jax.grad
                         (LSE-emitting fwd + fused one-pass dq/dk/dv bwd)
  model_forward_*        attention dispatch under a Backend plan
  train_step_*           end-to-end composites (tests only: tracing a full
                         train step is seconds-to-minutes, not a check gate)
  spmd_*                 per-shard path (subprocess tests, fake devices)

``traced_counts`` measures the TRACED subset by building the real jaxprs
(jax.make_jaxpr, no execution) and counting pallas_call equations with
kernels/ops.count_pallas_calls — the same structural counter the tests use.
"""
from __future__ import annotations

from typing import Dict, List

LAUNCHES: Dict[str, int] = {
    # gathered flat-buffer optimizer update
    "flat_update": 1,
    # gradient-moment accumulation
    "grad_stats_scan": 2,
    # flash-attention custom VJP
    "attention_primal": 1,
    "attention_grad": 2,
    # attention dispatch through the Backend execution plan
    "model_forward_fused": 1,
    "model_forward_reference": 0,
    # end-to-end composites (consumed by tests only)
    "train_step_fused": 6,   # attn fwd + remat LSE fwd + fused bwd + 2 stats + update
    "train_step_packed": 6,  # packed positions ride the same calls as operands
    # dynamic-k autoscale path: the noise-scale readings (core/noise_scale.py)
    # are jnp reductions over the already-materialized moment carry, so a
    # noise_scale=True step launches EXACTLY what train_step_fused does —
    # at every k the autoscale loop compiles (asserted per-k in
    # tests/test_autoscale.py)
    "train_step_noise": 6,
    # SPMD per-shard flat path (shard_map; subprocess tests)
    "spmd_update": 2,  # r-partials + apply, per shard
    "spmd_grad_stats_scan": 2,
    "spmd_train_step": 7,  # train_step_fused with the update split in two
}

# The subset the analyzer traces itself (cheap jaxprs, a few seconds total).
TRACED = ("flat_update", "grad_stats_scan", "attention_primal", "attention_grad")


def _count(fn, *args) -> int:
    import jax

    from repro.kernels.ops import count_pallas_calls

    return count_pallas_calls(jax.make_jaxpr(fn)(*args))


def traced_counts() -> Dict[str, int]:
    """Measured pallas_call counts for every TRACED entry."""
    import jax
    import jax.numpy as jnp

    from repro.analysis.registry import demo_tree
    from repro.backend import Backend
    from repro.configs.base import OptimizerConfig
    from repro.core import GradStats, grad_stats, make_optimizer

    tm = jax.tree_util.tree_map
    counts: Dict[str, int] = {}

    # optimizer update: one fused launch
    params = tm(jnp.asarray, demo_tree("hostile"))
    g = tm(lambda x: x + 0.01, params)
    stats = GradStats(mean=g, sq_mean=tm(lambda x: x * x + 1e-3, g), k=8)
    cfg = OptimizerConfig(name="vr_lamb", lr=0.01, schedule="constant",
                          weight_decay=0.01)
    opt = make_optimizer(cfg, backend=Backend.all_fused())
    state = opt.init(params)
    counts["flat_update"] = _count(
        lambda s: opt.update(g, s, params, stats=stats), state)

    # grad stats: scan accum + finalize
    lin = {"w": jnp.ones(300), "b": jnp.zeros(())}

    def loss_fn(p, b):
        x, y = b
        return jnp.mean((x @ p["w"] + p["b"] - y) ** 2)

    batch = (jnp.ones((16, 300)), jnp.ones((16,)))
    counts["grad_stats_scan"] = _count(
        lambda p, b: grad_stats(loss_fn, p, b, 4, backend=Backend.all_fused())[2],
        lin, batch)

    # attention custom VJP: primal vs jax.grad structure
    from repro.kernels.flash_attention import flash_attention

    ks = jax.random.split(jax.random.PRNGKey(0), 3)
    q = jax.random.normal(ks[0], (1, 130, 4, 32))
    k = jax.random.normal(ks[1], (1, 130, 2, 32))
    v = jax.random.normal(ks[2], (1, 130, 2, 32))
    counts["attention_primal"] = _count(lambda *a: flash_attention(*a), q, k, v)
    counts["attention_grad"] = _count(
        jax.grad(lambda *a: jnp.sum(flash_attention(*a)), argnums=(0, 1, 2)), q, k, v)
    return counts


def check_launches() -> List:
    """LAUNCH-COUNT findings for every traced entry that disagrees."""
    from repro.analysis.rules import Finding

    got = traced_counts()
    return [
        Finding("LAUNCH-COUNT", name, "traced",
                f"counted {n} pallas_call(s), manifest expects {LAUNCHES[name]}")
        for name, n in got.items() if n != LAUNCHES[name]
    ]
