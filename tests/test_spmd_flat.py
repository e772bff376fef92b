"""Flat-buffer kernels under SPMD: the Backend.shard(mesh, rules) plan runs
the flat-update / flat-stats pallas_calls per-shard (shard_map over the
FSDP-sharded rows dimension) instead of gathering the whole buffer.

Needs >1 device, so the checks run in subprocesses with
XLA_FLAGS=--xla_force_host_platform_device_count=8 (the main test process
must keep seeing 1 device), mirroring tests/test_distributed.py.

Assertions (ISSUE 5 acceptance, remainder coverage from ISSUE 6):
  * differential vs the gathered oracle — BITWISE when no leaf straddles a
    shard boundary (zero partials from other shards add exactly; VR-LARS is
    within 1 ulp because its trust*||w|| epilogue multiply may fuse
    differently), tight allclose on a hostile straddling layout;
  * launch counts: a sharded update is exactly 2 pallas_calls (partials +
    apply; the trust-ratio epilogue is jnp), sharded scan stats stay 2
    (accum + finalize), and the end-to-end sharded fused train step is 8
    (4 attention + 2 stats + 2 update) vs the gathered 7;
  * block counts that do NOT divide the shard count no longer fall back to
    the gathered path: FlatSpmd pads the rows dimension with zero blocks
    internally (exact-zero psum contributions), so supports() is True and
    the update still runs as 2 per-shard launches, allclose vs gathered —
    including the 195-block smoke model on an 8-device mesh end to end.
"""
import os
import subprocess
import sys

import pytest


def _run(script: str) -> None:
    env = dict(os.environ)
    env["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
    env["PYTHONPATH"] = os.pathsep.join(
        [os.path.join(os.path.dirname(__file__), "..", "src"), os.path.dirname(__file__)]
    )
    out = subprocess.run(
        [sys.executable, "-c", script], env=env, capture_output=True, text=True, timeout=600
    )
    assert out.returncode == 0, (out.stdout[-2000:], out.stderr[-4000:])
    assert "OK" in out.stdout


OPS_SCRIPT = r"""
import jax, jax.numpy as jnp, numpy as np
from repro.backend import Backend
from repro.configs.base import OptimizerConfig
from repro.core import grad_stats, make_optimizer
from repro.core.gsnr import GradStats
from repro.core.layout import ParamLayout
from repro.analysis.launch_manifest import LAUNCHES
from repro.kernels.ops import count_pallas_calls
from repro.launch.mesh import make_mesh
from repro.sharding.rules import Rules
import oracle

tm = jax.tree_util.tree_map
mesh = make_mesh((8,), ("data",))
bk = Backend.all_fused()
plan = bk.shard(mesh, Rules(mesh=mesh))

def updates(params, spmd):
    g = tm(lambda x: x * 0.01, params)
    stats = GradStats(mean=g, sq_mean=tm(lambda x: jnp.square(x) + 1e-3, g), k=8)
    out = {}
    for name in ("vr_sgd", "vr_momentum", "vr_adam", "vr_lars", "vr_lamb"):
        cfg = OptimizerConfig(name=name, lr=0.01, schedule="constant", weight_decay=0.01)
        opt = make_optimizer(cfg, backend=bk, spmd=spmd)
        state = opt.init(params)
        fn = lambda s: opt.update(g, s, params, stats=stats)
        out[name] = (jax.jit(fn)(state)[0], count_pallas_calls(jax.make_jaxpr(fn)(state)))
    return out

# --- leaf-aligned layout: one 64-row block per leaf, 8 leaves on 8 shards —
# shard boundaries never split a leaf, so sharded == gathered BIT FOR BIT
key = jax.random.PRNGKey(0)
aligned = {f"w{i}": jax.random.normal(jax.random.fold_in(key, i), (64, 128)) * 0.5
           for i in range(8)}
assert plan.supports(ParamLayout.for_tree(aligned))
got = updates(aligned, plan)
want = updates(aligned, None)
for name in got:
    u_s, n_s = got[name]; u_g, n_g = want[name]
    assert n_g == LAUNCHES["flat_update"], (name, n_g)
    assert n_s == LAUNCHES["spmd_update"], (name, n_s)  # partials + apply, per shard
    for a, b in zip(jax.tree_util.tree_leaves(u_s), jax.tree_util.tree_leaves(u_g)):
        if name == "vr_lars":  # trust*||w|| epilogue: fusion-order 1-ulp
            np.testing.assert_allclose(np.asarray(a), np.asarray(b), rtol=1e-6, atol=1e-10)
        else:
            np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
print("aligned bitwise ok")

# --- hostile layout (ragged leaves straddling shard boundaries, block count
# NOT divisible by 8 — the internal zero-block padding covers the remainder):
# the per-leaf scalar psum reassociates one add per straddle, so tight
# allclose instead of bitwise
params = oracle.hostile_params()
assert plan.supports(ParamLayout.for_tree(params))
got = updates(params, plan)
want = updates(params, None)
for name in got:
    for a, b in zip(jax.tree_util.tree_leaves(got[name][0]),
                    jax.tree_util.tree_leaves(want[name][0])):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), rtol=3e-6, atol=1e-8)
print("hostile allclose ok")

# --- non-divisible layout runs SHARDED now (remainder rows padded with zero
# blocks inside FlatSpmd): still the 2-launch per-shard pipeline, allclose
# vs gathered (the single leaf straddles every shard boundary)
bad = {"w": jnp.linspace(-1.0, 1.0, 64 * 9 * 128).reshape(64 * 9, 128)}  # 9 blocks % 8 != 0
assert plan.supports(ParamLayout.for_tree(bad))
got = updates(bad, plan)
want = updates(bad, None)
for name in got:
    u_s, n_s = got[name]; u_g, n_g = want[name]
    assert n_g == LAUNCHES["flat_update"], (name, n_g)
    assert n_s == LAUNCHES["spmd_update"], (name, n_s)  # remainder path is NOT a gathered fallback
    for a, b in zip(jax.tree_util.tree_leaves(u_s), jax.tree_util.tree_leaves(u_g)):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), rtol=3e-6, atol=1e-8)
print("remainder sharded ok")

# --- sharded stats sweeps, kernel level: identical inputs in, BITWISE out
# (element-wise kernels on local row slices, no collective)
from repro.kernels import ops as kops

layout2 = ParamLayout.for_tree(aligned)
key2 = jax.random.PRNGKey(7)
gs = jax.random.normal(key2, (layout2.n_rows, 128))
g2s = jnp.square(gs) * 0.5
gtree = tm(lambda x: x * 0.01, aligned)
a_g = jax.jit(lambda a, b, c: kops.moments_accum_flat(a, b, c, layout2))(gs, g2s, gtree)
a_s = jax.jit(lambda a, b, c: kops.moments_accum_flat(a, b, c, layout2, spmd=plan))(gs, g2s, gtree)
for x, y in zip(a_g, a_s):
    np.testing.assert_array_equal(np.asarray(x), np.asarray(y))
f_g = jax.jit(lambda a, b: kops.moments_finalize_flat(a, b, 4, layout2))(gs, g2s)
f_s = jax.jit(lambda a, b: kops.moments_finalize_flat(a, b, 4, layout2, spmd=plan))(gs, g2s)
np.testing.assert_array_equal(np.asarray(f_g.mean.data), np.asarray(f_s.mean.data))
np.testing.assert_array_equal(np.asarray(f_g.sq_mean.data), np.asarray(f_s.sq_mean.data))
print("sharded stats kernels bitwise ok")

# --- grad_stats end to end under the plan: launch counts + tight allclose
# (the two jit programs may fuse the BACKWARD matmul differently, so the
# gradient itself reassociates ~1 ulp — kernel exactness is asserted above)
def loss_fn(p, b):
    x, y = b
    return jnp.mean((x @ p["w"] - y) ** 2)

n = 8 * 64 * 128
params2 = {"w": jnp.linspace(-1.0, 1.0, n)}
assert plan.supports(ParamLayout.for_tree(params2))
X = jax.random.normal(jax.random.PRNGKey(1), (16, n)) * 0.05
Y = jnp.tanh(X @ jnp.linspace(0.3, -0.3, n))
s_g = jax.jit(lambda p, b: grad_stats(loss_fn, p, b, 4, backend=bk)[2])(params2, (X, Y))
s_s = jax.jit(lambda p, b: grad_stats(loss_fn, p, b, 4, backend=bk, spmd=plan)[2])(params2, (X, Y))
np.testing.assert_allclose(np.asarray(s_g.mean.data), np.asarray(s_s.mean.data),
                           rtol=1e-5, atol=2e-6)
np.testing.assert_allclose(np.asarray(s_g.sq_mean.data), np.asarray(s_s.sq_mean.data),
                           rtol=1e-5, atol=2e-6)
n_calls = count_pallas_calls(jax.make_jaxpr(
    lambda p, b: grad_stats(loss_fn, p, b, 4, backend=bk, spmd=plan)[2])(params2, (X, Y)))
assert n_calls == LAUNCHES["spmd_grad_stats_scan"], n_calls  # scan-body accum + finalize, sharded
print("sharded grad_stats ok")
print("OK")
"""


SWEEP_SCRIPT = r"""
import jax, jax.numpy as jnp, numpy as np
from repro.backend import Backend
from repro.core.layout import ParamLayout
from repro.kernels import flat_stats as fs
from repro.kernels import ops as kops
from repro.kernels import ref
from repro.launch.mesh import make_mesh
from repro.sharding.rules import Rules

mesh = make_mesh((4,), ("data",))
plan = Backend.all_fused().shard(mesh, Rules(mesh=mesh))
# (blocks, shard rows): 320 rows a shard, fewer than a tile and not a
# multiple of it; 2,368 a shard, a whole tile and a ragged 320; 150 blocks
# pad to 152 (2,432 a shard: a tile and a ragged 384)
for blocks, shard_rows in ((20, 320), (148, 2368), (150, 2432)):
    tree = {"w": jax.random.normal(jax.random.PRNGKey(blocks), (blocks * 64, 128))}
    layout = ParamLayout.for_tree(tree)
    assert (layout.n_rows + plan._pad_rows(layout)) // 4 == shard_rows
    assert fs.sweep_rows(shard_rows, fs.STREAMS["flat_moments_accum"]) == min(shard_rows, 2048)
    gs = jax.random.normal(jax.random.PRNGKey(1), (layout.n_rows, 128))
    g2s = jnp.square(gs) * 0.5
    grads = jax.tree_util.tree_map(lambda x: x * 0.01, tree)
    a_s = jax.jit(lambda a, b, c: kops.moments_accum_flat(a, b, c, layout, spmd=plan))(gs, g2s, grads)
    a_g = jax.jit(lambda a, b, c: kops.moments_accum_flat(a, b, c, layout))(gs, g2s, grads)
    want = jax.jit(ref.moments_accum_ref)(gs, g2s, layout.pack(grads))
    for x, y, z in zip(a_s, a_g, want):
        np.testing.assert_array_equal(np.asarray(x), np.asarray(y))
        np.testing.assert_array_equal(np.asarray(x), np.asarray(z))
    f_s = jax.jit(lambda a, b: kops.moments_finalize_flat(a, b, 4, layout, spmd=plan))(*a_s)
    f_g = jax.jit(lambda a, b: kops.moments_finalize_flat(a, b, 4, layout))(*a_g)
    np.testing.assert_array_equal(np.asarray(f_s.mean.data), np.asarray(f_g.mean.data))
    np.testing.assert_array_equal(np.asarray(f_s.sq_mean.data), np.asarray(f_g.sq_mean.data))
    print("sweep", blocks, shard_rows, "bitwise ok")
print("OK")
"""


TRAINER_SCRIPT = r"""
import dataclasses
import jax, jax.numpy as jnp, numpy as np
from repro.backend import Backend
from repro.configs import get_smoke
from repro.data import lm_batches
from repro.analysis.launch_manifest import LAUNCHES
from repro.kernels.ops import count_pallas_calls
from repro.launch.mesh import make_mesh
from repro.sharding.rules import Rules, activate
from repro.train import init_state, make_loss_fn, make_train_step

# the smoke transformer packs to 195 blocks — NOT divisible by this 8-device
# mesh, so the END-TO-END fused train step exercises the remainder-padding
# path for its per-shard stats and update (the ISSUE 6 carry-over case)
mesh = make_mesh((8,), ("data",))
cfg = get_smoke("granite-3-2b").replace(global_batch=16, seq_len=16)
cfg = cfg.replace(
    optimizer=dataclasses.replace(cfg.optimizer, name="vr_lamb", k=4),
    parallel=dataclasses.replace(
        cfg.parallel, backend=Backend.all_fused(), compute_dtype="float32"),
)
batch = next(iter(lm_batches(cfg.model.vocab_size, 16, 16, seed=0)))
state = init_state(cfg)
plan = Backend.all_fused().shard(mesh, Rules(mesh=mesh))
assert plan.supports(state.opt_state["m"].layout)

step_ref, _ = make_train_step(cfg, make_loss_fn(cfg))
with activate(mesh):
    step_spmd, _ = make_train_step(cfg, make_loss_fn(cfg), mesh=mesh)
s1, m1 = jax.jit(step_ref)(state, batch)
s2, m2 = jax.jit(step_spmd)(state, batch)
assert float(m1["loss"]) == float(m2["loss"])  # forward untouched by the plan
for a, b in zip(jax.tree_util.tree_leaves(s1.params), jax.tree_util.tree_leaves(s2.params)):
    np.testing.assert_allclose(
        np.asarray(a, np.float32), np.asarray(b, np.float32), rtol=1e-4, atol=1e-5)
# gathered fused step is 6 launches (fused one-pass attention backward);
# sharding splits stats(2)+update(1) into per-shard stats(2) +
# update(partials+apply = 2): 7 total
assert count_pallas_calls(jax.make_jaxpr(step_ref)(state, batch)) == LAUNCHES["train_step_fused"]
assert count_pallas_calls(jax.make_jaxpr(step_spmd)(state, batch)) == LAUNCHES["spmd_train_step"]
print("OK")
"""


@pytest.mark.slow
def test_spmd_flat_ops_match_gathered_oracle_subprocess():
    """Sharded optimizer updates / stats sweeps vs the gathered single-launch
    oracle on an 8-device CPU mesh: bitwise on leaf-aligned layouts, tight
    allclose on straddling ones (including non-divisible block counts via
    the internal remainder padding), launch counts pinned."""
    _run(OPS_SCRIPT)


def test_spmd_stats_sweeps_tile_each_shard_subprocess():
    """The statistics sweeps per shard of a 4-device mesh tile each shard's
    own rows: a shard smaller than one tile, one a tile and a ragged rest,
    and one padded to a whole number of layout blocks; accumulate and
    finalize are bitwise equal to the gathered launches and the reference."""
    _run(SWEEP_SCRIPT)


@pytest.mark.slow
def test_spmd_full_train_step_subprocess():
    """make_train_step(mesh=...) under a fused plan runs the flat stats and
    update per-shard end to end on the smoke transformer — 195 blocks on an
    8-device mesh, so every sharded launch takes the remainder-padding path —
    matching the unsharded step."""
    _run(TRAINER_SCRIPT)
