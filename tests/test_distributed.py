"""Device-wise (shard_map) GSNR statistics == microbatch statistics.

Needs >1 device, so the check runs in a subprocess with
XLA_FLAGS=--xla_force_host_platform_device_count=8 (the main test process
must keep seeing 1 device).
"""
import os
import subprocess
import sys

import pytest

SCRIPT = r"""
import jax, jax.numpy as jnp, numpy as np
from repro.core import grad_stats, device_grad_stats_fn

from repro.launch.mesh import make_mesh
mesh = make_mesh((8,), ("data",))
key = jax.random.PRNGKey(0)
X = jax.random.normal(key, (64, 10))
W = jnp.arange(1.0, 11.0)
Y = X @ W

def loss_fn(params, batch):
    x, y = batch
    return jnp.mean((x @ params["w"] - y) ** 2)

params = {"w": jnp.ones(10) * 0.3}
for fused in (True, False):
    f = jax.jit(device_grad_stats_fn(loss_fn, mesh, fused=fused))
    l1, _, s1 = f(params, (X, Y))
    l2, _, s2 = grad_stats(loss_fn, params, (X, Y), 8)
    assert np.allclose(float(l1), float(l2), rtol=1e-5)
    assert np.allclose(s1.mean["w"], s2.mean["w"], rtol=1e-4, atol=1e-6)
    assert np.allclose(s1.sq_mean["w"], s2.sq_mean["w"], rtol=1e-4, atol=1e-6)
    assert s1.k == 8

# flat path: stats arrive as FlatBuffers, identical statistics, and the
# single all-reduce runs over the contiguous flat carry (no stacked tree copy)
f = jax.jit(device_grad_stats_fn(loss_fn, mesh, flat=True))
l3, _, s3 = f(params, (X, Y))
_, _, s2 = grad_stats(loss_fn, params, (X, Y), 8)
from repro.core.layout import is_flat
assert is_flat(s3.mean) and is_flat(s3.sq_mean)
s3t = s3.as_tree()
assert np.allclose(s3t.mean["w"], s2.mean["w"], rtol=1e-4, atol=1e-6)
assert np.allclose(s3t.sq_mean["w"], s2.sq_mean["w"], rtol=1e-4, atol=1e-6)
txt = f.lower(params, (X, Y)).compile().as_text()
n_ar = txt.count(" all-reduce(")
assert n_ar <= 2, f"expected one flat stats reduction, got {n_ar} all-reduces"

# fused path emits exactly ONE all-reduce for the stats payload
txt = jax.jit(device_grad_stats_fn(loss_fn, mesh, fused=True)).lower(params, (X, Y)).compile().as_text()
n_ar = txt.count(" all-reduce(")
assert n_ar <= 2, f"expected fused stats reduction, got {n_ar} all-reduces"
print("OK")
"""


@pytest.mark.slow
def test_device_stats_match_microbatch_subprocess():
    env = dict(os.environ)
    env["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
    env["PYTHONPATH"] = os.path.join(os.path.dirname(__file__), "..", "src")
    out = subprocess.run(
        [sys.executable, "-c", SCRIPT], env=env, capture_output=True, text=True, timeout=300
    )
    assert out.returncode == 0, out.stderr[-3000:]
    assert "OK" in out.stdout


FLAT_SHARD_SCRIPT = r"""
import jax, jax.numpy as jnp, numpy as np
from jax.sharding import PartitionSpec as P
from repro.core.layout import FlatBuffer, is_flat
from repro.launch.mesh import make_mesh
from repro.sharding import activate, param_shardings

mesh = make_mesh((8,), ("data",))
import oracle
params = oracle.hostile_params()
from repro.configs.base import OptimizerConfig
from repro.core import make_optimizer
opt = make_optimizer(
    OptimizerConfig(name="vr_adam", lr=0.01, schedule="constant"), use_pallas=True
)
state = opt.init(params)
assert is_flat(state["m"])

with activate(mesh) as rules:
    shardings = param_shardings(state, rules)
# the FlatBuffer node survives with a rows-dimension FSDP spec, NOT the
# generic 2-D weight rule (which would TP-shard the 128-lane dim) and NOT a
# replicated leaf
for part in ("m", "v", "p"):
    sh = shardings[part]
    assert is_flat(sh), type(sh)
    assert sh.data.spec == P("data", None), sh.data.spec

placed = jax.device_put(state, shardings)
rows = state["m"].shape[0]
assert rows % 8 == 0
shard_shapes = {s.data.shape for s in placed["m"].data.addressable_shards}
assert shard_shapes == {(rows // 8, 128)}, shard_shapes
# round trip: unpack of the sharded buffer still reconstructs every leaf
for a, b in zip(
    jax.tree_util.tree_leaves(placed["m"].unpack()),
    jax.tree_util.tree_leaves(state["m"].unpack()),
):
    np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
print("OK")
"""


@pytest.mark.slow
def test_flat_opt_state_fsdp_shards_rows_subprocess():
    """FSDP on the flat m/v/p buffers: the rows dimension shards over the
    data axis (8 ways here) exactly like the per-leaf state it replaced —
    a FlatBuffer must not fall through the generic 2-D weight rule."""
    env = dict(os.environ)
    env["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
    env["PYTHONPATH"] = os.pathsep.join(
        [os.path.join(os.path.dirname(__file__), "..", "src"), os.path.dirname(__file__)]
    )
    out = subprocess.run(
        [sys.executable, "-c", FLAT_SHARD_SCRIPT],
        env=env, capture_output=True, text=True, timeout=300,
    )
    assert out.returncode == 0, out.stderr[-3000:]
    assert "OK" in out.stdout
