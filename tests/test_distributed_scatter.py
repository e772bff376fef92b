"""The data-axis GSNR statistics on the flat path reduce-scatter g and g²
into the flat state's row shards instead of all-reducing them.

Four virtual CPU devices, so the checks run in a subprocess with
XLA_FLAGS=--xla_force_host_platform_device_count=4 (the main test process
keeps seeing one device).  One process computes everything; the tests read
its JSON line:

  * the scattered mean / mean of squares equal the all-reduced ones and the
    k-microbatch statistics, for both schedules, and lie in
    ``Rules.flat_buffer_pspec``'s row split;
  * the noise terms equal the ones read off the replicated statistics, and
    the logged GSNR summary equals the one of the unpacked tree;
  * where the rules do not split the rows over the data axis alone (rows
    that do not divide, FSDP off, FSDP over pod and data) the statistics
    keep the replicated pmean;
  * the compiled train step of a four-device data-axis run (VR-LAMB, the
    FSDP-sharded flat state, the fused plan) reduce-scatters g and g² into
    the row shards and all-reduces or all-gathers no full flat buffer; with
    FSDP off it reduce-scatters nothing and gathers no flat buffer either.
"""
import json
import math
import os
import re
import subprocess
import sys

import pytest

SCRIPT = r"""
import dataclasses, json
import jax, jax.numpy as jnp, numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from repro.backend import Backend
from repro.configs import get_smoke
from repro.core import device_grad_stats_fn, grad_stats, stats_summary
from repro.core.layout import ParamLayout, is_flat
from repro.core.noise_scale import noise_terms
from repro.data import lm_batches
from repro.launch.mesh import make_mesh
from repro.sharding import activate, param_shardings
from repro.sharding.rules import Rules
from repro.train import init_state, make_loss_fn, make_train_step

out = {}
mesh = make_mesh((4,), ("data",))
key = jax.random.PRNGKey(0)
kx, k1, k2, kb = jax.random.split(key, 4)
X = jax.random.normal(kx, (32, 24))
Y = jnp.sin(X[:, 0])
params = {"w1": jax.random.normal(k1, (24, 40)) * 0.2,
          "w2": jax.random.normal(k2, (40,)) * 0.2, "b": jnp.zeros(())}


def loss_fn(p, batch):
    x, y = batch
    return jnp.mean((jnp.tanh(x @ p["w1"]) @ p["w2"] + p["b"] - y) ** 2)


def close(a, b, rtol=1e-4, atol=1e-6):
    return bool(np.allclose(np.asarray(a), np.asarray(b), rtol=rtol, atol=atol))


layout = ParamLayout.for_tree(params)
rows = layout.n_rows
_, _, micro = grad_stats(loss_fn, params, (X, Y), 4)
want = Rules(mesh=mesh).flat_buffer_pspec((rows, 128))
out["rows"] = [rows, rows % 4]
for fused in (True, False):
    name = "fused" if fused else "two"
    l_new, _, s_new, t_new = jax.jit(device_grad_stats_fn(
        loss_fn, mesh, fused=fused, flat=True, with_noise_terms=True))(params, (X, Y))
    # the all-reduced statistics: the tree path keeps its pmeans
    l_old, _, s_old, t_old = jax.jit(device_grad_stats_fn(
        loss_fn, mesh, fused=fused, with_noise_terms=True))(params, (X, Y))
    assert is_flat(s_new.mean) and is_flat(s_new.sq_mean)
    mean_old, sq_old = layout.pack(s_old.mean), layout.pack(s_old.sq_mean)
    tree = s_new.as_tree()
    shards = sorted((s.device.id, s.index[0].start or 0, s.data.shape[0])
                    for s in s_new.mean.data.addressable_shards)
    terms_old = noise_terms(s_old)
    out[name] = {
        "loss": close(l_new, l_old, rtol=1e-6, atol=0),
        "pmean": close(s_new.mean.data, mean_old) and close(s_new.sq_mean.data, sq_old),
        "micro": all(close(tree.mean[n], micro.mean[n]) and close(tree.sq_mean[n], micro.sq_mean[n])
                     for n in params),
        "k": int(s_new.k),
        "want": str(want),
        "split": all(x.data.sharding.is_equivalent_to(NamedSharding(mesh, want), 2)
                     for x in (s_new.mean, s_new.sq_mean)),
        "shards": shards,
        "terms": close(t_new, t_old, rtol=1e-5, atol=0)
                 and close(t_new, [terms_old.g2_big, terms_old.g2_small], rtol=1e-5, atol=0),
        "summary": all(close(a, b, rtol=1e-5, atol=1e-7) for a, b in zip(
            jax.tree_util.tree_leaves(stats_summary(s_new, 0.1)),
            jax.tree_util.tree_leaves(stats_summary(s_old, 0.1)))),
    }

# rows that do not divide over the data axis: the pmean, replicated
mesh3 = Mesh(np.array(jax.devices()[:3]), ("data",))
p5 = {"w": jnp.linspace(-1.0, 1.0, 5 * 64 * 128)}
X5 = jax.random.normal(kb, (12, p5["w"].size)) * 0.05
Y5 = jnp.tanh(X5 @ jnp.linspace(0.3, -0.3, p5["w"].size))
lin = lambda p, b: jnp.mean((b[0] @ p["w"] - b[1]) ** 2)
f3 = jax.jit(device_grad_stats_fn(lin, mesh3, flat=True))
_, _, s3 = f3(p5, (X5, Y5))
_, _, m3 = grad_stats(lin, p5, (X5, Y5), 3)
txt3 = f3.lower(p5, (X5, Y5)).compile().as_text()
out["odd"] = {
    "rows": [ParamLayout.for_tree(p5).n_rows, 3],
    "replicated": s3.mean.data.sharding.is_fully_replicated,
    "micro": close(s3.as_tree().mean["w"], m3.mean["w"]),
    "reduce_scatter": txt3.count(" reduce-scatter("),
}

# rules that do not split the rows over the data axis alone: the pmean
mesh22 = make_mesh((2, 2), ("pod", "data"))
for name, m_, kw in (("no_fsdp", mesh, {"fsdp": False}),
                     ("fsdp_over_pod", mesh22, {"fsdp_over_pod": True})):
    _, _, micro_ = grad_stats(loss_fn, params, (X, Y), dict(m_.shape)["data"])
    with activate(m_, **kw) as rules:  # the rules active when it is built
        f_ = jax.jit(device_grad_stats_fn(loss_fn, m_, flat=True))
    _, _, s_ = f_(params, (X, Y))
    txt_ = f_.lower(params, (X, Y)).compile().as_text()
    out[name] = {
        "spec": str(rules.flat_buffer_pspec((rows, 128))),
        "replicated": s_.mean.data.sharding.is_fully_replicated,
        "micro": close(s_.mean.data, layout.pack(micro_.mean))
                 and close(s_.sq_mean.data, layout.pack(micro_.sq_mean)),
        "reduce_scatter": txt_.count(" reduce-scatter("),
    }

# the compiled four-device train step, p1-dp4's settings at a small size
cfg = get_smoke("bert-large").replace(global_batch=16, seq_len=16)
cfg = cfg.replace(
    optimizer=dataclasses.replace(cfg.optimizer, name="vr_lamb", k=4,
                                  gsnr_source="data_axis", grad_clip=1.0),
    parallel=dataclasses.replace(cfg.parallel, backend=Backend.all_fused()),
)
state = init_state(cfg)
with activate(mesh) as rules:
    placed = jax.device_put(state, param_shardings(state, rules))
batch = jax.device_put(next(iter(lm_batches(cfg.model.vocab_size, 16, 16, seed=0))),
                       NamedSharding(mesh, P("data")))
m = placed.opt_state["m"].data
out["state"] = [m.shape[0], str(m.sharding.spec)]
for name, rules_kw, kw in (("step", {}, {}),
                           ("step_logged", {}, {"log_gsnr": True, "noise_scale": True}),
                           ("step_no_fsdp", {"fsdp": False}, {})):
    # the step is built under the rules the state is placed by, and traced
    # outside them, as the benchmark's program does
    with activate(mesh, **rules_kw) as rules:
        placed = jax.device_put(state, param_shardings(state, rules))
        step_fn, _ = make_train_step(cfg, make_loss_fn(cfg), mesh=mesh, **kw)
    outs = (jax.tree_util.tree_map(lambda x: x.sharding, placed), NamedSharding(mesh, P()))
    f = jax.jit(lambda s, b: step_fn(s, b, True), out_shardings=outs)
    out[name] = f.lower(placed, batch).compile().as_text()
print(json.dumps(out))
"""

_DT = {"f32": 4, "bf16": 2, "s32": 4, "u32": 4, "pred": 1}
ROW_SPLIT = "PartitionSpec('data', None)"


def _collectives(hlo: str):
    """(opcode, result bytes, result shape) of every collective in ``hlo``."""
    found = []
    for m in re.finditer(
        r"= (\(?[^=]*?\)?) (all-reduce|all-gather|reduce-scatter)(?:-start)?\(", hlo
    ):
        shapes = re.findall(r"(\w+)\[([\d,]*)\]", m.group(1))
        nbytes = sum(_DT[dt] * math.prod(int(d) for d in dims.split(",") if d)
                     for dt, dims in shapes)
        found.append((m.group(2), nbytes, shapes))
    return found


@pytest.fixture(scope="module")
def run():
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               XLA_FLAGS="--xla_force_host_platform_device_count=4")
    env["PYTHONPATH"] = os.path.join(os.path.dirname(__file__), "..", "src")
    r = subprocess.run([sys.executable, "-c", SCRIPT], env=env, capture_output=True,
                       text=True, timeout=300)
    assert r.returncode == 0, r.stderr[-4000:]
    return json.loads(r.stdout.strip().splitlines()[-1])


@pytest.mark.parametrize("schedule", ["fused", "two"])
def test_scattered_statistics_equal_the_all_reduced_and_the_microbatch_ones(run, schedule):
    got = run[schedule]
    assert run["rows"][1] == 0, run["rows"]
    assert got["loss"] and got["pmean"] and got["micro"], got
    assert got["k"] == 4


@pytest.mark.parametrize("schedule", ["fused", "two"])
def test_scattered_statistics_lie_in_the_flat_states_row_split(run, schedule):
    got = run[schedule]
    rows = run["rows"][0]
    assert got["want"] == ROW_SPLIT
    assert got["split"]  # both moments, sharded as that spec places them
    assert got["shards"] == [[d, d * rows // 4, rows // 4] for d in range(4)]


@pytest.mark.parametrize("schedule", ["fused", "two"])
def test_noise_terms_and_logged_summary_match_the_replicated_statistics(run, schedule):
    assert run[schedule]["terms"]
    assert run[schedule]["summary"]


def test_rows_that_do_not_divide_keep_the_replicated_pmean(run):
    odd = run["odd"]
    assert odd["rows"][0] % odd["rows"][1] != 0
    assert odd["replicated"] and odd["micro"]
    assert odd["reduce_scatter"] == 0


@pytest.mark.parametrize("rules", ["no_fsdp", "fsdp_over_pod"])
def test_rows_not_split_over_the_data_axis_alone_keep_the_replicated_pmean(run, rules):
    got = run[rules]
    assert got["spec"] != ROW_SPLIT
    assert got["replicated"] and got["micro"]
    assert got["reduce_scatter"] == 0


@pytest.mark.parametrize("step", ["step", "step_logged"])
def test_train_step_reduce_scatters_the_payload_and_gathers_no_flat_buffer(run, step):
    rows, spec = run["state"]
    assert spec == ROW_SPLIT
    full = rows * 128 * 4
    found = _collectives(run[step])
    scattered = [s for c in found if c[0] == "reduce-scatter" for s in c[2]]
    # g and g², each into this device's quarter of the rows
    assert scattered == [("f32", f"{rows // 4},128")] * 2, scattered
    big = [c for c in found if c[0] in ("all-reduce", "all-gather") and c[1] >= full]
    assert not big, big


def test_train_step_without_fsdp_scatters_nothing_and_gathers_no_flat_buffer(run):
    rows = run["state"][0]
    found = _collectives(run["step_no_fsdp"])
    assert not [c for c in found if c[0] == "reduce-scatter"], found
    assert not [c for c in found if c[0] == "all-gather" and c[1] >= rows * 128 * 4], found
