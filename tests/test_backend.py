"""Backend execution plan: resolution semantics, the one-release use_pallas
deprecation shim (warns once, maps to the equivalent plan), mixed
per-subsystem plans, and the tier-1 guard that no raw use_pallas boolean
survives in src/ outside the shim itself."""
import dataclasses
import os
import re
import warnings

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import oracle
from repro import backend as backend_mod
from repro.analysis.launch_manifest import LAUNCHES
from repro.backend import Backend, resolve_backend
from repro.configs.base import Config, OptimizerConfig, ParallelismConfig
from repro.core import GradStats, grad_stats, make_optimizer
from repro.core.layout import is_flat

_tm = jax.tree_util.tree_map


@pytest.fixture()
def fresh_shim():
    """Re-arm the warn-once latch around a test and restore it after."""
    backend_mod.reset_deprecation_warnings()
    yield
    backend_mod.reset_deprecation_warnings()


# ---------------------------------------------------------------------------
# plan semantics
# ---------------------------------------------------------------------------


def test_default_plan_auto_resolves_by_platform():
    bk = Backend()
    expect = "fused" if jax.default_backend() == "tpu" else "reference"
    for sub in ("optimizer", "stats", "attention"):
        assert bk.resolve(sub) == expect
    # explicit modes override auto
    assert Backend.all_fused().resolve("optimizer") == "fused"
    assert Backend.all_reference().fused("stats") is False
    assert Backend(optimizer="fused").resolve("stats") == expect


def test_plan_validation_is_loud():
    with pytest.raises(ValueError, match="optimizer"):
        Backend(optimizer="pallas")
    with pytest.raises(KeyError, match="subsystem"):
        Backend().resolve("moments")


def test_interpret_detection_is_centralized():
    from repro.kernels.ops import _interpret

    assert backend_mod.default_interpret() == (jax.default_backend() != "tpu")
    # ops delegates to the single probe
    assert _interpret() == backend_mod.default_interpret()
    # explicit override wins over platform detection
    assert Backend(interpret=False).interpret_mode() is False
    assert Backend(interpret=True).interpret_mode() is True
    assert Backend().interpret_mode() == backend_mod.default_interpret()


def test_describe_carries_the_full_plan():
    d = Backend.all_fused().describe()
    assert d["optimizer"] == d["stats"] == d["attention"] == "fused"
    assert d["platform"] == jax.default_backend()
    assert d["interpret"] == backend_mod.default_interpret()


def test_plan_is_hashable_config_field():
    pc = ParallelismConfig(backend=Backend.all_fused())
    assert hash(pc) is not None
    assert resolve_backend(pc) == Backend.all_fused()
    # dataclasses.replace keeps the plan
    assert resolve_backend(dataclasses.replace(pc, remat=False)) == Backend.all_fused()


# ---------------------------------------------------------------------------
# resolution sources + the deprecation shim
# ---------------------------------------------------------------------------


def test_resolve_backend_sources():
    assert resolve_backend(None) == Backend()
    assert resolve_backend(Backend.all_fused()) == Backend.all_fused()
    assert resolve_backend(ParallelismConfig()) == Backend()
    cfg = Config(parallel=ParallelismConfig(backend=Backend.all_fused()))
    assert resolve_backend(cfg) == Backend.all_fused()
    with pytest.raises(TypeError):
        resolve_backend(object())


def test_use_pallas_shim_warns_once_and_maps(fresh_shim):
    with warnings.catch_warnings(record=True) as w:
        warnings.simplefilter("always")
        assert resolve_backend(ParallelismConfig(use_pallas=True)) == Backend.all_fused()
        assert resolve_backend(ParallelismConfig(use_pallas=False)) == Backend.all_reference()
        assert resolve_backend(use_pallas=True) == Backend.all_fused()
        assert resolve_backend(True) == Backend.all_fused()  # legacy positional
    deps = [x for x in w if issubclass(x.category, DeprecationWarning)]
    assert len(deps) == 1, "the shim must warn exactly once per process"
    assert "deprecated" in str(deps[0].message)


def test_explicit_plan_plus_flag_is_an_error():
    with pytest.raises(ValueError, match="deprecated"):
        resolve_backend(Backend.all_fused(), use_pallas=True)


def test_config_flag_takes_precedence_over_backend_field(fresh_shim):
    # a caller flipping the legacy boolean on a config that also carries a
    # plan gets the legacy semantics (that's what their code asked for)
    pc = ParallelismConfig(backend=Backend.all_reference(), use_pallas=True)
    assert resolve_backend(pc) == Backend.all_fused()


def test_make_optimizer_shim_is_equivalent(fresh_shim):
    params = oracle.hostile_params()
    g = _tm(lambda x: x * 0.01, params)
    stats = GradStats(mean=g, sq_mean=_tm(lambda x: jnp.square(x) + 1e-3, g), k=8)
    cfg = OptimizerConfig(name="vr_lamb", lr=0.01, schedule="constant", weight_decay=0.01)
    with warnings.catch_warnings(record=True) as w:
        warnings.simplefilter("always")
        o_old = make_optimizer(cfg, use_pallas=True)
    assert any(issubclass(x.category, DeprecationWarning) for x in w)
    o_new = make_optimizer(cfg, backend=Backend.all_fused())
    s_old, s_new = o_old.init(params), o_new.init(params)
    assert is_flat(s_old["m"]) and is_flat(s_new["m"])
    u_old, _ = jax.jit(lambda s: o_old.update(g, s, params, stats=stats))(s_old)
    u_new, _ = jax.jit(lambda s: o_new.update(g, s, params, stats=stats))(s_new)
    for a, b in zip(jax.tree_util.tree_leaves(u_old), jax.tree_util.tree_leaves(u_new)):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


def test_grad_stats_shim_is_equivalent(fresh_shim):
    params = {"w": jnp.ones(300), "b": jnp.zeros(())}
    X, Y = jnp.ones((16, 300)), jnp.ones((16,))

    def loss_fn(p, b):
        x, y = b
        return jnp.mean((x @ p["w"] + p["b"] - y) ** 2)

    with warnings.catch_warnings():
        warnings.simplefilter("ignore", DeprecationWarning)
        _, _, s_old = grad_stats(loss_fn, params, (X, Y), 4, use_pallas=True)
    _, _, s_new = grad_stats(loss_fn, params, (X, Y), 4, backend=Backend.all_fused())
    assert is_flat(s_old.mean) and is_flat(s_new.mean)
    np.testing.assert_array_equal(np.asarray(s_old.mean.data), np.asarray(s_new.mean.data))
    np.testing.assert_array_equal(np.asarray(s_old.sq_mean.data), np.asarray(s_new.sq_mean.data))


# ---------------------------------------------------------------------------
# mixed per-subsystem plans (the new capability the boolean could not express)
# ---------------------------------------------------------------------------


def _quad_setup():
    params = {"w": jnp.linspace(-1.0, 1.0, 500), "b": jnp.ones(())}
    X = jax.random.normal(jax.random.PRNGKey(0), (16, 500)) * 0.3
    Y = jnp.tanh(X @ jnp.linspace(0.5, -0.5, 500))

    def loss_fn(p, b):
        x, y = b
        return jnp.mean((x @ p["w"] + p["b"] - y) ** 2)

    return params, (X, Y), loss_fn


@pytest.mark.parametrize(
    "plan",
    (
        Backend(optimizer="fused", stats="reference", attention="reference"),
        Backend(optimizer="reference", stats="fused", attention="reference"),
    ),
    ids=("fused-opt-tree-stats", "tree-opt-fused-stats"),
)
def test_mixed_plans_cross_the_flat_boundary(plan):
    """optimizer and stats subsystems select independently: flat GradStats
    feed the jnp optimizer (unpacked on entry) and tree GradStats feed the
    fused optimizer (packed on entry) — both match the all-reference run."""
    params, batch, loss_fn = _quad_setup()
    cfg = OptimizerConfig(name="vr_adam", lr=0.05, schedule="constant")

    def step(bk):
        loss, _, stats = grad_stats(loss_fn, params, batch, 4, backend=bk)
        opt = make_optimizer(cfg, backend=bk)
        state = opt.init(params)
        upd, _ = opt.update(stats.mean, state, params, stats=stats)
        return loss, upd

    loss_ref, upd_ref = jax.jit(lambda: step(Backend.all_reference()))()
    loss_mix, upd_mix = jax.jit(lambda: step(plan))()
    np.testing.assert_allclose(float(loss_ref), float(loss_mix), rtol=1e-6)
    for a, b in zip(jax.tree_util.tree_leaves(upd_ref), jax.tree_util.tree_leaves(upd_mix)):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), rtol=5e-5, atol=1e-7)


def test_fused_stats_flat_grads_survive_reference_momentum():
    """A FlatBuffer mean gradient (fused stats) entering a reference
    vr_momentum/vr_sgd update unpacks at the transform boundary instead of
    crashing tree_map structure matching."""
    params, batch, loss_fn = _quad_setup()
    bk = Backend(optimizer="reference", stats="fused", attention="reference")
    _, _, stats = grad_stats(loss_fn, params, batch, 4, backend=bk)
    assert is_flat(stats.mean)
    for name in ("vr_sgd", "vr_momentum", "vr_lamb"):
        opt = make_optimizer(
            OptimizerConfig(name=name, lr=0.01, schedule="constant"), backend=bk
        )
        state = opt.init(params)
        upd, _ = opt.update(stats.mean, state, params, stats=stats)
        assert not is_flat(upd)
        assert jax.tree_util.tree_structure(upd) == jax.tree_util.tree_structure(params)


# ---------------------------------------------------------------------------
# tier-1 guard: the boolean is gone from src/ outside the shim
# ---------------------------------------------------------------------------

_SRC = os.path.join(os.path.dirname(__file__), "..", "src")
# the shim proper: the resolution/warning logic and the deprecated config field
_SHIM_FILES = {
    os.path.join("repro", "backend.py"),
    os.path.join("repro", "configs", "base.py"),
}
# outside those files the only legal appearances are the deprecated keyword
# in a signature (use_pallas=None) and its forwarding into resolve_backend
# (use_pallas=use_pallas) — no reads, no branches, no bool annotations
_SHIM_LINE = re.compile(r"use_pallas=(None\b|use_pallas\b)")


def test_no_raw_use_pallas_outside_the_shim():
    offenders = []
    for root, _dirs, files in os.walk(_SRC):
        for fname in files:
            if not fname.endswith(".py"):
                continue
            path = os.path.join(root, fname)
            rel = os.path.relpath(path, _SRC)
            if rel in _SHIM_FILES:
                continue
            with open(path) as f:
                for lineno, line in enumerate(f, 1):
                    if "use_pallas" in line and not _SHIM_LINE.search(line):
                        offenders.append(f"{rel}:{lineno}: {line.strip()}")
    assert not offenders, (
        "raw use_pallas outside the deprecation shim — dispatch must go "
        "through repro.backend.Backend:\n" + "\n".join(offenders)
    )


# ---------------------------------------------------------------------------
# model dispatch through the plan
# ---------------------------------------------------------------------------


def test_attention_dispatch_follows_the_plan(fresh_shim):
    """config.backend fused-attention runs the kernel path (1 pallas_call in
    the forward jaxpr); the legacy boolean maps to the same dispatch."""
    from repro.configs import get_smoke
    from repro.kernels.ops import count_pallas_calls
    from repro.models import forward, init_params

    cfg = get_smoke("granite-3-2b")
    params = init_params(cfg.model, jax.random.PRNGKey(0))
    tokens = jax.random.randint(jax.random.PRNGKey(1), (2, 16), 0, cfg.model.vocab_size)

    def n_calls(pc):
        jx = jax.make_jaxpr(lambda t: forward(cfg.model, pc, params, t)[0])(tokens)
        return count_pallas_calls(jx)

    pc_new = dataclasses.replace(cfg.parallel, backend=Backend(attention="fused"))
    with warnings.catch_warnings():
        warnings.simplefilter("ignore", DeprecationWarning)
        pc_old = dataclasses.replace(cfg.parallel, use_pallas=True)
        assert n_calls(pc_old) == n_calls(pc_new) == LAUNCHES["model_forward_fused"]
    assert n_calls(dataclasses.replace(cfg.parallel, backend=Backend.all_reference())) \
        == LAUNCHES["model_forward_reference"]


def test_spmd_plan_falls_back_on_single_device():
    """Backend.shard on a 1-device mesh reports supports() False for any
    layout — the gathered single-launch path keeps serving."""
    from repro.core.layout import ParamLayout
    from repro.launch.mesh import make_mesh
    from repro.sharding.rules import Rules

    mesh = make_mesh((1,), ("data",))
    plan = Backend.all_fused().shard(mesh, Rules(mesh=mesh))
    layout = ParamLayout.for_tree(oracle.hostile_params())
    assert plan.supports(layout) is False
    opt = make_optimizer(
        OptimizerConfig(name="vr_adam", lr=0.01, schedule="constant"),
        backend=Backend.all_fused(), spmd=plan,
    )
    params = oracle.hostile_params()
    g = _tm(lambda x: x * 0.01, params)
    stats = GradStats(mean=g, sq_mean=_tm(lambda x: jnp.square(x) + 1e-3, g), k=8)
    from repro.kernels.ops import count_pallas_calls

    state = opt.init(params)
    jaxpr = jax.make_jaxpr(lambda s: opt.update(g, s, params, stats=stats))(state)
    # gathered single launch preserved
    assert count_pallas_calls(jaxpr) == LAUNCHES["flat_update"]
