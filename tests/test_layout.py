"""ParamLayout / FlatBuffer: round trips, loud structure errors, checkpoint
interop, and the one-pallas_call-per-step launch-count guarantees.

The launch counts are asserted structurally: trace the step and count
pallas_call equations in the jaxpr (recursing into scan/cond/jit bodies) —
the flat refactor's whole point is accumulation and update each being a
SINGLE call over the flat buffer instead of a kernel per pytree leaf.
"""
import dataclasses
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import oracle
from repro.core import GradStats, make_optimizer
from repro.core.layout import FlatBuffer, ParamLayout, is_flat, unpack_tree
from repro.configs.base import OptimizerConfig
from repro.analysis.launch_manifest import LAUNCHES
from repro.kernels.ops import count_pallas_calls

_tm = jax.tree_util.tree_map


# ---------------------------------------------------------------------------
# pack / unpack round trips
# ---------------------------------------------------------------------------

TREES = {
    "nested": {"a": jnp.arange(7.0), "c": {"d": jnp.ones((3, 5, 7)), "e": jnp.zeros(())}},
    "tuple_nodes": {"pair": (jnp.arange(12.0).reshape(3, 4), jnp.ones(5)), "w": jnp.ones((33, 5))},
    "ragged": {"w": jnp.arange(1000.0), "b": jnp.ones(1), "e": jnp.arange(4096.0)},
}


@pytest.mark.parametrize("name", sorted(TREES))
def test_pack_unpack_identity(name):
    tree = TREES[name]
    layout = ParamLayout.for_tree(tree)
    buf = layout.pack(tree)
    assert buf.shape == (layout.n_rows, 128)
    assert layout.n_rows % layout.block_rows == 0
    back = layout.unpack(buf)
    for a, b in zip(jax.tree_util.tree_leaves(back), jax.tree_util.tree_leaves(tree)):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
    # the tail padding is exactly zero (kernels rely on it for reductions)
    total = sum(layout.sizes)
    assert float(jnp.sum(jnp.abs(buf))) == pytest.approx(
        float(sum(jnp.sum(jnp.abs(x.astype(jnp.float32))) for x in jax.tree_util.tree_leaves(tree))),
        rel=1e-6,
    )
    assert buf.size >= total


def test_pack_unpack_bf16_state():
    tree = {"m": jnp.asarray(np.random.RandomState(0).randn(37, 3), jnp.bfloat16)}
    layout = ParamLayout.for_tree(tree)
    buf = layout.pack(tree, jnp.bfloat16)
    assert buf.dtype == jnp.bfloat16
    back = layout.unpack(buf)
    assert back["m"].dtype == jnp.bfloat16
    np.testing.assert_array_equal(
        np.asarray(back["m"], np.float32), np.asarray(tree["m"], np.float32)
    )


def test_block_leaf_map_consistent():
    tree = oracle.hostile_params()
    layout = ParamLayout.for_tree(tree)
    ids = layout.block_leaf_ids()
    assert ids.shape == (layout.n_blocks,)
    # every leaf owns a whole number of blocks, in offset order
    counts = np.bincount(ids, minlength=layout.n_leaves)
    np.testing.assert_array_equal(
        counts, np.asarray(layout.leaf_rows) // layout.block_rows
    )
    assert (np.diff(ids) >= 0).all()


def test_structure_mismatch_raises_loudly():
    tree = {"a": jnp.ones(4), "b": jnp.ones((2, 2))}
    layout = ParamLayout.for_tree(tree)
    with pytest.raises(ValueError, match="structure"):
        layout.pack({"a": jnp.ones(4)})  # missing leaf
    with pytest.raises(ValueError, match="shape"):
        layout.pack({"a": jnp.ones(5), "b": jnp.ones((2, 2))})  # wrong leaf shape
    # diverging moment tree structure surfaces the same loud error through
    # the kernel dispatch (the old flatten_up_to failure was opaque)
    stats = GradStats(mean=tree, sq_mean={"a": jnp.ones(4)}, k=4)
    from repro.kernels import ops as kops

    with pytest.raises(ValueError, match="structure"):
        kops.vr_scale_tree(stats, tree, 0.1, 1e-12)


def test_flatbuffer_is_a_pytree_node():
    tree = {"a": jnp.arange(6.0)}
    layout = ParamLayout.for_tree(tree)
    fb = FlatBuffer(layout.pack(tree), layout)
    doubled = _tm(lambda x: 2 * x, fb)
    assert is_flat(doubled)
    np.testing.assert_array_equal(np.asarray(doubled.unpack()["a"]), 2 * np.arange(6.0))
    # layouts ride in the treedef: structure equality includes geometry
    assert jax.tree_util.tree_structure(fb) == jax.tree_util.tree_structure(doubled)
    assert unpack_tree({"m": fb, "step": 0})["m"]["a"].shape == (6,)


# ---------------------------------------------------------------------------
# launch counts: ONE pallas_call per optimizer step / accumulation sweep
# ---------------------------------------------------------------------------


def _opt_and_inputs(name):
    params = oracle.hostile_params()
    g = _tm(lambda x: x * 0.01, params)
    stats = GradStats(mean=g, sq_mean=_tm(lambda x: jnp.square(x) + 1e-3, g), k=8)
    cfg = OptimizerConfig(name=name, lr=0.01, schedule="constant", weight_decay=0.01)
    opt = make_optimizer(cfg, use_pallas=True)
    return opt, params, g, stats


@pytest.mark.parametrize("name", ("vr_sgd", "vr_momentum", "vr_adam", "vr_lars", "vr_lamb"))
def test_update_is_one_pallas_call(name):
    opt, params, g, stats = _opt_and_inputs(name)
    state = opt.init(params)
    jaxpr = jax.make_jaxpr(lambda s: opt.update(g, s, params, stats=stats))(state)
    assert count_pallas_calls(jaxpr) == LAUNCHES["flat_update"], jaxpr


def test_grad_stats_scan_is_two_pallas_calls():
    """One accumulation call in the scan body + one finalize call."""
    from repro.core import grad_stats

    params = {"w": jnp.ones(300), "b": jnp.zeros(())}

    def loss_fn(p, b):
        x, y = b
        return jnp.mean((x @ p["w"] + p["b"] - y) ** 2)

    X = jnp.ones((16, 300))
    Y = jnp.ones((16,))
    jaxpr = jax.make_jaxpr(
        lambda p, b: grad_stats(loss_fn, p, b, 4, use_pallas=True)[2]
    )(params, (X, Y))
    assert count_pallas_calls(jaxpr) == LAUNCHES["grad_stats_scan"], jaxpr


def test_flash_attention_train_vjp_launch_counts():
    """The attention custom VJP is structurally fused: the primal is ONE
    pallas_call (no LSE emitted when nothing differentiates), and a jax.grad
    trace is exactly TWO — the LSE-emitting forward + the fused one-pass
    dq/dk/dv backward (the s = qkᵀ recompute shared across all three grads).
    The delta preprocess is a jnp einsum, not a launch."""
    import jax.numpy as jnp

    from repro.kernels.flash_attention import flash_attention

    ks = jax.random.split(jax.random.PRNGKey(0), 3)
    q = jax.random.normal(ks[0], (1, 130, 4, 32))
    k = jax.random.normal(ks[1], (1, 130, 2, 32))
    v = jax.random.normal(ks[2], (1, 130, 2, 32))
    primal = jax.make_jaxpr(lambda *a: flash_attention(*a))(q, k, v)
    assert count_pallas_calls(primal) == LAUNCHES["attention_primal"], primal
    grad = jax.make_jaxpr(
        jax.grad(lambda *a: jnp.sum(flash_attention(*a)), argnums=(0, 1, 2))
    )(q, k, v)
    assert count_pallas_calls(grad) == LAUNCHES["attention_grad"], grad


def test_packed_flash_attention_launch_counts():
    """The PACKED path is structurally identical to the implicit-arange path:
    explicit positions/segments ride the same pallas_calls as extra operands
    — primal 1, jax.grad exactly 2 (LSE fwd + fused dq/dk/dv backward).  A
    packing gate regression (packed layouts falling back to jnp) changes the
    count."""
    import oracle as orc

    from repro.kernels.flash_attention import flash_attention

    case = orc.PACKED_ATTN_CASES["multi_segment"]
    q, k, v, pos, _ = orc.packed_case_inputs(case, seed=0)
    fn = lambda *a: flash_attention(*a, pos, pos, causal=True)
    primal = jax.make_jaxpr(fn)(q, k, v)
    assert count_pallas_calls(primal) == LAUNCHES["attention_primal"], primal
    grad = jax.make_jaxpr(
        jax.grad(lambda *a: jnp.sum(fn(*a)), argnums=(0, 1, 2))
    )(q, k, v)
    assert count_pallas_calls(grad) == LAUNCHES["attention_grad"], grad


def test_packed_batch_attention_is_on_the_fused_path():
    """Structural regression for the retired implicit_pos gate: a packed
    batch (explicit positions) must NOT produce zero pallas_calls in the
    model forward jaxpr — the exact failure mode of the old fallback."""
    import dataclasses

    from repro.configs import get_smoke
    from repro.models import forward, init_params

    cfg = get_smoke("granite-3-2b")
    pc = dataclasses.replace(cfg.parallel, use_pallas=True)
    params = init_params(cfg.model, jax.random.PRNGKey(0))
    tokens = jax.random.randint(jax.random.PRNGKey(1), (2, 16), 0, cfg.model.vocab_size)
    packed = jnp.concatenate(
        [jnp.arange(8, dtype=jnp.int32), jnp.arange(8, dtype=jnp.int32)]
    )[None, :].repeat(2, axis=0)
    jx = jax.make_jaxpr(
        lambda t, p: forward(cfg.model, pc, params, t, positions=p)[0]
    )(tokens, packed)
    assert count_pallas_calls(jx) == LAUNCHES["model_forward_fused"], jx


def test_packed_full_train_step_launch_count():
    """End to end on a PACKED batch (positions/segments from the data
    packer): the same 6 structural pallas_calls as the implicit-arange step
    — attention fwd + remat recompute + fused dq/dk/dv + 2 stats + 1
    update."""
    from repro.configs import get_smoke
    from repro.data import packed_lm_batches
    from repro.train import init_state, make_loss_fn, make_train_step

    cfg = get_smoke("granite-3-2b").replace(global_batch=8, seq_len=16)
    cfg = cfg.replace(
        optimizer=dataclasses.replace(cfg.optimizer, name="vr_lamb", k=4),
        parallel=dataclasses.replace(cfg.parallel, use_pallas=True),
    )
    batch = next(iter(packed_lm_batches(cfg.model.vocab_size, 8, 16, seed=0)))
    assert int((batch["segments"].max(axis=1) > 0).sum()) > 0  # really packed
    state = init_state(cfg)
    step_fn, _ = make_train_step(cfg, make_loss_fn(cfg))
    jaxpr = jax.make_jaxpr(step_fn)(state, batch)
    assert count_pallas_calls(jaxpr) == LAUNCHES["train_step_packed"], count_pallas_calls(jaxpr)


def test_full_train_step_launch_count():
    """End to end (fresh VR-LAMB step, use_pallas): the whole hot loop is
    Pallas.  Exactly 6 structural pallas_calls, regardless of leaf count:

      1  attention forward in the primal layer scan (no LSE)
      1  attention forward recompute under remat (LSE-emitting custom-vjp fwd)
      1  attention backward (fused one-pass dq/dk/dv kernel)
      2  grad-stats (scan-body accumulation + finalize)
      1  flat optimizer update

    A dispatch regression on any layer (attention falling back to jnp, the
    optimizer splitting per leaf, an extra stats sweep) changes the count."""
    from repro.configs import get_smoke
    from repro.data import lm_batches
    from repro.train import init_state, make_loss_fn, make_train_step

    cfg = get_smoke("granite-3-2b").replace(global_batch=8, seq_len=16)
    cfg = cfg.replace(
        optimizer=dataclasses.replace(cfg.optimizer, name="vr_lamb", k=4),
        parallel=dataclasses.replace(cfg.parallel, use_pallas=True),
    )
    assert cfg.parallel.remat  # the count below includes the remat recompute
    batch = next(iter(lm_batches(cfg.model.vocab_size, 8, 16, seed=0)))
    state = init_state(cfg)
    step_fn, _ = make_train_step(cfg, make_loss_fn(cfg))
    jaxpr = jax.make_jaxpr(step_fn)(state, batch)
    assert count_pallas_calls(jaxpr) == LAUNCHES["train_step_fused"], count_pallas_calls(jaxpr)


# ---------------------------------------------------------------------------
# checkpoint interop: flat <-> pytree state, old checkpoints still load
# ---------------------------------------------------------------------------


def _cfg(use_pallas: bool, state_dtype: str = "float32"):
    from repro.configs import get_smoke

    cfg = get_smoke("granite-3-2b")
    return cfg.replace(
        optimizer=dataclasses.replace(cfg.optimizer, name="vr_adam", state_dtype=state_dtype),
        parallel=dataclasses.replace(cfg.parallel, use_pallas=use_pallas),
    )


@pytest.mark.parametrize("state_dtype", ("float32", "bfloat16"))
def test_checkpoint_flat_roundtrip(tmp_path, state_dtype):
    from repro.train import init_state
    from repro.train.checkpoint import restore, save

    state = init_state(_cfg(True, state_dtype))
    assert is_flat(state.opt_state["m"])
    path = os.path.join(tmp_path, "flat.npz")
    save(path, state)
    like = init_state(_cfg(True, state_dtype), key=jax.random.PRNGKey(7))
    restored = restore(path, like)
    assert is_flat(restored.opt_state["m"])
    for a, b in zip(
        jax.tree_util.tree_leaves(unpack_tree(state.opt_state)),
        jax.tree_util.tree_leaves(unpack_tree(restored.opt_state)),
    ):
        assert a.dtype == b.dtype
        np.testing.assert_array_equal(np.asarray(a, np.float32), np.asarray(b, np.float32))


def test_checkpoint_cross_format(tmp_path):
    """A pytree-state checkpoint restores into a flat template and vice
    versa — the .npz key space is the unpacked pytree format either way."""
    from repro.train import init_state
    from repro.train.checkpoint import restore, save

    flat_state = init_state(_cfg(True))
    tree_state = init_state(_cfg(False))
    p_flat = os.path.join(tmp_path, "flat.npz")
    p_tree = os.path.join(tmp_path, "tree.npz")
    save(p_flat, flat_state)
    save(p_tree, tree_state)
    # same key space
    with np.load(p_flat) as a, np.load(p_tree) as b:
        assert sorted(a.files) == sorted(b.files)
    # old (pytree) checkpoint -> flat template
    r1 = restore(p_tree, init_state(_cfg(True), key=jax.random.PRNGKey(5)))
    assert is_flat(r1.opt_state["m"])
    for a, b in zip(
        jax.tree_util.tree_leaves(unpack_tree(r1.opt_state)),
        jax.tree_util.tree_leaves(tree_state.opt_state),
    ):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
    # flat checkpoint -> pytree template
    r2 = restore(p_flat, init_state(_cfg(False), key=jax.random.PRNGKey(5)))
    assert not is_flat(r2.opt_state["m"])
    for a, b in zip(
        jax.tree_util.tree_leaves(r2.opt_state),
        jax.tree_util.tree_leaves(unpack_tree(flat_state.opt_state)),
    ):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


def test_train_resume_across_formats(tmp_path):
    """Train flat -> checkpoint -> resume flat continues bit-compatibly with
    an uninterrupted flat run (checkpoint boundary is lossless)."""
    from repro.data import lm_batches
    from repro.train import init_state, make_loss_fn, make_train_step
    from repro.train.checkpoint import restore, save

    cfg = _cfg(True).replace(global_batch=8, seq_len=16)
    batches = list(b for b, _ in zip(lm_batches(cfg.model.vocab_size, 8, 16, seed=0), range(4)))
    step_fn, _ = make_train_step(cfg, make_loss_fn(cfg))
    jstep = jax.jit(step_fn)

    state = init_state(cfg)
    for b in batches[:2]:
        state, _ = jstep(state, b)
    path = os.path.join(tmp_path, "mid.npz")
    save(path, state)
    resumed = restore(path, init_state(cfg, key=jax.random.PRNGKey(3)))
    cont, chk = state, resumed
    for b in batches[2:]:
        cont, _ = jstep(cont, b)
        chk, _ = jstep(chk, b)
    for a, b_ in zip(
        jax.tree_util.tree_leaves(cont.params), jax.tree_util.tree_leaves(chk.params)
    ):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b_), atol=1e-6)
