"""MoE dispatch correctness: sparse gather/scatter vs dense oracle."""
import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs.base import MoEConfig
from repro.models.moe import apply_moe, apply_moe_dense, apply_moe_dropless, moe_init


def setup(key, e=4, k=2, cap=8.0, shared=0, d=16, f=32):
    cfg = MoEConfig(n_experts=e, top_k=k, capacity_factor=cap, n_shared_experts=shared)
    p = moe_init(key, d, f, "swiglu", cfg)
    x = jax.random.normal(jax.random.fold_in(key, 1), (2, 12, d))
    return cfg, p, x


def test_sparse_matches_dense_at_high_capacity():
    """With capacity >= tokens, no drops -> sparse == dense oracle exactly."""
    cfg, p, x = setup(jax.random.PRNGKey(0), cap=8.0)
    out_s, aux_s = apply_moe(p, x, "swiglu", cfg)
    out_d, aux_d = apply_moe_dense(p, x, "swiglu", cfg)
    np.testing.assert_allclose(np.asarray(out_s), np.asarray(out_d), atol=2e-5)
    assert float(aux_s["moe_lb_loss"]) == pytest.approx(float(aux_d["moe_lb_loss"]), rel=1e-5)


def test_top1_routing():
    cfg, p, x = setup(jax.random.PRNGKey(1), e=4, k=1)
    out_s, _ = apply_moe(p, x, "swiglu", cfg)
    out_d, _ = apply_moe_dense(p, x, "swiglu", cfg)
    np.testing.assert_allclose(np.asarray(out_s), np.asarray(out_d), atol=2e-5)


def test_shared_expert_added():
    cfg, p, x = setup(jax.random.PRNGKey(2), shared=1)
    out, _ = apply_moe(p, x, "swiglu", cfg)
    outd, _ = apply_moe_dense(p, x, "swiglu", cfg)
    np.testing.assert_allclose(np.asarray(out), np.asarray(outd), atol=2e-5)
    # removing the shared expert changes the output
    p2 = {k_: v for k_, v in p.items() if not k_.startswith("shared_")}
    cfg2 = MoEConfig(n_experts=4, top_k=2, capacity_factor=8.0, n_shared_experts=0)
    out2, _ = apply_moe(p2, x, "swiglu", cfg2)
    assert float(jnp.max(jnp.abs(out - out2))) > 1e-4


def test_capacity_drops_reduce_output():
    """Tiny capacity (1 slot/expert) drops most tokens: the combined output
    loses most of its mass vs the lossless dispatch."""
    cfg, p, x = setup(jax.random.PRNGKey(3))
    out_full, _ = apply_moe(p, x, "swiglu", cfg)  # lossless (cap=8.0)
    cfg1 = MoEConfig(n_experts=4, top_k=2, capacity_factor=1e-9)  # ceil -> 1 slot
    out_drop, _ = apply_moe(p, x, "swiglu", cfg1)
    n_nonzero_full = int(np.sum(np.abs(np.asarray(out_full)).sum(-1) > 1e-6))
    n_nonzero_drop = int(np.sum(np.abs(np.asarray(out_drop)).sum(-1) > 1e-6))
    assert n_nonzero_drop < n_nonzero_full
    assert float(jnp.linalg.norm(out_drop)) < float(jnp.linalg.norm(out_full))


def test_load_balance_loss_favors_uniform():
    """Uniform router -> lb loss ~= 1; collapsed router -> ~= n_experts."""
    e, d = 4, 16
    key = jax.random.PRNGKey(4)
    cfg = MoEConfig(n_experts=e, top_k=1, router_aux_weight=1.0)
    p = moe_init(key, d, 32, "swiglu", cfg)
    x = jax.random.normal(jax.random.fold_in(key, 2), (4, 64, d))
    p_uniform = dict(p, router=jnp.zeros((d, e)))
    _, aux_u = apply_moe_dense(p_uniform, x, "swiglu", cfg)
    # collapsed: positive inputs + a single hot column route everything to e0
    x_pos = jnp.abs(x) + 0.5
    collapsed = jnp.zeros((d, e)).at[:, 0].set(10.0)
    _, aux_c = apply_moe_dense(dict(p, router=collapsed), x_pos, "swiglu", cfg)
    assert float(aux_u["moe_lb_loss"]) == pytest.approx(1.0, rel=0.15)
    assert float(aux_c["moe_lb_loss"]) > 2.0


def test_moe_gradients_flow_to_router():
    cfg, p, x = setup(jax.random.PRNGKey(5))

    def loss(p_):
        out, aux = apply_moe(p_, x, "swiglu", cfg)
        return jnp.sum(out**2) + aux["moe_lb_loss"]

    g = jax.grad(loss)(p)
    assert float(jnp.max(jnp.abs(g["router"]))) > 0
    assert float(jnp.max(jnp.abs(g["expert_wi"]))) > 0


# ---------------------------------------------------------------------------
# the dropless layer that holds a share of the experts
# ---------------------------------------------------------------------------


def held_setup(key, e=8, k=2, held=0, first=0, d=16, f=32, tokens=24):
    cfg = MoEConfig(n_experts=e, top_k=k, capacity_factor=None, n_held=held, first_held=first)
    p = moe_init(key, d, f, "swiglu", cfg)
    x = jax.random.normal(jax.random.fold_in(key, 1), (2, tokens // 2, d))
    return cfg, p, x


def share(p, first, held):
    """The uncut layer's weights as a share holds them: every router column,
    its own experts."""
    return {k_: v[first:first + held] if k_.startswith("expert_") else v for k_, v in p.items()}


def test_dropless_held_share_matches_the_dense_oracle():
    """Experts 3-5 of 8, top-2: the grouped matmuls over the rows routed to
    them give the dense oracle restricted to them, output and gradients."""
    cfg, p, x = held_setup(jax.random.PRNGKey(6), held=3, first=3)
    assert p["router"].shape == (16, 8) and p["expert_wi"].shape == (3, 16, 32)

    def loss(fn, p_):
        out, aux = fn(p_)
        return jnp.sum(out * out) + aux["moe_lb_loss"]

    dropless = lambda p_: apply_moe_dropless(p_, x, "swiglu", cfg, interpret=True)  # noqa: E731
    dense = lambda p_: apply_moe_dense(p_, x, "swiglu", cfg)  # noqa: E731
    out, aux = jax.jit(dropless)(p)
    out_d, aux_d = dense(p)
    np.testing.assert_allclose(np.asarray(out), np.asarray(out_d), atol=2e-5)
    assert float(aux["moe_lb_loss"]) == pytest.approx(float(aux_d["moe_lb_loss"]), rel=1e-6)
    g = jax.jit(jax.grad(lambda p_: loss(dropless, p_)))(p)
    g_d = jax.grad(lambda p_: loss(dense, p_))(p)
    for name in g:
        np.testing.assert_allclose(np.asarray(g[name]), np.asarray(g_d[name]), atol=1e-4,
                                   err_msg=name)


def test_dropless_counts_the_rows_routed_to_held_experts():
    cfg, p, x = held_setup(jax.random.PRNGKey(7), held=3, first=3)
    _, aux = apply_moe_dropless(p, x, "swiglu", cfg, interpret=True)
    logits = x.reshape(-1, 16) @ p["router"]
    _, idx = jax.lax.top_k(logits, 2)
    assert int(aux["moe_rows"]) == int(jnp.sum((idx >= 3) & (idx < 6))) > 0


def test_the_shares_add_up_to_the_uncut_layer():
    """E = 8 held in 4 shares of 2: what the four shares return sums to the
    output of the layer that holds all 8."""
    cfg, p, x = held_setup(jax.random.PRNGKey(8))
    def part(first, held):
        c = dataclasses.replace(cfg, n_held=held, first_held=first)
        return apply_moe_dropless(share(p, first, held), x, "swiglu", c, interpret=True)[0]

    whole = part(0, 8)
    parts = [part(2 * i, 2) for i in range(4)]
    assert all(float(jnp.max(jnp.abs(part))) > 0 for part in parts)
    np.testing.assert_allclose(np.asarray(sum(parts)), np.asarray(whole), atol=2e-5)
    whole_d, _ = apply_moe_dense(p, x, "swiglu", cfg)
    np.testing.assert_allclose(np.asarray(whole), np.asarray(whole_d), atol=2e-5)


def test_the_capacity_path_under_skewed_routing_fails_the_comparison():
    """Negative control: at capacity_factor 1.0 a router that sends most
    tokens to one expert overflows its buffer, and the dropped rows put the
    capacity path far outside the tolerance the dropless layer meets."""
    cfg, p, x = held_setup(jax.random.PRNGKey(9))
    skewed = dict(p, router=p["router"].at[:, 0].add(3.0))
    x = jnp.abs(x) + 0.5
    capped = dataclasses.replace(cfg, capacity_factor=1.0)
    out_cap, _ = apply_moe(skewed, x, "swiglu", capped)
    out_drop, _ = apply_moe_dropless(skewed, x, "swiglu", cfg, interpret=True)
    oracle, _ = apply_moe_dense(skewed, x, "swiglu", cfg)
    np.testing.assert_allclose(np.asarray(out_drop), np.asarray(oracle), atol=2e-5)
    assert float(jnp.max(jnp.abs(out_cap - oracle))) > 100 * 2e-5
