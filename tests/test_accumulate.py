"""Microbatch gradient-moment accumulation (paper's k groups)."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import GradStats, grad_stats, split_batch


def loss_fn(params, batch):
    x, y = batch
    return jnp.mean((x @ params["w"] - y) ** 2)


def setup(n=64, d=6, seed=0):
    key = jax.random.PRNGKey(seed)
    x = jax.random.normal(key, (n, d))
    w_true = jnp.linspace(1.0, 2.0, d)
    y = x @ w_true
    return {"w": jnp.zeros(d)}, (x, y)


def test_mean_equals_full_batch_gradient():
    params, batch = setup()
    for k in (2, 4, 8):
        loss, _, stats = grad_stats(loss_fn, params, batch, k)
        full = jax.grad(loss_fn)(params, batch)
        np.testing.assert_allclose(np.asarray(stats.mean["w"]), np.asarray(full["w"]), rtol=1e-4)


def test_sq_mean_matches_numpy():
    params, batch = setup()
    k = 8
    _, _, stats = grad_stats(loss_fn, params, batch, k)
    x, y = batch
    gs = []
    for i in range(k):
        sl = slice(i * 8, (i + 1) * 8)
        gs.append(np.asarray(jax.grad(loss_fn)(params, (x[sl], y[sl]))["w"]))
    gs = np.stack(gs)
    np.testing.assert_allclose(np.asarray(stats.sq_mean["w"]), (gs**2).mean(0), rtol=1e-4)
    var = np.asarray(stats.sq_mean["w"]) - np.asarray(stats.mean["w"]) ** 2
    np.testing.assert_allclose(var, gs.var(0), rtol=1e-3, atol=1e-7)


def test_loss_is_mean_over_groups():
    params, batch = setup()
    loss, _, _ = grad_stats(loss_fn, params, batch, 4)
    # groups have equal size, so mean of group losses == full-batch loss
    assert float(loss) == pytest.approx(float(loss_fn(params, batch)), rel=1e-5)


def test_split_batch_rejects_indivisible():
    with pytest.raises(ValueError):
        split_batch({"x": jnp.zeros((10, 2))}, 3)


def test_has_aux_path():
    def lf(params, batch):
        x, y = batch
        loss = jnp.mean((x @ params["w"] - y) ** 2)
        return loss, {"l2": jnp.sum(params["w"] ** 2), "n": jnp.float32(x.shape[0])}

    params, batch = setup()
    loss, aux, stats = grad_stats(lf, params, batch, 4, has_aux=True)
    assert set(aux) == {"l2", "n"}
    assert float(aux["n"]) == 16.0  # per-microbatch size, averaged
    assert isinstance(stats, GradStats)


def test_identical_microbatches_zero_variance():
    params, _ = setup()
    x = jnp.ones((4, 6))
    y = jnp.ones((4,))
    xx = jnp.tile(x, (4, 1))
    yy = jnp.tile(y, (4,))
    _, _, stats = grad_stats(loss_fn, params, (xx, yy), 4)
    var = np.asarray(stats.sq_mean["w"]) - np.asarray(stats.mean["w"]) ** 2
    np.testing.assert_allclose(var, 0.0, atol=1e-5)
