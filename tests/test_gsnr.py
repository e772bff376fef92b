"""Unit + property tests for the GSNR pipeline (paper eq. 2/7/8/9).

The property sweeps are dependency-free seeded loops (see tests/oracle.py's
``property_cases`` for the kernel-side equivalent): hypothesis is NOT
required for this suite to collect or run.
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import GradStats, clip_ratio, gsnr_scale, normalize_per_layer, raw_gsnr, variance


def make_stats(mean, extra_sq, k=8):
    mean = jnp.asarray(mean)
    sq = jnp.square(mean) + jnp.asarray(extra_sq)  # guarantees var >= 0 pre-clip
    return GradStats(mean={"w": mean}, sq_mean={"w": sq}, k=k)


def tree_cases(n_cases=50, seed=0):
    """Seeded (mean, extra_sq) draws: sizes 3..40, mean in [-3,3], var in [0,9]."""
    rng = np.random.RandomState(seed)
    for _ in range(n_cases):
        n = rng.randint(3, 41)
        mean = rng.uniform(-3, 3, n).astype(np.float32)
        extra = rng.uniform(0, 9, n).astype(np.float32)
        yield mean, extra


def test_variance_nonnegative():
    for mean, extra in tree_cases():
        stats = make_stats(mean, extra)
        var = variance(stats)["w"]
        assert np.all(np.asarray(var) >= 0)
        np.testing.assert_allclose(np.asarray(var), extra, rtol=1e-4, atol=1e-5)


def test_scale_bounds():
    rng = np.random.RandomState(1)
    for mean, extra in tree_cases(seed=2):
        gamma = float(rng.uniform(0.01, 0.9))
        stats = make_stats(mean, extra)
        scale = gsnr_scale(stats, gamma=gamma)["w"]
        s = np.asarray(scale)
        assert np.all(s >= gamma - 1e-6)
        assert np.all(s <= 1.0 + 1e-6)


def test_normalized_mean_is_one():
    for mean, extra in tree_cases(seed=3):
        if float(np.max(np.abs(mean))) <= 1e-3:  # degenerate all-zero grad
            continue
        stats = make_stats(mean, extra)
        r = normalize_per_layer(raw_gsnr(stats))["w"]
        m = float(np.mean(np.asarray(r)))
        assert m == pytest.approx(1.0, rel=1e-3)


def test_gamma_one_collapses_to_identity_scale():
    """gamma=1 clips r to exactly 1 -> VRGD == base optimizer (paper §7.3)."""
    stats = make_stats(np.random.RandomState(0).randn(32).astype(np.float32),
                       np.random.RandomState(1).rand(32).astype(np.float32))
    scale = gsnr_scale(stats, gamma=1.0)["w"]
    np.testing.assert_allclose(np.asarray(scale), 1.0)


def test_zero_variance_largest_coordinate_full_rate():
    """Identical group gradients (no noise): r -> g^2/eps, so after per-layer
    normalization the ranking follows |g| — the largest coordinate gets the
    full rate, everything stays >= gamma (no coordinate dies)."""
    mean = jnp.array([0.5, -1.0, 2.0])
    stats = GradStats(mean={"w": mean}, sq_mean={"w": jnp.square(mean)}, k=4)
    scale = np.asarray(gsnr_scale(stats, gamma=0.1)["w"])
    assert scale[2] == pytest.approx(1.0)
    assert np.all(scale >= 0.1 - 1e-6)
    assert scale[1] > scale[0]  # ordering follows |g|


def test_noisy_coordinate_damped():
    """A coordinate with tiny signal / huge noise hits the gamma floor."""
    mean = jnp.array([1.0, 1.0, 1e-4])
    sq = jnp.square(mean) + jnp.array([1e-6, 1e-6, 10.0])
    stats = GradStats(mean={"w": mean}, sq_mean={"w": sq}, k=8)
    scale = gsnr_scale(stats, gamma=0.1)["w"]
    assert float(scale[2]) == pytest.approx(0.1)
    assert float(scale[0]) == pytest.approx(1.0)


def test_clip_ratio_range():
    r = {"a": jnp.array([0.0, 0.05, 0.5, 3.0])}
    out = clip_ratio(r, 0.1)["a"]
    np.testing.assert_allclose(np.asarray(out), [0.1, 0.1, 0.5, 1.0])


def test_multi_layer_normalization_independent():
    """Each tensor ("layer") normalizes independently (paper eq. 8)."""
    stats = GradStats(
        mean={"a": jnp.array([1.0, 2.0]), "b": jnp.array([10.0, 20.0, 30.0])},
        sq_mean={"a": jnp.array([2.0, 5.0]), "b": jnp.array([101.0, 402.0, 903.0])},
        k=8,
    )
    r = normalize_per_layer(raw_gsnr(stats))
    assert float(jnp.mean(r["a"])) == pytest.approx(1.0, rel=1e-4)
    assert float(jnp.mean(r["b"])) == pytest.approx(1.0, rel=1e-4)


@pytest.mark.parametrize("shapes", [
    {"w": (3,)},  # one leaf, nearly all padding
    {"a": (40, 24), "b": (24,), "c": ()},  # several leaves and a scalar
    {"e": (130, 64), "f": (64 * 128 + 5,), "g": (7, 3)},  # past one 64-row block, ragged tails
], ids=("one", "several", "blocks"))
@pytest.mark.parametrize("gamma", [0.1, 0.5, 1.0])
def test_flat_summary_equals_the_tree_pipelines(shapes, gamma):
    """stats_summary on flat moments (segment sums over rows, tail padding
    masked) reads what gsnr_summary(gsnr_scale(...)) reads on the tree."""
    from repro.core import gsnr_summary, stats_summary
    from repro.core.layout import FlatBuffer, ParamLayout

    rng = np.random.RandomState(len(shapes))
    mean = {n: jnp.asarray(rng.uniform(-3, 3, s), jnp.float32) for n, s in shapes.items()}
    # a fifth of the coordinates noiseless (r at its largest), the rest noisy
    extra = {n: jnp.asarray(rng.uniform(0, 9, s) * (rng.uniform(size=s) > 0.2), jnp.float32)
             for n, s in shapes.items()}
    tree = GradStats(mean=mean, sq_mean=jax.tree_util.tree_map(
        lambda m, e: jnp.square(m) + e, mean, extra), k=4)
    layout = ParamLayout.for_tree(mean)
    assert layout.n_rows * 128 > sum(layout.sizes)  # padding is there to mask
    flat = GradStats(mean=FlatBuffer(layout.pack(tree.mean, jnp.float32), layout),
                     sq_mean=FlatBuffer(layout.pack(tree.sq_mean, jnp.float32), layout), k=4)
    got = stats_summary(flat, gamma)
    want = gsnr_summary(gsnr_scale(tree, gamma), gamma)
    assert got.keys() == want.keys()
    for key in want:
        np.testing.assert_allclose(np.asarray(got[key]), np.asarray(want[key]),
                                   rtol=1e-5, atol=1e-7, err_msg=key)
