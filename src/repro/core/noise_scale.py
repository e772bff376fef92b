"""Online gradient noise scale (critical batch size) from the GradStats carry.

McCandlish et al.'s "simple noise scale" B_simple ≈ tr(Σ)/|G|² (the gpt-neox
``gradient_noise_scale.py`` idiom, SNIPPETS §1) estimated at ZERO extra kernel
launches: the flat stats path already accumulates per-microbatch Σg and Σg²
into one packed (rows, 128) FlatBuffer each optimizer step, so both squared
gradient norms the estimator needs are plain reductions over moments that are
already materialized:

    |G_small|²  =  Σ_elem E_d[g_d²]      =  sum(sq_mean buffer)
    |G_big|²    =  Σ_elem (E_d[g_d])²    =  sum(mean buffer ** 2)

FlatBuffer tail padding is zero by layout invariant, so sums over the packed
buffer are exact — no per-leaf tree walk, no unpack.  Both totals (and their
per-leaf decomposition, for diagnostics) come out of ONE row segment-sum over
``layout.row_leaf_ids()``.  With B_small = batch/k and B_big = batch, the
unbiased estimators are

    tr(Σ) ≈ (|G_small|² - |G_big|²) / (1/B_small - 1/B_big)
    |G|²  ≈ (B_big·|G_big|² - B_small·|G_small|²) / (B_big - B_small)
    B_simple = tr(Σ) / |G|²

Per-step estimates are noisy; callers smooth tr(Σ) and |G|² with the
bias-corrected EMA below (``ema`` mirrors SNIPPETS §1 exactly) and take the
ratio of the debiased averages, never an EMA of the ratio.

Everything here is jnp on already-reduced moments — the fused train step's
pallas_call count is unchanged (asserted in tests/test_autoscale.py against
analysis/launch_manifest.py).  train/autoscale.py turns the smoothed estimate
into accumulation-count decisions.
"""
from __future__ import annotations

import math
from typing import Any, NamedTuple, Optional, Tuple

import jax
import jax.numpy as jnp

from repro.core.gsnr import GradStats

PyTree = Any
_tm = jax.tree_util.tree_map


def ema(avg, beta, yi, i):
    """Exponential moving average with bias correction (SNIPPETS §1).

    Returns (new_avg, debiased) where debiased = avg / (1 - beta**(i+1));
    ``i`` is the zero-based update index.  Works on python floats and jnp
    scalars alike.
    """
    if avg is None:
        avg = 0
    avg = beta * avg + (1 - beta) * yi
    return avg, avg / (1 - beta ** (i + 1))


class NoiseTerms(NamedTuple):
    """The two squared-norm readings the estimator consumes.

    g2_small: E_d |g_d|²  — expected squared norm of a size-B/k group gradient
    g2_big:   |E_d g_d|²  — squared norm of the accumulated full-batch gradient
    per_leaf: optional (n_leaves, 2) [g2_big, g2_small] decomposition
    """

    g2_small: jnp.ndarray
    g2_big: jnp.ndarray
    per_leaf: Optional[jnp.ndarray] = None


def noise_terms(stats: GradStats, *, per_leaf: bool = False) -> NoiseTerms:
    """Read |G_small|² and |G_big|² off a GradStats carry.

    Flat carries reduce in one pass over the packed buffer (one segment-sum
    when per_leaf; zero tail padding makes the sums exact).  Tree carries
    fall back to a leaf-wise reduction — identical values (property-tested).
    """
    if stats.sq_mean is None:
        raise ValueError("noise_terms needs second moments: GradStats.sq_mean is None")
    from repro.core.layout import is_flat

    if is_flat(stats.mean):
        mean, sq = stats.mean, stats.sq_mean
        # (2, rows): lane-reduced [mean², sq_mean] rows, one buffer sweep
        rows = jnp.stack(
            [jnp.sum(jnp.square(mean.data), axis=-1), jnp.sum(sq.data, axis=-1)]
        )
        if per_leaf:
            ids = jnp.asarray(mean.layout.row_leaf_ids())
            leaf = jax.ops.segment_sum(rows.T, ids, num_segments=mean.layout.n_leaves)
            return NoiseTerms(
                g2_small=jnp.sum(leaf[:, 1]), g2_big=jnp.sum(leaf[:, 0]), per_leaf=leaf
            )
        tot = jnp.sum(rows, axis=-1)
        return NoiseTerms(g2_small=tot[1], g2_big=tot[0])
    leaves_m = jax.tree_util.tree_leaves(stats.mean)
    leaves_s = jax.tree_util.tree_leaves(stats.sq_mean)
    g2_big = sum(jnp.sum(jnp.square(m)) for m in leaves_m)
    g2_small = sum(jnp.sum(s) for s in leaves_s)
    if per_leaf:
        leaf = jnp.stack(
            [
                jnp.stack([jnp.sum(jnp.square(m)), jnp.sum(s)])
                for m, s in zip(leaves_m, leaves_s)
            ]
        )
        return NoiseTerms(g2_small=g2_small, g2_big=g2_big, per_leaf=leaf)
    return NoiseTerms(g2_small=g2_small, g2_big=g2_big)


class NoiseScaleEstimate(NamedTuple):
    g2_small: jnp.ndarray
    g2_big: jnp.ndarray
    tr_sigma: jnp.ndarray  # unbiased estimate of tr(Σ), the gradient noise
    g2: jnp.ndarray  # unbiased estimate of |G|², the gradient signal
    b_simple: jnp.ndarray  # tr(Σ)/|G|² — the raw (unsmoothed) noise scale


def estimate_from_terms(
    g2_small, g2_big, b_small: float, b_big: float
) -> NoiseScaleEstimate:
    """Unbiased tr(Σ), |G|², B_simple from the two norm readings."""
    if not b_big > b_small > 0:
        raise ValueError(
            f"noise-scale estimator needs b_big > b_small > 0, got "
            f"b_small={b_small}, b_big={b_big} (is k >= 2?)"
        )
    tr_sigma = (g2_small - g2_big) / (1.0 / b_small - 1.0 / b_big)
    g2 = (b_big * g2_big - b_small * g2_small) / (b_big - b_small)
    b_simple = tr_sigma / jnp.where(g2 == 0, jnp.ones_like(g2), g2)
    b_simple = jnp.where(g2 == 0, jnp.full_like(b_simple, jnp.inf), b_simple)
    return NoiseScaleEstimate(
        g2_small=g2_small, g2_big=g2_big, tr_sigma=tr_sigma, g2=g2, b_simple=b_simple
    )


def estimate(stats: GradStats, b_small: float, b_big: float) -> NoiseScaleEstimate:
    """GradStats carry -> NoiseScaleEstimate (see module docstring)."""
    terms = noise_terms(stats)
    return estimate_from_terms(terms.g2_small, terms.g2_big, b_small, b_big)


class NoiseScaleState(NamedTuple):
    """Host-side EMA state: smooth tr(Σ) and |G|² separately (gpt-neox), then
    ratio the debiased averages — never EMA the per-step ratio."""

    count: int = 0
    noise_avg: float = 0.0  # biased EMA of tr(Σ)
    signal_avg: float = 0.0  # biased EMA of |G|²


class SmoothedNoiseScale(NamedTuple):
    noise: float  # debiased EMA of tr(Σ)
    signal: float  # debiased EMA of |G|²
    b_simple: float  # ratio of the two (nan until signal is usable)


def init_noise_state() -> NoiseScaleState:
    return NoiseScaleState()


def update_noise_state(
    state: NoiseScaleState, tr_sigma: float, g2: float, beta: float = 0.9
) -> Tuple[NoiseScaleState, SmoothedNoiseScale]:
    """One EMA step; returns (new_state, smoothed readings)."""
    noise_avg, noise_hat = ema(state.noise_avg, beta, float(tr_sigma), state.count)
    signal_avg, signal_hat = ema(state.signal_avg, beta, float(g2), state.count)
    new = NoiseScaleState(state.count + 1, noise_avg, signal_avg)
    if signal_hat > 0 and math.isfinite(signal_hat) and math.isfinite(noise_hat):
        b_simple = noise_hat / signal_hat
    else:
        b_simple = float("nan")
    return new, SmoothedNoiseScale(noise=noise_hat, signal=signal_hat, b_simple=b_simple)
