"""GSNR: gradient signal-to-noise ratio (paper §3.1, §4.1).

Pipeline (paper eq. 7 -> 2 -> 8 -> 9):

    variance   sigma^2 = E_d[g_d^2] - (E_d[g_d])^2          (k groups)
    gsnr       r       = g_mean^2 / sigma^2
    normalize  r      <- r / mean_layer(r)    (per parameter tensor)
    clip       r      <- clip(r, gamma, 1)

All element-wise except the per-layer mean — which is why GSNR computes
directly on FSDP-sharded (reduce-scattered) gradient shards on TPU: only the
scalar layer mean needs a cross-shard reduction (DESIGN.md §3).

``GradStats`` carries the two raw moments; everything downstream is pure.
"""
from __future__ import annotations

from typing import Any, NamedTuple

import jax
import jax.numpy as jnp

PyTree = Any


class GradStats(NamedTuple):
    """Per-parameter first/second moments of the k group gradient means.

    mean:    E_d[g_d]        — the usual (all-reduced) gradient
    sq_mean: E_d[g_d ⊗ g_d]  — mean of element-wise squared group gradients
    k:       number of groups (devices / microbatches)

    On the flat-state path (a Backend plan with fused stats) mean/sq_mean
    are FlatBuffer nodes (core/layout.py) — already contiguous for the
    single-launch optimizer kernels.  ``as_tree()`` unpacks for the
    per-layer jnp pipeline below.
    """

    mean: PyTree
    sq_mean: PyTree
    k: int

    def as_tree(self) -> "GradStats":
        """GradStats with pytree-valued moments (no-op if already trees)."""
        from repro.core.layout import is_flat

        if not is_flat(self.mean):
            return self
        sq = self.sq_mean.unpack() if is_flat(self.sq_mean) else self.sq_mean
        return self._replace(mean=self.mean.unpack(), sq_mean=sq)


def variance(stats: GradStats) -> PyTree:
    """sigma^2 = E[g_d^2] - E[g_d]^2, clipped at 0 (paper eq. 7)."""
    stats = stats.as_tree()
    return jax.tree_util.tree_map(
        lambda s, m: jnp.maximum(s - jnp.square(m), 0.0), stats.sq_mean, stats.mean
    )


def raw_gsnr(stats: GradStats, eps: float = 1e-12) -> PyTree:
    """r = g^2 / sigma^2 (paper eq. 2 with the batch estimator of eq. 7)."""
    stats = stats.as_tree()
    var = variance(stats)
    return jax.tree_util.tree_map(
        lambda m, v: jnp.square(m) / (v + eps), stats.mean, var
    )


def normalize_per_layer(r: PyTree) -> PyTree:
    """r / mean(r) per parameter tensor ("layer", paper eq. 8)."""
    return jax.tree_util.tree_map(lambda x: x / jnp.maximum(jnp.mean(x), 1e-30), r)


def clip_ratio(r: PyTree, gamma: float) -> PyTree:
    """clip to [gamma, 1] (paper eq. 9); gamma=1 reduces VRGD to the base opt."""
    return jax.tree_util.tree_map(lambda x: jnp.clip(x, gamma, 1.0), r)


def gsnr_scale(stats: GradStats, gamma: float = 0.1, eps: float = 1e-12) -> PyTree:
    """Full pipeline: the element-wise LR multiplier r(theta) in [gamma, 1]."""
    return clip_ratio(normalize_per_layer(raw_gsnr(stats, eps)), gamma)


def gsnr_summary(scale: PyTree, gamma: float = 0.1) -> dict:
    """Scalar diagnostics for logging: mean/min/fraction clipped at the floor."""
    leaves = [x.reshape(-1) for x in jax.tree_util.tree_leaves(scale)]
    flat = jnp.concatenate(leaves) if leaves else jnp.zeros((1,))
    return {
        "gsnr/mean": jnp.mean(flat),
        "gsnr/min": jnp.min(flat),
        "gsnr/frac_floor": jnp.mean((flat <= gamma * (1 + 1e-5)).astype(jnp.float32)),
    }


def stats_summary(stats: GradStats, gamma: float = 0.1, eps: float = 1e-12) -> dict:
    """``gsnr_summary(gsnr_scale(stats, gamma, eps), gamma)``.

    Flat moments are summarized where they lie: the per-leaf means of r are
    a segment sum over the rows, and the zero tail padding is masked out of
    the mean, the min and the floor share.  Moments sharded by rows (the
    data-axis statistics' reduce-scatter, core/distributed.py) are then
    never gathered back into leaves just to be logged.
    """
    from repro.core.layout import LANE, is_flat

    if not is_flat(stats.mean):
        return gsnr_summary(gsnr_scale(stats, gamma, eps), gamma)
    layout = stats.mean.layout
    m, s = stats.mean.data, stats.sq_mean.data
    r = jnp.square(m) / (jnp.maximum(s - jnp.square(m), 0.0) + eps)  # raw_gsnr
    ids = jnp.asarray(layout.row_leaf_ids())
    sizes = jnp.asarray(layout.sizes, jnp.int32)
    leaf_mean = jax.ops.segment_sum(
        jnp.sum(r, axis=1), ids, num_segments=layout.n_leaves
    ) / sizes.astype(jnp.float32)
    scale = jnp.clip(r / jnp.maximum(leaf_mean, 1e-30)[ids][:, None], gamma, 1.0)
    # an element's index inside its leaf: past the leaf's size is padding
    row_in_leaf = jnp.arange(layout.n_rows) - jnp.asarray(layout.row_offsets, jnp.int32)[ids]
    live = row_in_leaf[:, None] * LANE + jnp.arange(LANE) < sizes[ids][:, None]
    n = float(sum(layout.sizes))
    return {
        "gsnr/mean": jnp.sum(jnp.where(live, scale, 0.0)) / n,
        "gsnr/min": jnp.min(jnp.where(live, scale, jnp.inf)),
        "gsnr/frac_floor": jnp.sum(live & (scale <= gamma * (1 + 1e-5))) / n,
    }
