"""Grouped matmul over rows sorted by group: the dropless expert layer's
kernel, megablox's differentiable ``gmm`` (shipped with JAX).

``grouped_matmul(lhs, rhs, group_sizes)``: lhs (M, K) holds its rows sorted
by group, rhs (G, K, N) one matrix a group, group_sizes (G,) int32 summing
to at most M; the rows of group g are multiplied by rhs[g].  The kernel's
grid visits only the row tiles that hold a group's rows, so its work follows
sum(group_sizes), not M.  Rows past sum(group_sizes) are not computed:
their output is undefined and the caller masks it, before the call (so
that no cotangent reaches them) and after.

megablox asks :func:`tiling` for the tiles of each of its calls, the
forward's and the backward's ``gmm`` and ``tgmm``, so each call takes tiles
fitted to its own K and N: the largest multiple of 128 that divides the
dimension, up to ``TILE_CAP`` (a dimension that is no multiple of 128 is one
tile).
"""
from __future__ import annotations

import jax.numpy as jnp
from jax.experimental.pallas.ops.tpu import megablox

ROW_TILE = 512
TILE_CAP = 1024


def _tile(dim: int) -> int:
    if dim % 128:
        return dim
    t = min(TILE_CAP, dim) // 128 * 128
    while dim % t:
        t -= 128
    return t


def tiling(m: int, k: int, n: int):
    """(row, K, N) tiles of one megablox call of shape (m, k) x (k, n)."""
    return min(ROW_TILE, m), _tile(k), _tile(n)


def grouped_matmul(lhs, rhs, group_sizes, interpret: bool = False):
    """(M, N) of lhs (M, K) sorted by group times rhs (G, K, N), in lhs's
    dtype with float32 accumulation."""
    m = lhs.shape[0]
    pad = (-m) % min(ROW_TILE, m)  # megablox takes whole row tiles
    if pad:
        lhs = jnp.pad(lhs, ((0, pad), (0, 0)))
    out = megablox.gmm(lhs, rhs, group_sizes, lhs.dtype, tiling, None, None, False, interpret)
    return out[:m]
