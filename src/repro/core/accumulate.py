"""k-group gradient moment accumulation (paper's `k`, the "device number").

The paper equates k with gradient-accumulation groups (Appendix Table 9:
"Acc-steps in NVIDIA's code is equivalent to device number k"), and §7.3
shows the optimum k is a statistical choice (~[32, 256]) independent of the
physical device count.  This module computes GradStats by scanning k
microbatches — sharding-agnostic: each microbatch gradient is itself a fully
pjit-sharded computation, so this composes with FSDP/TP/EP unchanged.

The device-wise variant (paper Alg. 1 literally) lives in core/distributed.py;
both produce identical statistics for equal group sizes (tested).
"""
from __future__ import annotations

from typing import Any, Callable, Optional, Tuple

import jax
import jax.numpy as jnp

from repro.backend import Backend, resolve_backend
from repro.core.gsnr import GradStats

PyTree = Any
_tm = jax.tree_util.tree_map


def split_batch(batch: PyTree, k: int) -> PyTree:
    """Reshape every leaf (B, ...) -> (k, B//k, ...).

    Raises a loud ValueError when the batch size doesn't divide into k
    accumulation groups — with both numbers and the remainder, since this is
    the first thing a bad autoscale proposal or hand-edited k hits.
    """
    if k < 1:
        raise ValueError(f"split_batch: k={k} must be a positive group count")
    leaves = jax.tree_util.tree_leaves(batch)
    if not leaves:
        return batch
    b = leaves[0].shape[0]
    if b % k:
        raise ValueError(
            f"split_batch: batch_size={b} is not divisible by k={k} "
            f"accumulation groups (remainder {b % k}). Pick k from the "
            f"divisors of the batch size — "
            f"repro.train.autoscale.AutoscalePolicy.feasible_ks({b}) "
            f"proposes only those."
        )

    def one(x):
        if x.shape[0] != b:
            raise ValueError(
                f"split_batch: ragged batch — leaf with leading dim "
                f"{x.shape[0]} alongside {b}"
            )
        return x.reshape(k, b // k, *x.shape[1:])

    return _tm(one, batch)


def grad_stats(
    loss_fn: Callable,
    params: PyTree,
    batch: PyTree,
    k: int,
    *,
    has_aux: bool = False,
    backend: Optional[Backend] = None,
    spmd=None,
    use_pallas=None,
) -> Tuple[jnp.ndarray, Any, GradStats]:
    """Accumulate (mean loss, aux, GradStats) over k microbatches.

    loss_fn(params, microbatch) -> loss  (or (loss, aux) when has_aux).

    A sequential lax.scan over the k microbatches (the paper's gradient
    accumulation): memory holds the two f32 moment carries, Σg and Σg²,
    whatever k is.

    backend: the execution plan (repro.backend.Backend; the deprecated
    boolean keyword maps through the shim there, warning once).  With a
    fused ``stats`` subsystem the carries live as ParamLayout flat buffers
    (core/layout.py): each microbatch's moment update (g_sum += g;
    g2_sum += g²) is ONE fused pallas_call over the flat carries
    (kernels/flat_stats.py) — the gradient tree is packed once per
    microbatch — and the terminal /k normalize is a second single call.
    The returned GradStats then carries FlatBuffers, already contiguous for
    the single-launch optimizer kernels; statistics are identical to the
    jnp path (oracle-tested).  spmd (Backend.shard) runs those flat sweeps
    per-shard under shard_map on FSDP-sharded buffer rows.
    """
    bk = resolve_backend(backend, use_pallas=use_pallas, where="grad_stats")
    fused_stats = bk.fused("stats")
    mb = split_batch(batch, k)
    gfn = jax.value_and_grad(loss_fn, has_aux=has_aux)
    if fused_stats:
        from repro.core.layout import ParamLayout
        from repro.kernels import ops as kops

        layout = ParamLayout.for_tree(params)

    def step(carry, microbatch):
        loss_sum, aux_sum, g_sum, g2_sum = carry
        out, g = gfn(params, microbatch)
        loss, aux = out if has_aux else (out, aux_sum)
        g = _tm(lambda x: x.astype(jnp.float32), g)
        aux_new = _tm(jnp.add, aux_sum, aux) if has_aux else aux_sum
        if fused_stats:
            g_sum, g2_sum = kops.moments_accum_flat(
                g_sum, g2_sum, g, layout, backend=bk, spmd=spmd
            )
        else:
            g_sum = _tm(jnp.add, g_sum, g)
            g2_sum = _tm(lambda a, x: a + jnp.square(x), g2_sum, g)
        return (loss_sum + loss, aux_new, g_sum, g2_sum), None

    aux0 = None
    if has_aux:
        # probe aux structure abstractly (zeros of the right shapes)
        aux_shape = jax.eval_shape(lambda p, b: loss_fn(p, b)[1], params, _tm(lambda x: x[0], mb))
        aux0 = _tm(lambda s: jnp.zeros(s.shape, s.dtype), aux_shape)
    if fused_stats:
        g0, g20 = kops.moments_init_flat(layout)
    else:
        g0 = _tm(lambda p: jnp.zeros(p.shape, jnp.float32), params)
        g20 = _tm(jnp.zeros_like, g0)
    (loss_sum, aux_sum, g_sum, g2_sum), _ = jax.lax.scan(
        step, (jnp.zeros((), jnp.float32), aux0, g0, g20), mb
    )
    inv = 1.0 / k
    if fused_stats:
        stats = kops.moments_finalize_flat(g_sum, g2_sum, k, layout, backend=bk, spmd=spmd)
    else:
        stats = GradStats(
            mean=_tm(lambda x: x * inv, g_sum), sq_mean=_tm(lambda x: x * inv, g2_sum), k=k
        )
    aux_out = _tm(lambda x: x * inv, aux_sum) if has_aux else None
    return loss_sum * inv, aux_out, stats


def grad_only(loss_fn: Callable, params: PyTree, batch: PyTree, *, has_aux: bool = False):
    """Plain single-pass gradient (baseline optimizers; no moment of squares)."""
    gfn = jax.value_and_grad(loss_fn, has_aux=has_aux)
    out, g = gfn(params, batch)
    loss, aux = out if has_aux else (out, None)
    return loss, aux, _tm(lambda x: x.astype(jnp.float32), g)
