"""Device-wise GSNR statistics — the paper's Algorithm 1 mapped to TPU.

The paper synchronizes per-device gradient means g_d and their element-wise
squares with two Ring-AllReduces.  On a TPU mesh under shard_map we instead:

  * compute the local gradient of the local batch shard (pure DP over the
    "data" axis — this variant targets the replicated-params regime the
    paper ran; the sharded-params regime uses core/accumulate.py),
  * stack [g_d, g_d^2] into ONE pytree and issue a SINGLE psum — the fused
    collective halves the number of latency-bound reduction launches
    (beyond-paper optimization; ``fused=False`` reproduces the paper's
    two-collective schedule for the §Perf comparison).

Statistics are identical to k-microbatch accumulation for equal group sizes
(property-tested in tests/test_distributed.py).
"""
from __future__ import annotations

import functools
from typing import Any, Callable, Tuple

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, PartitionSpec as P

from repro.core.gsnr import GradStats

PyTree = Any
_tm = jax.tree_util.tree_map


def device_grad_stats_fn(
    loss_fn: Callable,
    mesh: Mesh,
    data_axis: str = "data",
    fused: bool = True,
    has_aux: bool = False,
    flat: bool = False,
    backend=None,
    with_noise_terms: bool = False,
) -> Callable:
    """Returns f(params, batch) -> (loss, aux, GradStats) with device-wise k
    — or (loss, aux, GradStats, terms) when ``with_noise_terms``, where terms
    is the (2,) array [|G_big|², |G_small|²] the noise-scale estimator
    consumes (core/noise_scale.py).  The two norms reduce INSIDE shard_map
    from the already-pmean'ed moment payload — they ride the existing fused
    collective (a pre-reduction sum would be wrong for |E[g]|², and a
    post-shard_map read of the replicated stats would be a second sweep),
    adding two scalars and zero collectives/launches.

    params replicated, batch sharded over ``data_axis``.

    flat=True (the flat-state path; implied by a Backend plan whose stats
    subsystem is fused): the local gradient packs into the ParamLayout flat
    buffer first, then ONE Pallas kernel (flat_stats.flat_pack_square)
    emits the collective-shaped (2, rows, LANE) [g; g²] payload in a single
    read of the buffer — no per-leaf tree copy, and no jnp
    concatenate/split round-trip either — so the fused collective is one
    pmean and mean/sq come back as views of the reduced payload, ready for
    the single-launch optimizer kernels.  fused=False still reproduces the
    paper's two-collective schedule, over flat carries.
    """
    resolved = None
    if backend is not None:
        if flat:
            raise ValueError(
                "device_grad_stats_fn: pass either backend= (flat follows the "
                "plan's stats subsystem) or flat=True, not both"
            )
        from repro.backend import resolve_backend

        resolved = resolve_backend(backend, where="device_grad_stats_fn")
        flat = resolved.fused("stats")
    k = dict(mesh.shape)[data_axis]
    gfn = jax.value_and_grad(loss_fn, has_aux=has_aux)

    def inner(params, batch):
        out, g = gfn(params, batch)
        loss, aux = out if has_aux else (out, None)
        g = _tm(lambda x: x.astype(jnp.float32), g)
        if flat:
            from repro.core.layout import ParamLayout
            from repro.kernels.flat_stats import flat_pack_square
            from repro.kernels.ops import _interp

            layout = ParamLayout.for_tree(params)
            gf = layout.pack(g, jnp.float32)
            if fused:
                # one kernel builds the [g; g²] payload in a single read of
                # gf; mean/sq are views of the reduced payload, not copies
                payload = flat_pack_square(gf, layout, interpret=_interp(resolved))
                payload = jax.lax.pmean(payload, data_axis)  # one collective
                mean, sq = payload[0], payload[1]
            else:  # paper-faithful two-collective schedule, flat carries
                mean = jax.lax.pmean(gf, data_axis)
                sq = jax.lax.pmean(jnp.square(gf), data_axis)
        elif fused:
            payload = _tm(lambda x: jnp.stack([x, jnp.square(x)]), g)
            payload = jax.lax.pmean(payload, data_axis)  # one collective
            mean = _tm(lambda s: s[0], payload)
            sq = _tm(lambda s: s[1], payload)
        else:  # paper-faithful: two all-reduces
            mean = jax.lax.pmean(g, data_axis)
            sq = jax.lax.pmean(_tm(jnp.square, g), data_axis)
        loss = jax.lax.pmean(loss, data_axis)
        if has_aux:
            aux = jax.lax.pmean(aux, data_axis)
        if with_noise_terms:
            # reduced moments are identical on every shard, so these sums
            # need no further collective; flat buffers sum exactly (zero
            # tail padding) and the tree path reduces leaf-wise
            if flat:
                g2_big = jnp.sum(jnp.square(mean))
                g2_small = jnp.sum(sq)
            else:
                g2_big = sum(jnp.sum(jnp.square(x)) for x in jax.tree_util.tree_leaves(mean))
                g2_small = sum(jnp.sum(x) for x in jax.tree_util.tree_leaves(sq))
            terms = jnp.stack([g2_big, g2_small])
        else:
            terms = jnp.zeros((2,), jnp.float32)
        return loss, aux, GradStats(mean=mean, sq_mean=sq, k=k), terms

    # k is static; keep it outside shard_map and rebuild GradStats at the end
    def inner2(params, batch):
        loss, aux, stats, terms = inner(params, batch)
        aux_out = aux if has_aux else jnp.zeros(())
        return loss, aux_out, stats.mean, stats.sq_mean, terms

    smapped = jax.shard_map(
        inner2,
        mesh=mesh,
        in_specs=(P(), P(data_axis)),
        out_specs=(P(), P(), P(), P(), P()),
        check_vma=False,
    )

    @functools.wraps(loss_fn)
    def fn(params, batch):
        loss, aux, mean, sq, terms = smapped(params, batch)
        if flat:
            from repro.core.layout import FlatBuffer, ParamLayout

            layout = ParamLayout.for_tree(params)
            mean, sq = FlatBuffer(mean, layout), FlatBuffer(sq, layout)
        stats = GradStats(mean=mean, sq_mean=sq, k=k)
        if with_noise_terms:
            return loss, (aux if has_aux else None), stats, terms
        return loss, (aux if has_aux else None), stats

    return fn
