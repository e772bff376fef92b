"""Device-wise GSNR statistics — the paper's Algorithm 1 mapped to TPU.

The paper synchronizes per-device gradient means g_d and their element-wise
squares with two Ring-AllReduces.  On a TPU mesh under shard_map we instead:

  * compute the local gradient of the local batch shard (pure DP over the
    "data" axis — this variant targets the replicated-params regime the
    paper ran; the sharded-params regime uses core/accumulate.py),
  * stack [g_d, g_d^2] into ONE payload and issue a SINGLE collective — the
    fused collective halves the number of latency-bound reduction launches
    (beyond-paper optimization; ``fused=False`` reproduces the paper's
    two-collective schedule for the §Perf comparison),
  * on the flat path, where the flat optimizer state is split by rows over
    the data axis (sharding/rules.py ``flat_buffer_pspec``), reduce-SCATTER
    g_d and g_d^2 into those row shards instead of all-reducing them: each
    device receives the summed statistics of its own rows only, which
    halves the bytes on the links and leaves the clip, the norm and the
    per-shard update a quarter of the buffer each on four devices.

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
from repro.core.layout import LANE, FlatBuffer, ParamLayout

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
    rules=None,
) -> Callable:
    """Returns f(params, batch) -> (loss, aux, GradStats) with device-wise k
    — or (loss, aux, GradStats, terms) when ``with_noise_terms``, where terms
    is the (2,) array [|G_big|², |G_small|²] the noise-scale estimator
    consumes (core/noise_scale.py).  The two norms reduce INSIDE shard_map
    from the already-reduced moment payload — they ride the existing fused
    collective (a pre-reduction sum would be wrong for |E[g]|², and a
    post-shard_map read of the stats would be a second sweep): replicated
    moments sum locally, row-scattered ones sum their shard and add the two
    scalars with one psum.

    params replicated, batch sharded over ``data_axis``.

    flat=True (the flat-state path; implied by a Backend plan whose stats
    subsystem is fused): the local gradient packs into the ParamLayout flat
    buffer first.  Where the sharding rules of the flat state (``rules``:
    the train step passes its FlatSpmd plan's; by default the active ones
    for ``mesh`` when this is built, else fresh defaults) split its rows
    over ``data_axis`` alone, g and g² are each reduce-scattered into that
    split and divided by k: mean/sq come back as FlatBuffers sharded
    exactly as the state, so the per-shard update reads them where they
    land.  Both schedules take these two collectives: the TPU compiler
    keeps a reduce-scatter only over the major dimension, so one collective
    would need a shard-major [g; g²] payload, which XLA builds in two
    passes over the buffer (a shard, 651,104 rows for BERT-large on four
    devices, ends inside a 64-row kernel block); that costs more than the
    second collective (on four TPU v5e chips: 14.0 ms a step for the
    payload, 4.05 ms for g² alone, the exchange ~32.5 ms either way).
    Elsewhere (rows replicated, or split over other axes too) the
    statistics stay replicated: ONE Pallas kernel
    (flat_stats.flat_pack_square) emits the (2, rows, LANE) payload in a
    single read of the buffer and one pmean reduces it, mean/sq views of
    the reduced payload; fused=False takes two pmeans.
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
    if rules is None:
        from repro.sharding.rules import rules_for  # sharding imports core

        rules = rules_for(mesh)
    k = dict(mesh.shape)[data_axis]
    gfn = jax.value_and_grad(loss_fn, has_aux=has_aux)

    def inner(params, batch, layout, scatter):
        out, g = gfn(params, batch)
        loss, aux = out if has_aux else (out, None)
        g = _tm(lambda x: x.astype(jnp.float32), g)
        if flat:
            from repro.kernels.flat_stats import flat_pack_square
            from repro.kernels.ops import _interp

            gf = layout.pack(g, jnp.float32)
            if scatter:  # this device's row shard of the sums, either schedule
                mean = jax.lax.psum_scatter(gf, data_axis, tiled=True) / k
                sq = jax.lax.psum_scatter(jnp.square(gf), data_axis, tiled=True) / k
            elif fused:
                # one kernel builds the [g; g²] payload in a single read of
                # gf; mean/sq are views of the reduced payload, not copies
                payload = flat_pack_square(gf, interpret=_interp(resolved))
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
            # flat buffers sum exactly (zero tail padding) and the tree path
            # reduces leaf-wise; replicated moments need no further
            # collective, row shards add their partial sums
            if flat:
                terms = jnp.stack([jnp.sum(jnp.square(mean)), jnp.sum(sq)])
                if scatter:
                    terms = jax.lax.psum(terms, data_axis)
            else:
                g2_big = sum(jnp.sum(jnp.square(x)) for x in jax.tree_util.tree_leaves(mean))
                g2_small = sum(jnp.sum(x) for x in jax.tree_util.tree_leaves(sq))
                terms = jnp.stack([g2_big, g2_small])
        else:
            terms = jnp.zeros((2,), jnp.float32)
        aux_out = aux if has_aux else jnp.zeros(())
        # k is static; keep it outside shard_map and rebuild GradStats after
        return loss, aux_out, mean, sq, terms

    @functools.wraps(loss_fn)
    def fn(params, batch):
        layout = ParamLayout.for_tree(params) if flat else None
        rows = rules.flat_buffer_pspec((layout.n_rows, LANE)) if flat else P()
        scatter = rows == P(data_axis, None)
        moments = rows if scatter else P()
        smapped = jax.shard_map(
            functools.partial(inner, layout=layout, scatter=scatter),
            mesh=mesh,
            in_specs=(P(), P(data_axis)),
            out_specs=(P(), P(), moments, moments, P()),
            check_vma=False,
        )
        loss, aux, mean, sq, terms = smapped(params, batch)
        if flat:
            mean, sq = FlatBuffer(mean, layout), FlatBuffer(sq, layout)
        stats = GradStats(mean=mean, sq_mean=sq, k=k)
        if with_noise_terms:
            return loss, (aux if has_aux else None), stats, terms
        return loss, (aux if has_aux else None), stats

    return fn
