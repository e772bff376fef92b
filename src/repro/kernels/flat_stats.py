"""Pallas TPU kernels: flat-buffer GradStats accumulation — ONE pallas_call
per scan step / finalize over the whole parameter set.

PR 1's fused accumulation (kernels/grad_stats.py) removed the double HBM
sweep of the scan body but still launched one kernel per pytree leaf with a
pad/unpad round-trip each.  Here the carry (g_sum, g2_sum) lives in the
ParamLayout flat ``(n_rows, LANE)`` buffer for the whole scan, the incoming
gradient tree is packed once per microbatch (core/layout.py), and each of

  * ``flat_moments_accum``     (scan body:  g_sum += g; g2_sum += g*g)
  * ``flat_moments_finalize``  (terminal /k normalize of both moments)
  * ``flat_vmap_moments``      (batched (k, n_rows, LANE) stack -> moments)

is a single ``pallas_call`` with a grid over row-blocks.  The kernel bodies
for accum/finalize are shared with the per-leaf path (grad_stats.py), which
stays as the differential oracle reference.

``flat_vmap_moments`` covers the vmap stats method (ROADMAP item: it used to
ignore the fused-stats backend): the (k, param) gradient stack reduces to
(mean, sq_mean) in one kernel, grid (n_blocks, k) with k minor so the output
block revisits are consecutive (the standard accumulate-in-VMEM pattern).

``flat_g_accum`` is the g-only variant for the amortized-GSNR "stale" scan
path (squares=False): no Σg² stream, the mean-gradient carry stays a flat
buffer for the whole scan instead of a jnp tree.

The scan-path sweeps (``flat_moments_accum`` / ``flat_g_accum`` /
``flat_moments_finalize``) derive their grids from the LOCAL operand shape
(``gs.shape[0] // block_rows``), not ``layout.n_blocks`` — they are purely
element-wise, so those very wrappers run per-shard under shard_map
(backend.FlatSpmd) on FSDP row slices of the buffer, with no other change.
``flat_vmap_moments`` is the exception: its grid still comes from the full
``layout`` geometry and it has no per-shard wrapper (the vmap stats path
keeps the gathered one-launch reduction).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from repro.backend import resolve_interpret
from repro.core.layout import LANE, ParamLayout
from repro.kernels.grad_stats import _accum_kernel, _finalize_kernel


def _blk(layout: ParamLayout):
    return pl.BlockSpec((layout.block_rows, LANE), lambda i: (i, 0))


def _local_blocks(x, layout: ParamLayout) -> int:
    rows = x.shape[0]
    if rows % layout.block_rows:
        raise ValueError(
            f"flat carry has {rows} rows, not a multiple of block_rows="
            f"{layout.block_rows} — shard count must divide n_blocks"
        )
    return rows // layout.block_rows


@functools.partial(jax.jit, static_argnames=("layout", "interpret"))
def flat_moments_accum(gs, g2s, g, layout: ParamLayout, interpret: bool | None = None):
    """One scan-body update of both flat moment carries: a single launch."""
    blk = _blk(layout)
    sds = jax.ShapeDtypeStruct(gs.shape, jnp.float32)
    return pl.pallas_call(
        _accum_kernel,
        grid=(_local_blocks(gs, layout),),
        in_specs=[blk, blk, blk],
        out_specs=(blk, blk),
        out_shape=(sds, sds),
        input_output_aliases={0: 0, 1: 1},  # the carries update in place
        interpret=resolve_interpret(interpret),
    )(gs, g2s, g)


def _g_accum_kernel(gs_ref, g_ref, gs_out):
    gs_out[...] = gs_ref[...] + g_ref[...].astype(jnp.float32)


@functools.partial(jax.jit, static_argnames=("layout", "interpret"))
def flat_g_accum(gs, g, layout: ParamLayout, interpret: bool | None = None):
    """One scan-body update of the g-only flat carry (stale-GSNR steps):
    a single launch, no Σg² stream."""
    blk = _blk(layout)
    sds = jax.ShapeDtypeStruct(gs.shape, jnp.float32)
    return pl.pallas_call(
        _g_accum_kernel,
        grid=(_local_blocks(gs, layout),),
        in_specs=[blk, blk],
        out_specs=blk,
        out_shape=sds,
        input_output_aliases={0: 0},
        interpret=resolve_interpret(interpret),
    )(gs, g)


@functools.partial(jax.jit, static_argnames=("layout", "interpret"))
def flat_moments_finalize(gs, g2s, k, layout: ParamLayout, interpret: bool | None = None):
    """Terminal /k normalize of the flat carries: a single launch.

    k may be traced.  Returns flat (mean, sq_mean) f32 buffers.
    """
    inv = (1.0 / jnp.asarray(k, jnp.float32)).reshape(1, 1)
    blk = _blk(layout)
    sds = jax.ShapeDtypeStruct(gs.shape, jnp.float32)
    return pl.pallas_call(
        _finalize_kernel,
        grid=(_local_blocks(gs, layout),),
        in_specs=[blk, blk, pl.BlockSpec((1, 1), lambda i: (0, 0))],
        out_specs=(blk, blk),
        out_shape=(sds, sds),
        input_output_aliases={0: 0, 1: 1},  # the sums become the moments
        interpret=resolve_interpret(interpret),
    )(gs, g2s, inv)


def _pack_square_kernel(g_ref, out_ref):
    g = g_ref[...].astype(jnp.float32)
    out_ref[0] = g
    out_ref[1] = g * g


@functools.partial(jax.jit, static_argnames=("layout", "interpret"))
def flat_pack_square(gf, layout: ParamLayout, interpret: bool | None = None):
    """(rows, LANE) flat gradient -> (2, rows, LANE) [g; g²] payload: one
    launch, ONE read of gf per block.

    The output is the COLLECTIVE-SHAPED carry device_grad_stats_fn pmean's
    across the data axis (mean = payload[0], sq = payload[1] are views, not
    copies) — replacing the jnp concatenate([gf, square(gf)]) / split
    round-trip that re-read gf and materialized two extra copies of the
    buffer per step.  Grid derives from the LOCAL rows (_local_blocks) so
    the same wrapper runs per-shard under shard_map."""
    blk = _blk(layout)
    return pl.pallas_call(
        _pack_square_kernel,
        grid=(_local_blocks(gf, layout),),
        in_specs=[blk],
        out_specs=pl.BlockSpec((2, layout.block_rows, LANE), lambda i: (0, i, 0)),
        out_shape=jax.ShapeDtypeStruct((2,) + tuple(gf.shape), jnp.float32),
        interpret=resolve_interpret(interpret),
    )(gf)


def _vmap_kernel(g_ref, mean_ref, sq_ref, *, nk: int, inv: float):
    j = pl.program_id(1)

    @pl.when(j == 0)
    def _init():
        mean_ref[...] = jnp.zeros_like(mean_ref)
        sq_ref[...] = jnp.zeros_like(sq_ref)

    g = g_ref[0].astype(jnp.float32)
    mean_ref[...] += g
    sq_ref[...] += g * g

    @pl.when(j == nk - 1)
    def _fin():
        mean_ref[...] *= inv
        sq_ref[...] *= inv


@functools.partial(jax.jit, static_argnames=("layout", "k", "interpret"))
def flat_vmap_moments(gstack, layout: ParamLayout, k: int, interpret: bool | None = None):
    """(k, n_rows, LANE) gradient stack -> flat (mean, sq_mean): one launch.

    The k axis is the minor grid dimension, so each output block stays
    resident in VMEM while its k slices accumulate, then normalizes in place
    on the last visit.
    """
    br = layout.block_rows
    sds = jax.ShapeDtypeStruct((layout.n_rows, LANE), jnp.float32)
    out_blk = pl.BlockSpec((br, LANE), lambda b, j: (b, 0))
    return pl.pallas_call(
        functools.partial(_vmap_kernel, nk=k, inv=1.0 / k),
        grid=(layout.n_blocks, k),
        in_specs=[pl.BlockSpec((1, br, LANE), lambda b, j: (j, b, 0))],
        out_specs=(out_blk, out_blk),
        out_shape=(sds, sds),
        interpret=resolve_interpret(interpret),
    )(gstack)


# ---------------------------------------------------------------------------
# contract registration (repro.analysis)
# ---------------------------------------------------------------------------


def _analysis_geometry(kname: str, *, layout_kind: str = "hostile", k: int = 4):
    from repro.analysis.registry import Geometry, Operand, demo_layout

    layout = demo_layout(layout_kind)
    blk = _blk(layout)
    rows = (layout.n_rows, LANE)
    f32 = lambda spec: Operand(spec, rows)
    inv = Operand(pl.BlockSpec((1, 1), lambda i: (0, 0)), (1, 1))
    if kname == "flat_moments_accum":
        return Geometry(grid=(layout.n_blocks,),
                        ins={"gs": f32(blk), "g2s": f32(blk), "g": f32(blk)},
                        outs={"gs_out": f32(blk), "g2s_out": f32(blk)})
    if kname == "flat_g_accum":
        return Geometry(grid=(layout.n_blocks,),
                        ins={"gs": f32(blk), "g": f32(blk)},
                        outs={"gs_out": f32(blk)})
    if kname == "flat_moments_finalize":
        return Geometry(grid=(layout.n_blocks,),
                        ins={"gs": f32(blk), "g2s": f32(blk), "inv": inv},
                        outs={"mean": f32(blk), "sq": f32(blk)})
    if kname == "flat_pack_square":
        out = pl.BlockSpec((2, layout.block_rows, LANE), lambda i: (0, i, 0))
        return Geometry(grid=(layout.n_blocks,),
                        ins={"gf": f32(blk)},
                        outs={"payload": Operand(out, (2,) + rows)})
    # flat_vmap_moments: k-minor grid keeps each output block in one run —
    # the registry replay PROVES that
    br = layout.block_rows
    out_blk = pl.BlockSpec((br, LANE), lambda b, j: (b, 0))
    stack = pl.BlockSpec((1, br, LANE), lambda b, j: (j, b, 0))
    return Geometry(grid=(layout.n_blocks, k),
                    ins={"gstack": Operand(stack, (k,) + rows)},
                    outs={"mean": f32(out_blk), "sq": f32(out_blk)})


def _register():
    from repro.analysis.registry import register_kernel

    oracles = {
        "flat_moments_accum": "moments_accum_ref",
        "flat_g_accum": "g_accum_ref",
        "flat_moments_finalize": "moments_finalize_ref",
        "flat_pack_square": "pack_square_ref",
        "flat_vmap_moments": "vmap_moments_ref",
    }
    for kname, oracle in oracles.items():
        configs = {"representative": dict(layout_kind="aligned"),
                   "hostile_ragged": dict(layout_kind="hostile")}
        if kname == "flat_vmap_moments":
            configs["hostile_odd_k"] = dict(layout_kind="hostile", k=7)
        register_kernel(kname, module=__name__, oracle=oracle,
                        build=functools.partial(_analysis_geometry, kname),
                        configs=configs)


_register()
