"""Pallas TPU kernels: flat-buffer GradStats accumulation — ONE pallas_call
per scan step / finalize over the whole parameter set.

The carry (g_sum, g2_sum) lives in the ParamLayout flat ``(n_rows, LANE)``
buffer for the whole scan (core/accumulate.py), the incoming gradient tree
is packed once per microbatch (core/layout.py), and each of

  * ``flat_moments_accum``     (scan body:  g_sum += g; g2_sum += g*g)
  * ``flat_moments_finalize``  (terminal /k normalize of both moments)

is a single ``pallas_call`` with a grid over row-blocks: HBM sees each
carry read and written once per microbatch, g read once.
``flat_pack_square`` emits the data-parallel path's ``[g; g²]`` payload.

Every sweep derives its grid from the LOCAL operand shape
(``gs.shape[0] // block_rows``), not ``layout.n_blocks`` — they are purely
element-wise, so those very wrappers run per-shard under shard_map
(backend.FlatSpmd) on FSDP row slices of the buffer, with no other change.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from repro.backend import resolve_interpret
from repro.core.layout import LANE, ParamLayout


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


def _accum_kernel(gs_ref, g2s_ref, g_ref, gs_out, g2s_out):
    g = g_ref[...].astype(jnp.float32)
    gs_out[...] = gs_ref[...] + g
    g2s_out[...] = g2s_ref[...] + g * g


def _finalize_kernel(gs_ref, g2s_ref, scal_ref, mean_out, sq_out):
    inv = scal_ref[0, 0]
    mean_out[...] = gs_ref[...] * inv
    sq_out[...] = g2s_ref[...] * inv


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

    The output is the COLLECTIVE-SHAPED carry device_grad_stats_fn pmeans
    across the data axis where the statistics stay replicated (mean =
    payload[0], sq = payload[1] are views, not copies; where the flat
    state's rows are split over that axis it reduce-scatters g and g²
    instead) — replacing the jnp concatenate([gf, square(gf)]) / split
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


# ---------------------------------------------------------------------------
# contract registration (repro.analysis)
# ---------------------------------------------------------------------------


def _analysis_geometry(kname: str, *, layout_kind: str = "hostile"):
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
    if kname == "flat_moments_finalize":
        return Geometry(grid=(layout.n_blocks,),
                        ins={"gs": f32(blk), "g2s": f32(blk), "inv": inv},
                        outs={"mean": f32(blk), "sq": f32(blk)})
    # flat_pack_square
    out = pl.BlockSpec((2, layout.block_rows, LANE), lambda i: (0, i, 0))
    return Geometry(grid=(layout.n_blocks,),
                    ins={"gf": f32(blk)},
                    outs={"payload": Operand(out, (2,) + rows)})


def _register():
    from repro.analysis.registry import register_kernel

    oracles = {
        "flat_moments_accum": "moments_accum_ref",
        "flat_moments_finalize": "moments_finalize_ref",
        "flat_pack_square": "pack_square_ref",
    }
    for kname, oracle in oracles.items():
        register_kernel(kname, module=__name__, oracle=oracle,
                        build=functools.partial(_analysis_geometry, kname),
                        configs={"representative": dict(layout_kind="aligned"),
                                 "hostile_ragged": dict(layout_kind="hostile")})


_register()
