"""Pallas TPU kernels: flat-buffer GradStats accumulation — ONE pallas_call
per scan step / finalize over the whole parameter set.

The carry (g_sum, g2_sum) lives in the ParamLayout flat ``(n_rows, LANE)``
buffer for the whole scan (core/accumulate.py), the incoming gradient tree
is packed once per microbatch (core/layout.py), and each of

  * ``flat_moments_accum``     (scan body:  g_sum += g; g2_sum += g*g)
  * ``flat_moments_finalize``  (terminal /k normalize of both moments)

is a single ``pallas_call`` with a grid over row tiles: HBM sees each
carry read and written once per microbatch, g read once.
``flat_pack_square`` emits the data-parallel path's ``[g; g²]`` payload.

These sweeps are purely element-wise and read no leaf id, so their grid
step is a row tile chosen from the LOCAL operand shape (``sweep_rows``:
up to SWEEP_ROWS rows, as many as the double-buffered windows fit in
VMEM), not the layout's ``block_rows``, which stays the leaf padding the
per-leaf kernels (flat_update, flat_spmd) need for their norms.  A tile
spans many layout blocks; the last one may be ragged, and Pallas drops its
out-of-range writes.  Few, large steps matter: a TPU v5e grid step costs
a fixed 0.29-0.70 us whatever it holds.  Deriving the tile from the local
shape lets the very same wrappers run per-shard under shard_map
(backend.FlatSpmd) on FSDP row slices of the buffer: a shard smaller than
a tile is one tile of its own size.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from repro.analysis.layout_contracts import DOUBLE_BUFFER, VMEM_BUDGET_BYTES
from repro.backend import resolve_interpret
from repro.core.layout import LANE

# most rows a sweep's grid step takes: (2048, LANE) f32 = 1 MiB a window
SWEEP_ROWS = 2048
# the double-buffered windows leave a quarter of the VMEM budget to the
# kernel body's f32 values
SWEEP_VMEM_BYTES = VMEM_BUDGET_BYTES["tpu"] * 3 // 4


def sweep_rows(rows: int, streams: int) -> int:
    """Rows per grid step of an element-wise sweep over a ``(rows, LANE)``
    buffer with ``streams`` row windows (inputs and outputs, each counted
    as f32): SWEEP_ROWS, halved while the double-buffered windows exceed
    SWEEP_VMEM_BYTES, and never more rows than the buffer has.  A tile
    shorter than the buffer is a power of two of at least 32 rows, a
    multiple of every dtype's sublane tile."""
    r = SWEEP_ROWS
    while r > 32 and DOUBLE_BUFFER * streams * r * LANE * 4 > SWEEP_VMEM_BYTES:
        r //= 2
    return min(r, rows)


# row windows of each sweep, inputs and outputs: they size its tile
STREAMS = {"flat_moments_accum": 5, "flat_moments_finalize": 4, "flat_pack_square": 3}


def _sweep(kname: str, rows: int):
    """(grid, row-tile BlockSpec) of the sweep ``kname`` over ``rows`` rows."""
    r = sweep_rows(rows, STREAMS[kname])
    return (pl.cdiv(rows, r),), pl.BlockSpec((r, LANE), lambda i: (i, 0))


def _accum_kernel(gs_ref, g2s_ref, g_ref, gs_out, g2s_out):
    g = g_ref[...].astype(jnp.float32)
    gs_out[...] = gs_ref[...] + g
    g2s_out[...] = g2s_ref[...] + g * g


def _finalize_kernel(gs_ref, g2s_ref, scal_ref, mean_out, sq_out):
    inv = scal_ref[0, 0]
    mean_out[...] = gs_ref[...] * inv
    sq_out[...] = g2s_ref[...] * inv


@functools.partial(jax.jit, static_argnames=("interpret",))
def flat_moments_accum(gs, g2s, g, interpret: bool | None = None):
    """One scan-body update of both flat moment carries: a single launch."""
    grid, tile = _sweep("flat_moments_accum", gs.shape[0])
    sds = jax.ShapeDtypeStruct(gs.shape, jnp.float32)
    return pl.pallas_call(
        _accum_kernel,
        grid=grid,
        in_specs=[tile, tile, tile],
        out_specs=(tile, tile),
        out_shape=(sds, sds),
        input_output_aliases={0: 0, 1: 1},  # the carries update in place
        interpret=resolve_interpret(interpret),
    )(gs, g2s, g)


@functools.partial(jax.jit, static_argnames=("interpret",))
def flat_moments_finalize(gs, g2s, k, interpret: bool | None = None):
    """Terminal /k normalize of the flat carries: a single launch.

    k may be traced.  Returns flat (mean, sq_mean) f32 buffers.
    """
    inv = (1.0 / jnp.asarray(k, jnp.float32)).reshape(1, 1)
    grid, tile = _sweep("flat_moments_finalize", gs.shape[0])
    sds = jax.ShapeDtypeStruct(gs.shape, jnp.float32)
    return pl.pallas_call(
        _finalize_kernel,
        grid=grid,
        in_specs=[tile, tile, pl.BlockSpec((1, 1), lambda i: (0, 0))],
        out_specs=(tile, tile),
        out_shape=(sds, sds),
        input_output_aliases={0: 0, 1: 1},  # the sums become the moments
        interpret=resolve_interpret(interpret),
    )(gs, g2s, inv)


def _pack_square_kernel(g_ref, out_ref):
    g = g_ref[...].astype(jnp.float32)
    out_ref[0] = g
    out_ref[1] = g * g


def _payload_spec(tile):
    return pl.BlockSpec((2,) + tuple(tile.block_shape), lambda i: (0, i, 0))


@functools.partial(jax.jit, static_argnames=("interpret",))
def flat_pack_square(gf, interpret: bool | None = None):
    """(rows, LANE) flat gradient -> (2, rows, LANE) [g; g²] payload: one
    launch, ONE read of gf per tile.

    The output is the COLLECTIVE-SHAPED carry device_grad_stats_fn pmeans
    across the data axis where the statistics stay replicated (mean =
    payload[0], sq = payload[1] are views, not copies; where the flat
    state's rows are split over that axis it reduce-scatters g and g²
    instead) — replacing the jnp concatenate([gf, square(gf)]) / split
    round-trip that re-read gf and materialized two extra copies of the
    buffer per step.  Its three windows are gf's and the payload tile's
    two halves."""
    grid, tile = _sweep("flat_pack_square", gf.shape[0])
    return pl.pallas_call(
        _pack_square_kernel,
        grid=grid,
        in_specs=[tile],
        out_specs=_payload_spec(tile),
        out_shape=jax.ShapeDtypeStruct((2,) + tuple(gf.shape), jnp.float32),
        interpret=resolve_interpret(interpret),
    )(gf)


# ---------------------------------------------------------------------------
# contract registration (repro.analysis)
# ---------------------------------------------------------------------------


def _analysis_geometry(kname: str, *, layout_kind: str = "hostile", tiles: int = 0):
    """The launch over a demo layout's rows, plus ``tiles`` whole tiles: the
    demo layouts fit one tile, so ``tiles`` > 0 makes a grid of several
    whose last tile is ragged."""
    from repro.analysis.registry import Geometry, Operand, demo_layout

    n_rows = demo_layout(layout_kind).n_rows + tiles * SWEEP_ROWS
    rows = (n_rows, LANE)
    f32 = lambda spec: Operand(spec, rows)
    inv = Operand(pl.BlockSpec((1, 1), lambda i: (0, 0)), (1, 1))
    grid, tile = _sweep(kname, n_rows)
    if kname == "flat_moments_accum":
        return Geometry(grid=grid,
                        ins={"gs": f32(tile), "g2s": f32(tile), "g": f32(tile)},
                        outs={"gs_out": f32(tile), "g2s_out": f32(tile)})
    if kname == "flat_moments_finalize":
        return Geometry(grid=grid,
                        ins={"gs": f32(tile), "g2s": f32(tile), "inv": inv},
                        outs={"mean": f32(tile), "sq": f32(tile)})
    # flat_pack_square
    return Geometry(grid=grid,
                    ins={"gf": f32(tile)},
                    outs={"payload": Operand(_payload_spec(tile), (2,) + rows)})


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
                                 "hostile_ragged": dict(layout_kind="hostile"),
                                 "ragged_tiles": dict(layout_kind="hostile", tiles=2)})


_register()
