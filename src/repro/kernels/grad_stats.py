"""Pallas TPU kernel: fused k-group gradient-moment accumulation.

The paper-faithful scan path (core/accumulate.py, method="scan") updates two
f32 parameter-sized trees per microbatch:

    g_sum  += g
    g2_sum += g * g

As two separate tree-maps that is two full HBM sweeps over the state (read
g_sum + g, write g_sum; read g2_sum + g, write g2_sum — g is read twice and
XLA does not reliably fuse across the tree_map boundary inside a scan body).
The fused kernel performs both moment updates in a single VMEM pass: HBM sees
exactly read (g_sum, g2_sum, g) and write (g_sum', g2_sum') once each.

To avoid re-padding the carry every microbatch, the accumulator lives in the
padded (rows x 128) f32 layout for the whole scan: ``moments_init`` allocates
it, ``moments_accum`` pads only the incoming gradient leaf (one cheap DMA)
and ``moments_finalize`` applies the terminal ``/k`` normalize fused with the
unpad back to parameter shapes.

Tiling follows vr_update.py: leaves flatten to (rows x 128) f32, rows a
multiple of 8 (f32 sublane), blocked (BLOCK_ROWS, 128) in VMEM.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from repro.backend import resolve_interpret
from repro.kernels.vr_update import BLOCK_ROWS, LANE, _pad2d, padded_rows


def _accum_kernel(gs_ref, g2s_ref, g_ref, gs_out, g2s_out):
    g = g_ref[...].astype(jnp.float32)
    gs_out[...] = gs_ref[...] + g
    g2s_out[...] = g2s_ref[...] + g * g


def _finalize_kernel(gs_ref, g2s_ref, scal_ref, mean_out, sq_out):
    inv = scal_ref[0, 0]
    mean_out[...] = gs_ref[...] * inv
    sq_out[...] = g2s_ref[...] * inv


def _grid_blk(rows: int):
    br = min(BLOCK_ROWS, rows)
    return (-(-rows // br),), pl.BlockSpec((br, LANE), lambda i: (i, 0))


def moments_init(leaf: jnp.ndarray) -> jnp.ndarray:
    """Zero accumulator in the padded layout for one parameter leaf."""
    return jnp.zeros((padded_rows(leaf.size), LANE), jnp.float32)


@functools.partial(jax.jit, static_argnames=("interpret",))
def moments_accum(gs2d, g2s2d, g, interpret: bool | None = None):
    """One fused scan-body update: (g_sum+g, g2_sum+g²) on one leaf.

    gs2d/g2s2d are padded (rows x 128) carries; g is the raw param-shaped
    gradient (any float dtype).  Matches ref.moments_accum_ref on the
    unpadded region; the zero-padded tail stays exactly zero.
    """
    g2d, _ = _pad2d(g)
    grid, blk = _grid_blk(gs2d.shape[0])
    sds = jax.ShapeDtypeStruct(gs2d.shape, jnp.float32)
    return pl.pallas_call(
        _accum_kernel,
        grid=grid,
        in_specs=[blk, blk, blk],
        out_specs=(blk, blk),
        out_shape=(sds, sds),
        interpret=resolve_interpret(interpret),
    )(gs2d, g2s2d, g2d)


@functools.partial(jax.jit, static_argnames=("shape", "interpret"))
def moments_finalize(gs2d, g2s2d, k, shape, interpret: bool | None = None):
    """Terminal /k normalize fused in one pass; unpads to ``shape``.

    k may be a traced scalar (int or float).  Returns (mean, sq_mean) f32.
    """
    inv = (1.0 / jnp.asarray(k, jnp.float32)).reshape(1, 1)
    grid, blk = _grid_blk(gs2d.shape[0])
    sds = jax.ShapeDtypeStruct(gs2d.shape, jnp.float32)
    mean2d, sq2d = pl.pallas_call(
        _finalize_kernel,
        grid=grid,
        in_specs=[blk, blk, pl.BlockSpec((1, 1), lambda i: (0, 0))],
        out_specs=(blk, blk),
        out_shape=(sds, sds),
        interpret=resolve_interpret(interpret),
    )(gs2d, g2s2d, inv)
    n = 1
    for d in shape:
        n *= d
    unpad = lambda x: x.reshape(-1)[:n].reshape(shape)
    return unpad(mean2d), unpad(sq2d)


# ---------------------------------------------------------------------------
# contract registration (repro.analysis)
# ---------------------------------------------------------------------------


def _analysis_geometry(kname: str, *, n: int = 300, g_dtype: str = "float32"):
    from repro.analysis.registry import Geometry, Operand

    rows = (padded_rows(n), LANE)
    grid, blk = _grid_blk(rows[0])
    f32 = lambda spec: Operand(spec, rows)
    if kname == "grad_stats_accum":
        return Geometry(grid=grid,
                        ins={"gs": f32(blk), "g2s": f32(blk),
                             "g": Operand(blk, rows, dtype=g_dtype)},
                        outs={"gs_out": f32(blk), "g2s_out": f32(blk)})
    inv = Operand(pl.BlockSpec((1, 1), lambda i: (0, 0)), (1, 1))
    return Geometry(grid=grid,
                    ins={"gs": f32(blk), "g2s": f32(blk), "inv": inv},
                    outs={"mean": f32(blk), "sq": f32(blk)})


def _register():
    from repro.analysis.registry import register_kernel

    for kname, oracle in (("grad_stats_accum", "moments_accum_ref"),
                          ("grad_stats_finalize", "moments_finalize_ref")):
        register_kernel(
            kname, module=__name__, oracle=oracle,
            build=functools.partial(_analysis_geometry, kname),
            configs={
                # a small leaf fits one block; the hostile leaf spans a
                # ragged multi-block grid (320 rows over 256-row blocks)
                "representative": dict(n=300),
                "hostile_multiblock_bf16": dict(n=40_000, g_dtype="bfloat16"),
            },
        )


_register()
