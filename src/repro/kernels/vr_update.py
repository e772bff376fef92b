"""Pallas TPU kernel: fused GSNR scale (VR-SGD/Momentum/LARS hot path).

The VRGD pipeline (variance -> GSNR -> normalize -> clip -> scale) is pure
element-wise traffic over 2-3 full parameter-sized trees — HBM-bandwidth
bound.  The unfused jnp pipeline materializes var/r/r_norm intermediates
(XLA usually fuses some, but the normalize step forces a full r round-trip
because of the mean).  This kernel recomputes r from (g, g2) inside VMEM
using the *precomputed* scalar 1/mean(r) (one cheap fused jnp reduction),
so HBM sees exactly: read g, read g2, write sg, write r.

Tiling: leaves are flattened, padded to (rows x 128) f32 with rows a
multiple of 8 (TPU sublane), and blocked (BLOCK_ROWS, 128) in VMEM.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from repro.analysis.layout_contracts import LANE, sublane
from repro.backend import resolve_interpret

BLOCK_ROWS = 256  # (256, 128) f32 = 128 KiB per ref; ~0.5 MiB working set


def _kernel(g_ref, ga_ref, g2_ref, scal_ref, sg_ref, r_ref, *, gamma: float, eps: float):
    g = g_ref[...].astype(jnp.float32)
    ga = ga_ref[...].astype(jnp.float32)
    g2 = g2_ref[...].astype(jnp.float32)
    inv_mean = scal_ref[0, 0]
    var = jnp.maximum(g2 - g * g, 0.0)
    r = (g * g) / (var + eps)
    r = jnp.clip(r * inv_mean, gamma, 1.0)
    sg_ref[...] = (r * ga).astype(sg_ref.dtype)
    r_ref[...] = r.astype(r_ref.dtype)


def padded_rows(n: int) -> int:
    """Rows of the (rows x 128) f32 padded layout for an n-element leaf:
    ceil(n / LANE) rounded up to the f32 sublane multiple."""
    rows = -(-n // LANE)
    sub = sublane(jnp.float32)
    return -(-rows // sub) * sub


def _pad2d(x: jnp.ndarray):
    n = x.size
    rows_p = padded_rows(n)
    flat = jnp.pad(x.reshape(-1).astype(jnp.float32), (0, rows_p * LANE - n))
    return flat.reshape(rows_p, LANE), n


@functools.partial(jax.jit, static_argnames=("gamma", "eps", "interpret"))
def vr_scale(
    g: jnp.ndarray, g2: jnp.ndarray, gamma: float, eps: float,
    interpret: bool | None = None, g_apply: jnp.ndarray = None,
):
    """Fused (scaled_grad, r) for one tensor; matches ref.vr_scale_ref.

    r always derives from the raw group moments (g, g2); it multiplies
    ``g_apply`` (the gradient actually entering the update — differs from g
    when global grad-clip rescaled it).  g_apply=None means g_apply == g.
    Both outputs are f32 regardless of input dtype, matching the jnp oracle
    (r is f32, so r * g promotes).
    """
    ga = g if g_apply is None else g_apply
    orig_shape = ga.shape
    g2d, n = _pad2d(g)
    ga2d, _ = _pad2d(ga)
    g22d, _ = _pad2d(g2)
    # scalar pass: mean of raw r over the *unpadded* elements
    gf = g.reshape(-1).astype(jnp.float32)
    g2f = g2.reshape(-1).astype(jnp.float32)
    var = jnp.maximum(g2f - gf * gf, 0.0)
    mean_r = jnp.mean(gf * gf / (var + eps))
    inv_mean = (1.0 / jnp.maximum(mean_r, 1e-30)).reshape(1, 1)

    rows = g2d.shape[0]
    br = min(BLOCK_ROWS, rows)
    grid = (rows // br,) if rows % br == 0 else (-(-rows // br),)
    out_shape = (
        jax.ShapeDtypeStruct(g2d.shape, jnp.float32),
        jax.ShapeDtypeStruct(g2d.shape, jnp.float32),
    )
    sg2d, r2d = pl.pallas_call(
        functools.partial(_kernel, gamma=gamma, eps=eps),
        grid=grid,
        in_specs=[
            pl.BlockSpec((br, LANE), lambda i: (i, 0)),
            pl.BlockSpec((br, LANE), lambda i: (i, 0)),
            pl.BlockSpec((br, LANE), lambda i: (i, 0)),
            pl.BlockSpec((1, 1), lambda i: (0, 0)),
        ],
        out_specs=(
            pl.BlockSpec((br, LANE), lambda i: (i, 0)),
            pl.BlockSpec((br, LANE), lambda i: (i, 0)),
        ),
        out_shape=out_shape,
        interpret=resolve_interpret(interpret),
    )(g2d, ga2d, g22d, inv_mean)
    sg = sg2d.reshape(-1)[:n].reshape(orig_shape)
    r = r2d.reshape(-1)[:n].reshape(orig_shape)
    return sg, r


# ---------------------------------------------------------------------------
# contract registration (repro.analysis)
# ---------------------------------------------------------------------------


def _analysis_geometry(*, n: int = 65536):
    from repro.analysis.registry import Geometry, Operand

    rows = padded_rows(n)
    br = min(BLOCK_ROWS, rows)
    blk = pl.BlockSpec((br, LANE), lambda i: (i, 0))
    f32 = lambda spec: Operand(spec, (rows, LANE))
    scal = Operand(pl.BlockSpec((1, 1), lambda i: (0, 0)), (1, 1))
    return Geometry(
        grid=(-(-rows // br),),
        ins={"g": f32(blk), "ga": f32(blk), "g2": f32(blk), "scal": scal},
        outs={"sg": f32(blk), "r": f32(blk)},
    )


def _register():
    from repro.analysis.registry import register_kernel

    register_kernel(
        "vr_scale", module=__name__, oracle="vr_scale_ref",
        build=_analysis_geometry,
        configs={
            "representative": dict(n=65536),
            "hostile_subrow": dict(n=517),
            "hostile_partial_edge": dict(n=300000),
        },
    )


_register()
