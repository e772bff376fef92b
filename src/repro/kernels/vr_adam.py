"""Pallas TPU kernel: fused VR-Adam / VR-LAMB inner step (paper Alg. 3/5).

Per element this step reads 5 trees (g, g2, m, v, p) and writes 4
(direction, m', v', p') — ~9 parameter-sized HBM streams.  The jnp pipeline
adds materialized intermediates (r, ghat); the fused kernel performs the
entire chain in one VMEM pass: GSNR -> p-momentum -> bias-corrected ghat
-> m/v moments -> bias-corrected Adam direction.

Dynamic scalars (1/mean(r), 1-b1^t, 1-b2^t, 1-b3^t) arrive as a (1,4) block;
betas/gamma/eps are static closure constants.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from repro.backend import resolve_interpret
from repro.kernels.vr_update import LANE, BLOCK_ROWS, _pad2d, padded_rows


def _kernel(
    g_ref, ga_ref, g2_ref, m_ref, v_ref, p_ref, scal_ref,
    dir_ref, m_out, v_out, p_out,
    *, b1, b2, b3, eps, gamma, gsnr_eps,
):
    g = g_ref[...].astype(jnp.float32)
    ga = ga_ref[...].astype(jnp.float32)
    g2 = g2_ref[...].astype(jnp.float32)
    m = m_ref[...].astype(jnp.float32)
    v = v_ref[...].astype(jnp.float32)
    p = p_ref[...].astype(jnp.float32)
    inv_mean = scal_ref[0, 0]
    bc1 = scal_ref[0, 1]
    bc2 = scal_ref[0, 2]
    bc3 = scal_ref[0, 3]

    var = jnp.maximum(g2 - g * g, 0.0)
    r = jnp.clip((g * g) / (var + gsnr_eps) * inv_mean, gamma, 1.0)
    p_new = b3 * p + (1.0 - b3) * r
    ghat = (p_new / bc3) * ga
    m_new = b1 * m + (1.0 - b1) * ghat
    v_new = b2 * v + (1.0 - b2) * ghat * ghat
    direction = (m_new / bc1) / (jnp.sqrt(v_new / bc2) + eps)

    dir_ref[...] = direction
    m_out[...] = m_new
    v_out[...] = v_new
    p_out[...] = p_new


@functools.partial(
    jax.jit, static_argnames=("b1", "b2", "b3", "eps", "gamma", "gsnr_eps", "interpret")
)
def vr_adam_inner(
    g, g2, m, v, p, bc1, bc2, bc3,
    *, b1, b2, b3, eps, gamma, gsnr_eps, interpret: bool | None = None, g_apply=None,
):
    """Fused inner step on one tensor; matches ref.vr_adam_inner_ref.

    bcN are traced scalars (1 - betaN**t). Returns (dir, m', v', p') f32.
    ``g_apply`` is the gradient entering the moments (== g unless grad-clip
    rescaled it); the GSNR ratio always derives from the raw moments (g, g2).
    """
    ga = g if g_apply is None else g_apply
    shape = g.shape
    g2d, n = _pad2d(g)
    tens = [g2d] + [_pad2d(t)[0] for t in (ga, g2, m, v, p)]
    gf = g.reshape(-1).astype(jnp.float32)
    g2f = g2.reshape(-1).astype(jnp.float32)
    var = jnp.maximum(g2f - gf * gf, 0.0)
    inv_mean = 1.0 / jnp.maximum(jnp.mean(gf * gf / (var + gsnr_eps)), 1e-30)
    scal = jnp.stack([inv_mean, bc1, bc2, bc3]).astype(jnp.float32).reshape(1, 4)

    rows = g2d.shape[0]
    br = min(BLOCK_ROWS, rows)
    grid = (-(-rows // br),)
    blk = pl.BlockSpec((br, LANE), lambda i: (i, 0))
    sds = jax.ShapeDtypeStruct(g2d.shape, jnp.float32)
    outs = pl.pallas_call(
        functools.partial(
            _kernel, b1=b1, b2=b2, b3=b3, eps=eps, gamma=gamma, gsnr_eps=gsnr_eps
        ),
        grid=grid,
        in_specs=[blk] * 6 + [pl.BlockSpec((1, 4), lambda i: (0, 0))],
        out_specs=(blk,) * 4,
        out_shape=(sds,) * 4,
        interpret=resolve_interpret(interpret),
    )(*tens, scal)
    unpad = lambda x: x.reshape(-1)[:n].reshape(shape)
    return tuple(unpad(o) for o in outs)


# ---------------------------------------------------------------------------
# contract registration (repro.analysis)
# ---------------------------------------------------------------------------


def _analysis_geometry(*, n: int = 65536):
    from repro.analysis.registry import Geometry, Operand

    rows = padded_rows(n)
    br = min(BLOCK_ROWS, rows)
    blk = pl.BlockSpec((br, LANE), lambda i: (i, 0))
    f32 = lambda spec: Operand(spec, (rows, LANE))
    scal = Operand(pl.BlockSpec((1, 4), lambda i: (0, 0)), (1, 4))
    return Geometry(
        grid=(-(-rows // br),),
        ins={"g": f32(blk), "ga": f32(blk), "g2": f32(blk), "m": f32(blk),
             "v": f32(blk), "p": f32(blk), "scal": scal},
        outs={"dir": f32(blk), "m_out": f32(blk), "v_out": f32(blk),
              "p_out": f32(blk)},
    )


def _register():
    from repro.analysis.registry import register_kernel

    register_kernel(
        "vr_adam_inner", module=__name__, oracle="vr_adam_inner_ref",
        build=_analysis_geometry,
        configs={
            "representative": dict(n=65536),
            "hostile_subrow": dict(n=517),
            "hostile_partial_edge": dict(n=300000),
        },
    )


_register()
