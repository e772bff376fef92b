"""Pallas TPU kernels: fused VR-LAMB / VR-LARS trust-ratio steps (Alg. 5, §4.2).

LAMB/LARS add a per-tensor ("layer-wise") trust ratio on top of the
element-wise VR pipeline.  The ratio needs the full-tensor norms of the
update and the parameter, so a single-pass kernel cannot scale in place —
instead each kernel fuses the entire element-wise chain *and* the norm
reduction:

  VR-LAMB: GSNR r -> p-momentum -> bias-corrected ghat -> m/v moments ->
           Adam direction -> u = dir + wd*w, plus per-lane partial sums of
           u² and w² accumulated across the grid.
  VR-LARS: GSNR r -> sg = r*g_apply -> u = sg + wd*w, plus the same norm
           partials.

The wrapper (kernels/ops.py) finishes with two scalar sqrt's and one cheap
fused epilogue (ratio * u into the update / LARS momentum).  As in
vr_update/vr_adam, the scalar 1/mean(r) arrives from a jnp prepass that
re-reads g and g2 once (one fused reduction); the kernel then streams every
tree exactly once, where the jnp path additionally materializes and
re-streams r, ghat and u.  Folding the mean reduction into a first grid
pass would drop the prepass (ROADMAP open item).

Following the paper's remark in §4.2 the GSNR ratio is computed from the raw
group moments (g_stats, g2) but applied to the *clipped* gradient actually
entering the update (g_apply) — the two differ whenever global grad-clip
fires, and the jnp oracle keeps them distinct.

Norm partials are exact despite padding: zero-padded g/w tails produce
direction == u == 0 (see the padded-region note in the kernel body).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from repro.backend import resolve_interpret
from repro.kernels.vr_update import BLOCK_ROWS, LANE, _pad2d, padded_rows


def _pad_full_blocks(x2d: jnp.ndarray, br: int) -> jnp.ndarray:
    """Zero-pad rows to a whole number of (br x 128) blocks.

    The trust-ratio kernels REDUCE over every block, so a partial edge block
    is not allowed: out-of-range reads are undefined (NaN in interpret mode)
    and would poison the norm partials.  Zero rows contribute exactly 0.
    """
    rows = x2d.shape[0]
    tgt = -(-rows // br) * br
    return x2d if tgt == rows else jnp.pad(x2d, ((0, tgt - rows), (0, 0)))


def _lamb_kernel(
    g_ref, ga_ref, g2_ref, m_ref, v_ref, p_ref, w_ref, scal_ref,
    u_ref, m_out, v_out, p_out, uacc_ref, wacc_ref,
    *, b1, b2, b3, eps, wd, gamma, gsnr_eps,
):
    i = pl.program_id(0)

    @pl.when(i == 0)
    def _init():
        uacc_ref[...] = jnp.zeros_like(uacc_ref)
        wacc_ref[...] = jnp.zeros_like(wacc_ref)

    g = g_ref[...].astype(jnp.float32)
    ga = ga_ref[...].astype(jnp.float32)
    g2 = g2_ref[...].astype(jnp.float32)
    m = m_ref[...].astype(jnp.float32)
    v = v_ref[...].astype(jnp.float32)
    p = p_ref[...].astype(jnp.float32)
    w = w_ref[...].astype(jnp.float32)
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
    # padded tail: g = ga = w = 0 -> ghat = 0, m_new = v_new = 0, direction = 0,
    # u = 0 — so the norm partials below see exact zeros there.
    u = direction + wd * w

    u_ref[...] = u
    m_out[...] = m_new
    v_out[...] = v_new
    p_out[...] = p_new
    uacc_ref[...] += jnp.sum(u * u, axis=0, keepdims=True)
    wacc_ref[...] += jnp.sum(w * w, axis=0, keepdims=True)


@functools.partial(
    jax.jit,
    static_argnames=("b1", "b2", "b3", "eps", "wd", "gamma", "gsnr_eps", "interpret"),
)
def vr_lamb_inner(
    g, ga, g2, m, v, p, w, bc1, bc2, bc3,
    *, b1, b2, b3, eps, wd, gamma, gsnr_eps, interpret: bool | None = None,
):
    """Fused VR-LAMB step on one tensor; matches ref.vr_lamb_inner_ref.

    g is the group-mean gradient (GSNR source), ga the gradient entering the
    update (equal to g unless grad-clip rescaled it).  Returns
    (u, m', v', p', sum(u²), sum(w²)) — u is the pre-trust-ratio update
    dir + wd*w; the caller applies -lr * ratio.
    """
    shape = g.shape
    g2d, n = _pad2d(g)
    br = min(BLOCK_ROWS, g2d.shape[0])
    tens = [_pad_full_blocks(t, br) for t in
            [g2d] + [_pad2d(t)[0] for t in (ga, g2, m, v, p, w)]]
    g2d = tens[0]
    gf = g.reshape(-1).astype(jnp.float32)
    g2f = g2.reshape(-1).astype(jnp.float32)
    var = jnp.maximum(g2f - gf * gf, 0.0)
    inv_mean = 1.0 / jnp.maximum(jnp.mean(gf * gf / (var + gsnr_eps)), 1e-30)
    scal = jnp.stack([inv_mean, bc1, bc2, bc3]).astype(jnp.float32).reshape(1, 4)

    rows = g2d.shape[0]
    grid = (rows // br,)
    blk = pl.BlockSpec((br, LANE), lambda i: (i, 0))
    acc_blk = pl.BlockSpec((1, LANE), lambda i: (0, 0))
    sds = jax.ShapeDtypeStruct(g2d.shape, jnp.float32)
    acc_sds = jax.ShapeDtypeStruct((1, LANE), jnp.float32)
    u2d, m2d, v2d, p2d, uacc, wacc = pl.pallas_call(
        functools.partial(
            _lamb_kernel, b1=b1, b2=b2, b3=b3, eps=eps, wd=wd, gamma=gamma,
            gsnr_eps=gsnr_eps,
        ),
        grid=grid,
        in_specs=[blk] * 7 + [pl.BlockSpec((1, 4), lambda i: (0, 0))],
        out_specs=(blk,) * 4 + (acc_blk, acc_blk),
        out_shape=(sds,) * 4 + (acc_sds, acc_sds),
        interpret=resolve_interpret(interpret),
    )(*tens, scal)
    unpad = lambda x: x.reshape(-1)[:n].reshape(shape)
    return (
        unpad(u2d), unpad(m2d), unpad(v2d), unpad(p2d),
        jnp.sum(uacc), jnp.sum(wacc),
    )


def _lars_kernel(
    g_ref, ga_ref, g2_ref, w_ref, scal_ref, u_ref, uacc_ref, wacc_ref,
    *, wd, gamma, eps,
):
    i = pl.program_id(0)

    @pl.when(i == 0)
    def _init():
        uacc_ref[...] = jnp.zeros_like(uacc_ref)
        wacc_ref[...] = jnp.zeros_like(wacc_ref)

    g = g_ref[...].astype(jnp.float32)
    ga = ga_ref[...].astype(jnp.float32)
    g2 = g2_ref[...].astype(jnp.float32)
    w = w_ref[...].astype(jnp.float32)
    inv_mean = scal_ref[0, 0]

    var = jnp.maximum(g2 - g * g, 0.0)
    r = jnp.clip((g * g) / (var + eps) * inv_mean, gamma, 1.0)
    u = r * ga + wd * w  # padded tail: ga = w = 0 -> u = 0

    u_ref[...] = u
    uacc_ref[...] += jnp.sum(u * u, axis=0, keepdims=True)
    wacc_ref[...] += jnp.sum(w * w, axis=0, keepdims=True)


@functools.partial(jax.jit, static_argnames=("wd", "gamma", "eps", "interpret"))
def vr_lars_inner(g, ga, g2, w, *, wd, gamma, eps, interpret: bool | None = None):
    """Fused VR-LARS scale on one tensor; matches ref.vr_lars_inner_ref.

    Returns (u, sum(u²), sum(w²)) with u = r*ga + wd*w; the caller computes
    the trust ratio and folds it into the LARS momentum update.
    """
    shape = g.shape
    g2d, n = _pad2d(g)
    br = min(BLOCK_ROWS, g2d.shape[0])
    tens = [_pad_full_blocks(t, br) for t in
            [g2d] + [_pad2d(t)[0] for t in (ga, g2, w)]]
    g2d = tens[0]
    gf = g.reshape(-1).astype(jnp.float32)
    g2f = g2.reshape(-1).astype(jnp.float32)
    var = jnp.maximum(g2f - gf * gf, 0.0)
    inv_mean = (1.0 / jnp.maximum(jnp.mean(gf * gf / (var + eps)), 1e-30)).reshape(1, 1)

    rows = g2d.shape[0]
    grid = (rows // br,)
    blk = pl.BlockSpec((br, LANE), lambda i: (i, 0))
    acc_blk = pl.BlockSpec((1, LANE), lambda i: (0, 0))
    sds = jax.ShapeDtypeStruct(g2d.shape, jnp.float32)
    acc_sds = jax.ShapeDtypeStruct((1, LANE), jnp.float32)
    u2d, uacc, wacc = pl.pallas_call(
        functools.partial(_lars_kernel, wd=wd, gamma=gamma, eps=eps),
        grid=grid,
        in_specs=[blk] * 4 + [pl.BlockSpec((1, 1), lambda i: (0, 0))],
        out_specs=(blk, acc_blk, acc_blk),
        out_shape=(sds, acc_sds, acc_sds),
        interpret=resolve_interpret(interpret),
    )(*tens, inv_mean)
    u = u2d.reshape(-1)[:n].reshape(shape)
    return u, jnp.sum(uacc), jnp.sum(wacc)


# ---------------------------------------------------------------------------
# contract registration (repro.analysis)
# ---------------------------------------------------------------------------


def _analysis_geometry(kname: str, *, n: int = 65536):
    from repro.analysis.registry import Geometry, Operand

    rows = padded_rows(n)
    br = min(BLOCK_ROWS, rows)
    grid = (-(-rows // br),)  # _pad_full_blocks: the reduce grid has no edge block
    blk = pl.BlockSpec((br, LANE), lambda i: (i, 0))
    f32 = lambda spec: Operand(spec, (grid[0] * br, LANE))
    # (1, LANE) norm-partial accumulators: constant index every step, so the
    # registry replay proves the revisits are one consecutive run (no race)
    acc = Operand(pl.BlockSpec((1, LANE), lambda i: (0, 0)), (1, LANE))
    if kname == "vr_lamb_inner":
        scal = Operand(pl.BlockSpec((1, 4), lambda i: (0, 0)), (1, 4))
        return Geometry(
            grid=grid,
            ins={"g": f32(blk), "ga": f32(blk), "g2": f32(blk), "m": f32(blk),
                 "v": f32(blk), "p": f32(blk), "w": f32(blk), "scal": scal},
            outs={"u": f32(blk), "m_out": f32(blk), "v_out": f32(blk),
                  "p_out": f32(blk), "uacc": acc, "wacc": acc},
        )
    scal = Operand(pl.BlockSpec((1, 1), lambda i: (0, 0)), (1, 1))
    return Geometry(
        grid=grid,
        ins={"g": f32(blk), "ga": f32(blk), "g2": f32(blk), "w": f32(blk),
             "scal": scal},
        outs={"u": f32(blk), "uacc": acc, "wacc": acc},
    )


def _register():
    from repro.analysis.registry import register_kernel

    for kname, oracle in (
        ("vr_lamb_inner", "vr_lamb_inner_ref"),
        ("vr_lars_inner", "vr_lars_inner_ref"),
    ):
        register_kernel(
            kname, module=__name__, oracle=oracle,
            build=functools.partial(_analysis_geometry, kname),
            configs={
                "representative": dict(n=65536),
                "hostile_subrow": dict(n=517),
                "hostile_multiblock": dict(n=300000),
            },
        )


_register()
