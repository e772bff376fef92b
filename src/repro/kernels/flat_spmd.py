"""Per-shard building blocks for the flat-buffer kernels under shard_map.

The single-launch kernels in kernels/flat_update.py fold the per-leaf
("layer") scalar reductions — the GSNR normalizer 1/mean(r) and the
LAMB/LARS trust-ratio norms — into grid phases over a persistent VMEM
scratch accumulator.  That is correct only when one kernel instance sees ALL
of a leaf's rows; under FSDP the flat buffer's rows dimension is sharded
(Rules.flat_buffer_pspec), so each device holds a contiguous row slice and
the reduction must split:

  1. a per-shard PARTIALS kernel (``leaf_r_partials``) accumulating the raw
     GSNR sums into a (leaf_slots, LANE) OUTPUT block (revisited across the
     local grid, the flat_stats vmap pattern);
  2. one ``jax.lax.psum`` of that small accumulator across the shards — the
     only collective in the update (orchestrated by backend.FlatSpmd);
  3. a per-shard APPLY / COMPUTE kernel taking the combined accumulator as
     an ordinary operand where the fused kernel read its scratch.

The block bodies are IMPORTED from flat_update (``_accumulate_r``,
``_scale_apply``, ``_adam_apply``, ``_lamb_compute``, ``_lars_compute``),
so per-shard and single-launch paths cannot drift.  Numerics: a shard
accumulates its blocks in the same order
the fused kernel's phase-0 sweep does, and shards that hold none of a
leaf's rows contribute exact zero partials — so whenever no leaf straddles
a shard boundary the combined scalars (and therefore the whole update) are
BIT-IDENTICAL to the single-launch kernel; a straddling leaf reassociates
one addition per boundary (~1 ulp on the leaf scalar).

Grids derive from the LOCAL operand shapes (``g.shape[0] // block_rows``) —
the same wrappers serve any shard count, including 1 (the differential
tests run them unsharded against the fused kernels).  The (n_blocks,)
leaf-id map is SHARDED under the same PartitionSpec — its split is exactly
the buffer's block split — and each shard scalar-prefetches its own slice
into SMEM, so it reads its leaf ids with no index arithmetic.

PHASE-AWARE maps don't apply here: these per-shard kernels run SINGLE-PHASE
1-D grids (the multi-phase structure lives in the gathered flat_update
kernels, whose PHASE_WINDOWS index maps park operands outside their live
phases — see flat_update's docstring).  Every operand of a per-shard launch
is read/written on every grid step, so there is nothing to park; the math
inheritance above is unaffected.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from repro.core.layout import LANE, ParamLayout
from repro.kernels.flat_stats import _local_blocks
from repro.kernels.flat_update import (
    _accumulate_r,
    _adam_apply,
    _call,
    _lamb_compute,
    _lars_compute,
    _scale_apply,
)

_f32 = jnp.float32


def _specs(layout: ParamLayout):
    """(row-block, accumulator, inv-size, scalar) BlockSpecs for a 1-D local
    grid over row blocks; every index map also receives the SMEM leaf ids."""
    blk = pl.BlockSpec((layout.block_rows, LANE), lambda b, lids: (b, 0))
    acc = pl.BlockSpec((layout.leaf_slots, LANE), lambda b, lids: (0, 0))
    inv = pl.BlockSpec((layout.leaf_slots, 1), lambda b, lids: (0, 0))
    scal = pl.BlockSpec((1, 8), lambda b, lids: (0, 0))
    return blk, acc, inv, scal


def _launch(kernel, layout, g, lids, in_specs, out_specs, out_shape, interpret, *args):
    return _call(kernel, lids, _local_blocks(g, layout), in_specs, out_specs,
                 out_shape, [], interpret, *args, phases=None)


# ---------------------------------------------------------------------------
# partials: the fused kernels' phase 0, emitting the accumulator as output
# ---------------------------------------------------------------------------


def _r_partials_kernel(lids_ref, g_ref, g2_ref, racc_ref, *, gsnr_eps):
    b = pl.program_id(0)

    @pl.when(b == 0)
    def _init():
        racc_ref[...] = jnp.zeros_like(racc_ref)

    _accumulate_r(racc_ref, g_ref, g2_ref, lids_ref[b], gsnr_eps)


@functools.partial(jax.jit, static_argnames=("layout", "gsnr_eps", "interpret"))
def leaf_r_partials(g, g2, lids, layout: ParamLayout, *, gsnr_eps,
                    interpret: bool | None = None):
    """Shard-local per-leaf Σ r_raw partials: one launch over the local rows."""
    blk, acc, _, _ = _specs(layout)
    return _launch(
        functools.partial(_r_partials_kernel, gsnr_eps=gsnr_eps), layout, g, lids,
        [blk, blk], acc, jax.ShapeDtypeStruct((layout.leaf_slots, LANE), _f32),
        interpret, g, g2,
    )


# ---------------------------------------------------------------------------
# apply kernels: the fused kernels' later phases, accumulator as an operand
# ---------------------------------------------------------------------------


def _apply_kernel(lids_ref, invsz_ref, racc_ref, g_ref, ga_ref, g2_ref, *rest, body):
    """One block of a flat_update phase-1 body, with the psum-combined r
    accumulator as an ordinary operand where the fused kernel read scratch."""
    body(lids_ref[pl.program_id(0)], racc_ref, invsz_ref, g_ref, ga_ref, g2_ref, *rest)


def _trust_kernel(lids_ref, invsz_ref, racc_ref, g_ref, ga_ref, g2_ref, *rest, body):
    """_apply_kernel for the LAMB/LARS computes: also zero the norm-partial
    accumulators (the last two outputs) on the first block."""
    uacc_ref, wacc_ref = rest[-2:]

    @pl.when(pl.program_id(0) == 0)
    def _init():
        uacc_ref[...] = jnp.zeros_like(uacc_ref)
        wacc_ref[...] = jnp.zeros_like(wacc_ref)

    _apply_kernel(lids_ref, invsz_ref, racc_ref, g_ref, ga_ref, g2_ref, *rest, body=body)


@functools.partial(jax.jit, static_argnames=("layout", "gamma", "eps", "interpret"))
def vr_scale_apply(g, ga, g2, racc, lids, invsz, layout: ParamLayout, *,
                   gamma, eps, interpret: bool | None = None):
    """Shard-local (scaled_grad, r) given the combined r accumulator."""
    blk, acc, inv, _ = _specs(layout)
    sds = jax.ShapeDtypeStruct(g.shape, _f32)
    return _launch(
        functools.partial(_apply_kernel, body=functools.partial(
            _scale_apply, gamma=gamma, eps=eps)),
        layout, g, lids, [inv, acc, blk, blk, blk], (blk, blk), (sds, sds), interpret,
        invsz, racc, g, ga, g2,
    )


@functools.partial(
    jax.jit,
    static_argnames=(
        "layout", "b1", "b2", "b3", "eps", "wd", "gamma", "gsnr_eps", "state_dtype", "interpret",
    ),
)
def vr_adam_apply(g, ga, g2, m, v, p, w, scal, racc, lids, invsz,
                  layout: ParamLayout, *, b1, b2, b3, eps, wd, gamma, gsnr_eps,
                  state_dtype="float32", interpret: bool | None = None):
    """Shard-local full VR-Adam apply given the combined r accumulator."""
    blk, acc, inv, scal_spec = _specs(layout)
    f32_sds = jax.ShapeDtypeStruct(g.shape, _f32)
    sd_sds = jax.ShapeDtypeStruct(g.shape, jnp.dtype(state_dtype))
    body = functools.partial(_adam_apply, b1=b1, b2=b2, b3=b3, eps=eps, wd=wd,
                             gamma=gamma, gsnr_eps=gsnr_eps)
    return _launch(
        functools.partial(_apply_kernel, body=body), layout, g, lids,
        [inv, acc] + [blk] * 7 + [scal_spec], (blk,) * 4,
        (f32_sds, sd_sds, sd_sds, sd_sds), interpret,
        invsz, racc, g, ga, g2, m, v, p, w, scal,
    )


@functools.partial(
    jax.jit,
    static_argnames=(
        "layout", "b1", "b2", "b3", "eps", "wd", "gamma", "gsnr_eps", "state_dtype", "interpret",
    ),
)
def vr_lamb_compute(g, ga, g2, m, v, p, w, scal, racc, lids, invsz,
                    layout: ParamLayout, *, b1, b2, b3, eps, wd, gamma, gsnr_eps,
                    state_dtype="float32", interpret: bool | None = None):
    """Shard-local VR-LAMB compute: (u, m', v', p', uacc, wacc) — the
    pre-trust-ratio update plus the shard's norm partials; the cross-shard
    psum and the lamb_epilogue live in backend.FlatSpmd."""
    blk, acc, inv, scal_spec = _specs(layout)
    f32_sds = jax.ShapeDtypeStruct(g.shape, _f32)
    sd_sds = jax.ShapeDtypeStruct(g.shape, jnp.dtype(state_dtype))
    acc_sds = jax.ShapeDtypeStruct((layout.leaf_slots, LANE), _f32)
    body = functools.partial(_lamb_compute, b1=b1, b2=b2, b3=b3, eps=eps, wd=wd,
                             gamma=gamma, gsnr_eps=gsnr_eps)
    return _launch(
        functools.partial(_trust_kernel, body=body), layout, g, lids,
        [inv, acc] + [blk] * 7 + [scal_spec], (blk, blk, blk, blk, acc, acc),
        (f32_sds, sd_sds, sd_sds, sd_sds, acc_sds, acc_sds), interpret,
        invsz, racc, g, ga, g2, m, v, p, w, scal,
    )


@functools.partial(jax.jit, static_argnames=("layout", "wd", "eps", "interpret"))
def vr_lars_compute(g, ga, g2, w, scal, racc, lids, invsz, layout: ParamLayout,
                    *, wd, eps, interpret: bool | None = None):
    """Shard-local VR-LARS compute: (u, uacc, wacc); the momentum fold and
    trust-ratio epilogue (lars_epilogue) live in backend.FlatSpmd."""
    blk, acc, inv, scal_spec = _specs(layout)
    sds = jax.ShapeDtypeStruct(g.shape, _f32)
    acc_sds = jax.ShapeDtypeStruct((layout.leaf_slots, LANE), _f32)
    return _launch(
        functools.partial(_trust_kernel, body=functools.partial(_lars_compute, wd=wd, eps=eps)),
        layout, g, lids, [inv, acc] + [blk] * 4 + [scal_spec], (blk, acc, acc),
        (sds, acc_sds, acc_sds), interpret,
        invsz, racc, g, ga, g2, w, scal,
    )


# ---------------------------------------------------------------------------
# contract registration (repro.analysis): single-phase per-shard launches —
# the replay PROVES the accumulator outputs' constant index maps give one
# run per block (the safe accumulate-in-VMEM pattern)
# ---------------------------------------------------------------------------


def _analysis_geometry(kname: str, *, layout_kind: str = "hostile",
                       state_dtype: str = "float32"):
    from repro.analysis.registry import Geometry, Operand, demo_layout

    layout = demo_layout(layout_kind)
    blk, acc, inv, scal = _specs(layout)
    rows, accs = (layout.n_rows, LANE), (layout.leaf_slots, LANE)
    f32 = lambda spec: Operand(spec, rows)
    sd = lambda spec: Operand(spec, rows, dtype=state_dtype)
    a32 = Operand(acc, accs)
    meta = {"inv": Operand(inv, (layout.leaf_slots, 1))}
    grid = (layout.n_blocks,)
    extra = (layout.block_leaf_ids(),)
    if kname == "spmd_leaf_r_partials":
        return Geometry(grid=grid, ins={"g": f32(blk), "g2": f32(blk)},
                        outs={"racc": a32}, extra=extra)
    racc = {"racc": a32}
    if kname == "spmd_vr_scale_apply":
        return Geometry(grid=grid,
                        ins={**meta, **racc, "g": f32(blk), "ga": f32(blk),
                             "g2": f32(blk)},
                        outs={"sg": f32(blk), "r": f32(blk)}, extra=extra)
    scal_op = {"scal": Operand(scal, (1, 8))}
    if kname == "spmd_vr_adam_apply":
        return Geometry(grid=grid,
                        ins={**meta, **racc, "g": f32(blk), "ga": f32(blk),
                             "g2": f32(blk), "m": sd(blk), "v": sd(blk),
                             "p": sd(blk), "w": sd(blk), **scal_op},
                        outs={"upd": f32(blk), "m_out": sd(blk),
                              "v_out": sd(blk), "p_out": sd(blk)}, extra=extra)
    if kname == "spmd_vr_lamb_compute":
        return Geometry(grid=grid,
                        ins={**meta, **racc, "g": f32(blk), "ga": f32(blk),
                             "g2": f32(blk), "m": sd(blk), "v": sd(blk),
                             "p": sd(blk), "w": sd(blk), **scal_op},
                        outs={"u": f32(blk), "m_out": sd(blk), "v_out": sd(blk),
                              "p_out": sd(blk), "uacc": a32, "wacc": a32},
                        extra=extra)
    # spmd_vr_lars_compute
    return Geometry(grid=grid,
                    ins={**meta, **racc, "g": f32(blk), "ga": f32(blk),
                         "g2": f32(blk), "w": sd(blk), **scal_op},
                    outs={"u": f32(blk), "uacc": a32, "wacc": a32}, extra=extra)


def _register():
    from repro.analysis.registry import register_kernel

    oracles = {
        "spmd_leaf_r_partials": "gsnr_r_raw_ref",
        "spmd_vr_scale_apply": "vr_scale_ref",
        "spmd_vr_adam_apply": "vr_adam_inner_ref",
        "spmd_vr_lamb_compute": "vr_lamb_inner_ref",
        "spmd_vr_lars_compute": "vr_lars_inner_ref",
    }
    for kname, oracle in oracles.items():
        register_kernel(
            kname, module=__name__, oracle=oracle,
            build=functools.partial(_analysis_geometry, kname),
            configs={
                "representative": dict(layout_kind="aligned"),
                "hostile_bf16_state": dict(layout_kind="hostile",
                                           state_dtype="bfloat16"),
            },
        )


_register()
