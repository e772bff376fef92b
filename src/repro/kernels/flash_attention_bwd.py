"""Pallas TPU kernels: recomputation-based flash-attention backward
(FlashAttention-2, Dao 2023, Alg. 2), GQA-aware and position/segment-aware,
plus the differentiable jnp replicas used as the second-order VJP fallback
and as oracles.

Residual contract (from kernels/flash_attention.py): per query row
``lse = m + log l`` (NEG_INF for rows with no valid kv) and the jnp
preprocess ``delta_i = <dO_i, O_i>``, both shaped (B, H, S, 1) f32 on the
kernels' head-major layout (q/do (B, H, S, D), k/v (B, KV, S, D)).  With p
recomputed as ``exp(scale * q k^T - lse)`` (already softmax-normalized):

    dv_j = sum_i p_ij dO_i
    dp_ij = dO_i . v_j
    dS_ij = p_ij (dp_ij - delta_i) * scale
    dq_i = sum_j dS_ij k_j           dk_j = sum_i dS_ij q_i

ONE kernel on grid (B, KV, nk, G*nq): the inner dim walks every
(group member, q block) pair while the kv block stays resident, so the
s = q kᵀ / p recompute is shared — each tile pair does 5 matmuls
(s, dp, dv, dk, dq) where split dq + dk/dv kernels do 7 (s and dp
recomputed by both), and the grad launch count is 2 (delta preprocess
stays jnp):

  * dk/dv accumulate in VMEM scratch owned by the resident kv block
    (init at t == 0, finalized into the kv-head-shaped outputs at
    t == G*nq - 1, the GQA group-sum folded into the same sweep);
  * dq accumulates in an f32 VMEM scratch holding the whole q side of the
    kv group, (G*nq, block_q, D) — G*Sq*D*4 bytes, 4 MiB for G=4, Sq=4096,
    D=64 — zeroed at the group's first step so all-dead rows emit exact
    zeros, and written to its output block once, on the last kv step.  An
    output block that is left and later revisited is NOT re-fetched from
    HBM by the TPU pipeline, so dq cannot accumulate through its output
    window; its index map parks until the last kv step instead
    (bwd_geometry).

That scratch grows with G*Sq*D: 96 MiB for granite-20b (G=48, D=128) at
Sq=4096.  Where the one-pass kernel's working set would pass half the VMEM
budget (``fused_vmem_bytes``; the other half is headroom for the lane
padding of the (block, 1) columns and the (block_q, block_k) f32
intermediates the estimate leaves out), the backward SPLITS: the same
kernel without dq computes dk/dv, and a q-outer dq kernel on grid
(B, H, nq, nk) accumulates one (block_q, D) block across the kv sweep —
VMEM independent of the sequence length, at the cost of recomputing s and
dp and a third launch.

Both kernels take the same (q_pos, k_pos, q_seg, k_seg) operands as the
forward and mask through the SAME tile_mask rule — positions < 0 are
padding, segments gate cross-document pairs, and the q-side bound of
partial edge blocks is folded into the sanitized loads (out-of-range q rows
arrive as pos -1 / seg -1, and their q/do/lse/delta streams are zeroed so
they contribute nothing to the dk/dv reductions; interpret mode pads
partial blocks with NaN, and 0 * NaN would otherwise poison a whole kv
block).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.analysis.layout_contracts import DOUBLE_BUFFER, VMEM_BUDGET_BYTES

# the masking rule, pos/seg sanitization, dead-tile predicate and OOB zeroing
# are SHARED with the forward kernel: the backward's softmax recompute
# p = exp(s - lse) is only valid against the exact mask the forward's lse was
# built under
from repro.kernels.flash_attention import (
    DEFAULT_BLOCK_K,
    DEFAULT_BLOCK_Q,
    NEG_INF,
    _load_pos_seg,
    _maybe_skip_dead_tile,
    pos_operands,
    tile_mask,
    zero_oob_rows,
)
# the LSE-emitting jnp forward replica IS the naive attention oracle
# (kernels/ref.py) — one masked-softmax implementation; re-exported so the
# custom-VJP wiring reads fab.attention_fwd_ref next to fab.attention_bwd_ref
from repro.kernels import ref as rf
from repro.kernels.ref import attention_fwd_ref  # noqa: F401


def _dot(a, b, dims):
    return jax.lax.dot_general(a, b, (dims, ((), ())), preferred_element_type=jnp.float32)


def _load_q_side(q_ref, do_ref, lse_ref, delta_ref, iq, block_q, seq_q):
    """Sanitized q-side streams: OOB rows of partial q blocks zeroed."""
    q, q_valid = zero_oob_rows(q_ref[...].astype(jnp.float32), iq, block_q, seq_q)
    do, _ = zero_oob_rows(do_ref[...].astype(jnp.float32), iq, block_q, seq_q)
    lse = jnp.where(q_valid, lse_ref[...], 0.0)  # (BQ, 1)
    delta = jnp.where(q_valid, delta_ref[...], 0.0)
    return q, do, lse, delta


def _load_kv_side(k_ref, v_ref, ik, block_k, seq_kv):
    k, _ = zero_oob_rows(k_ref[...].astype(jnp.float32), ik, block_k, seq_kv)
    v, _ = zero_oob_rows(v_ref[...].astype(jnp.float32), ik, block_k, seq_kv)
    return k, v


def _p_ds(q, k, v, do, lse, delta, mask, scale):
    """Shared recompute: (p, dS) for one (BQ, BK) tile; lse/delta (BQ, 1)."""
    s = _dot(q * scale, k, ((1,), (1,)))  # (BQ, BK)
    s = jnp.where(mask, s, NEG_INF)
    # exact zeros off-mask; fully-masked rows carry lse == NEG_INF, so the
    # unmasked exp may overflow to inf there before the where kills it.
    p = jnp.where(mask, jnp.exp(s - lse), 0.0)
    dp = _dot(do, v, ((1,), (1,)))  # (BQ, BK)
    ds = p * (dp - delta) * scale
    return p, ds


def _fused_bwd_kernel(
    q_ref, k_ref, v_ref, lse_ref, delta_ref, do_ref, qp_ref, kp_ref, qs_ref, ks_ref,
    *rest, causal: bool, window: int, block_q: int, block_k: int, scale: float,
    seq_q: int, seq_kv: int, nq: int, nk: int, g: int, implicit: bool, with_dq: bool,
):
    if with_dq:
        dq_ref, dk_ref, dv_ref, dq_scr, dk_scr, dv_scr = rest
    else:
        dk_ref, dv_ref, dk_scr, dv_scr = rest
    ik = pl.program_id(2)
    t = pl.program_id(3)  # inner sweep over (group member, q block) pairs
    iq = t % nq

    @pl.when(t == 0)
    def _init():
        dk_scr[...] = jnp.zeros_like(dk_scr)
        dv_scr[...] = jnp.zeros_like(dv_scr)

    if with_dq:
        # dq for every (group member, q block) of this kv group accumulates
        # in VMEM across the kv sweep; zeroed once per group so q rows that
        # reach no kv at all still emit exact zeros
        @pl.when((ik == 0) & (t == 0))
        def _init_dq():
            dq_scr[...] = jnp.zeros_like(dq_scr)

    qp, qs = _load_pos_seg(qp_ref, qs_ref, iq, block_q, seq_q, seg_fill=-1)
    kp, ks = _load_pos_seg(kp_ref, ks_ref, ik, block_k, seq_kv, seg_fill=-2)

    def _compute():
        q, do, lse, delta = _load_q_side(q_ref, do_ref, lse_ref, delta_ref, iq, block_q, seq_q)
        k, v = _load_kv_side(k_ref, v_ref, ik, block_k, seq_kv)
        mask = tile_mask(qp, kp, qs, ks, causal, window)
        p, ds = _p_ds(q, k, v, do, lse, delta, mask, scale)
        dv_scr[...] += _dot(p, do, ((0,), (0,)))  # (BK, D)
        dk_scr[...] += _dot(ds, q, ((0,), (0,)))  # (BK, D)
        if with_dq:
            dq_scr[t] += _dot(ds, k, ((1,), (0,)))  # (BQ, D)

    _maybe_skip_dead_tile(_compute, qp, kp, qs, ks, causal, window,
                          implicit=implicit, iq=iq, ik=ik,
                          block_q=block_q, block_k=block_k)

    if with_dq:
        # the dq window is parked on this group's first block until the last
        # kv step (bwd_geometry), so it is written — and written back —
        # exactly once
        @pl.when(ik == nk - 1)
        def _write_dq():
            dq_ref[...] = dq_scr[t].astype(dq_ref.dtype)

    @pl.when(t == g * nq - 1)
    def _finalize():
        dk_ref[...] = dk_scr[...].astype(dk_ref.dtype)
        dv_ref[...] = dv_scr[...].astype(dv_ref.dtype)


def _dq_kernel(
    q_ref, k_ref, v_ref, lse_ref, delta_ref, do_ref, qp_ref, kp_ref, qs_ref, ks_ref,
    dq_ref, dq_scr,
    *, causal: bool, window: int, block_q: int, block_k: int, scale: float,
    seq_q: int, seq_kv: int, nk: int, implicit: bool,
):
    """Split-path dq: one q block resident, the kv blocks innermost; dq's
    output block recurs only on consecutive steps."""
    iq = pl.program_id(2)
    ik = pl.program_id(3)

    @pl.when(ik == 0)
    def _init():
        dq_scr[...] = jnp.zeros_like(dq_scr)

    qp, qs = _load_pos_seg(qp_ref, qs_ref, iq, block_q, seq_q, seg_fill=-1)
    kp, ks = _load_pos_seg(kp_ref, ks_ref, ik, block_k, seq_kv, seg_fill=-2)

    def _compute():
        q, do, lse, delta = _load_q_side(q_ref, do_ref, lse_ref, delta_ref, iq, block_q, seq_q)
        k, v = _load_kv_side(k_ref, v_ref, ik, block_k, seq_kv)
        mask = tile_mask(qp, kp, qs, ks, causal, window)
        _, ds = _p_ds(q, k, v, do, lse, delta, mask, scale)
        dq_scr[...] += _dot(ds, k, ((1,), (0,)))  # (BQ, D)

    _maybe_skip_dead_tile(_compute, qp, kp, qs, ks, causal, window,
                          implicit=implicit, iq=iq, ik=ik,
                          block_q=block_q, block_k=block_k)

    @pl.when(ik == nk - 1)
    def _write():
        dq_ref[...] = dq_scr[...].astype(dq_ref.dtype)


def check_bwd_shapes(q, k, v, lse, delta, do):
    """Loud shape validation for the backward residual contract (head-major
    layout: q/do (B,H,Sq,D), k/v (B,KV,Skv,D), lse/delta (B,H,Sq,1)).

    A mis-shaped lse/delta (or a do that doesn't match q) would otherwise
    reduce garbage into dk/dv.
    """
    b, h, sq, d = q.shape
    if do.shape != q.shape:
        raise ValueError(f"flash_attention_bwd: do {do.shape} must match q {q.shape}")
    if k.shape != v.shape:
        raise ValueError(f"flash_attention_bwd: k {k.shape} must match v {v.shape}")
    if k.shape[0] != b or k.shape[3] != d:
        raise ValueError(
            f"flash_attention_bwd: k {k.shape} incompatible with q {q.shape}"
        )
    for name, r in (("lse", lse), ("delta", delta)):
        if r.shape != (b, h, sq, 1):
            raise ValueError(
                f"flash_attention_bwd: {name} {r.shape} must be (B, H, Sq, 1)="
                f"{(b, h, sq, 1)}"
            )


def bwd_geometry(b, sq, h, d, skv, kvh, *, block_q: int, block_k: int,
                 with_dq: bool = True):
    """Grid, named BlockSpecs and array shapes of the fused backward.

    Single source of truth shared between flash_attention_bwd, the contract
    checker and benchmarks.cost_model (which replays the index maps with
    concrete grid indices to count block visits / HBM bytes).  Inner grid
    dim t = ig * nq + iq walks every query head of the GQA group (head index
    j*g + t//nq) and every q block while the kv block (b, j, ik) stays
    resident.  dq is written only on the last kv step: before it, its index
    map parks on the group's first block (the block the last step starts
    on), so no dq block is ever revisited or written back unwritten.
    ``with_dq=False`` is the split path's dk/dv-only kernel.
    """
    g = h // kvh
    nq = -(-sq // block_q)
    nk = -(-skv // block_k)
    grid = (b, kvh, nk, g * nq)
    q_spec = pl.BlockSpec(
        (None, None, block_q, d), lambda b_, j, ik, t: (b_, j * g + t // nq, t % nq, 0)
    )
    kv_spec = pl.BlockSpec((None, None, block_k, d), lambda b_, j, ik, t: (b_, j, ik, 0))
    res_spec = pl.BlockSpec(
        (None, None, block_q, 1), lambda b_, j, ik, t: (b_, j * g + t // nq, t % nq, 0)
    )
    qcol_spec = pl.BlockSpec((None, block_q, 1), lambda b_, j, ik, t: (b_, t % nq, 0))
    krow_spec = pl.BlockSpec((None, 1, block_k), lambda b_, j, ik, t: (b_, 0, ik))
    ins = {
        "q": q_spec, "k": kv_spec, "v": kv_spec, "lse": res_spec,
        "delta": res_spec, "do": q_spec, "q_pos": qcol_spec, "k_pos": krow_spec,
        "q_seg": qcol_spec, "k_seg": krow_spec,
    }
    outs = {"dk": kv_spec, "dv": kv_spec}
    if with_dq:
        outs = {"dq": pl.BlockSpec(
            (None, None, block_q, d),
            lambda b_, j, ik, t: (b_, j * g + (t // nq) * (ik == nk - 1),
                                  (t % nq) * (ik == nk - 1), 0),
        ), **outs}
    return grid, nq, nk, g, ins, outs, _bwd_shapes(b, h, sq, d, kvh, skv)


def dq_geometry(b, sq, h, d, skv, kvh, *, block_q: int, block_k: int):
    """Grid, named BlockSpecs and array shapes of the split path's dq kernel:
    grid (B, H, nq, nk), kv blocks innermost."""
    g = h // kvh
    nq = -(-sq // block_q)
    nk = -(-skv // block_k)
    grid = (b, h, nq, nk)
    q_spec = pl.BlockSpec((None, None, block_q, d), lambda b_, h_, iq, ik: (b_, h_, iq, 0))
    kv_spec = pl.BlockSpec((None, None, block_k, d),
                           lambda b_, h_, iq, ik: (b_, h_ // g, ik, 0))
    res_spec = pl.BlockSpec((None, None, block_q, 1), lambda b_, h_, iq, ik: (b_, h_, iq, 0))
    qcol_spec = pl.BlockSpec((None, block_q, 1), lambda b_, h_, iq, ik: (b_, iq, 0))
    krow_spec = pl.BlockSpec((None, 1, block_k), lambda b_, h_, iq, ik: (b_, 0, ik))
    ins = {
        "q": q_spec, "k": kv_spec, "v": kv_spec, "lse": res_spec,
        "delta": res_spec, "do": q_spec, "q_pos": qcol_spec, "k_pos": krow_spec,
        "q_seg": qcol_spec, "k_seg": krow_spec,
    }
    return grid, nq, nk, g, ins, {"dq": q_spec}, _bwd_shapes(b, h, sq, d, kvh, skv)


def _bwd_shapes(b, h, sq, d, kvh, skv):
    qs, kvs = (b, h, sq, d), (b, kvh, skv, d)
    return {
        "q": qs, "k": kvs, "v": kvs, "lse": (b, h, sq, 1), "delta": (b, h, sq, 1),
        "do": qs, "q_pos": (b, sq, 1), "k_pos": (b, 1, skv), "q_seg": (b, sq, 1),
        "k_seg": (b, 1, skv), "dq": qs, "dk": kvs, "dv": kvs,
    }


def fused_vmem_bytes(g, nq, block_q, block_k, d, itemsize) -> int:
    """The one-pass kernel's VMEM working set as the VMEM-BUDGET rule counts
    it: double-buffered operand windows (q/do/dq and k/v/dk/dv tiles, four
    f32 (block_q, 1) columns, two int32 (1, block_k) rows) plus scratch."""
    windows = (3 * block_q + 4 * block_k) * d * itemsize + (4 * block_q + 2 * block_k) * 4
    return DOUBLE_BUFFER * windows + (g * nq * block_q + 2 * block_k) * d * 4


def use_fused_dq(g, nq, block_q, block_k, d, itemsize) -> bool:
    """One pass when its working set stays within half the VMEM budget."""
    return (fused_vmem_bytes(g, nq, block_q, block_k, d, itemsize)
            <= VMEM_BUDGET_BYTES["tpu"] // 2)


def flash_attention_bwd(
    q, k, v, lse, delta, do, q_pos, k_pos, q_seg, k_seg,
    *, causal: bool, window: int, block_q: int, block_k: int, interpret: bool,
    implicit: bool = False,
):
    """(dq, dk, dv): one pallas_call, or two where the backward splits.

    q/do: (B,H,S,D); k/v: (B,KV,Skv,D); lse/delta: (B,H,S,1) f32;
    q_pos/q_seg: (B,S) int32; k_pos/k_seg: (B,Skv) int32.  The one-pass
    kernel holds dq for the whole q side of one kv group in VMEM; where that
    does not fit (``use_fused_dq``), dk/dv and dq run as two kernels.
    """
    check_bwd_shapes(q, k, v, lse, delta, do)
    b, h, sq, d = q.shape
    fused = use_fused_dq(h // k.shape[1], -(-sq // block_q), block_q, block_k, d,
                         q.dtype.itemsize)
    run = _one_pass_bwd if fused else _split_bwd
    return run(q, k, v, lse, delta, do, q_pos, k_pos, q_seg, k_seg, causal=causal,
               window=window, block_q=block_q, block_k=block_k, interpret=interpret,
               implicit=implicit)


def _bwd_call(q, k, *, causal, window, block_q, block_k, implicit, with_dq):
    """Geometry and kernel keywords shared by both paths' dk/dv launch."""
    b, h, sq, d = q.shape
    kvh, skv = k.shape[1], k.shape[2]
    geom = bwd_geometry(b, sq, h, d, skv, kvh, block_q=block_q, block_k=block_k,
                        with_dq=with_dq)
    kw = dict(causal=causal, window=window, block_q=block_q, block_k=block_k,
              scale=d**-0.5, seq_q=sq, seq_kv=skv, implicit=implicit)
    return geom, kw


def _dkv_outputs(k, v, block_k):
    d = k.shape[3]
    return ([jax.ShapeDtypeStruct(k.shape, k.dtype), jax.ShapeDtypeStruct(v.shape, v.dtype)],
            [pltpu.VMEM((block_k, d), jnp.float32), pltpu.VMEM((block_k, d), jnp.float32)])


def _one_pass_bwd(q, k, v, lse, delta, do, q_pos, k_pos, q_seg, k_seg,
                  *, interpret, **static):
    (grid, nq, nk, g, ins, outs, _), kw = _bwd_call(q, k, with_dq=True, **static)
    out_shape, scratch = _dkv_outputs(k, v, static["block_k"])
    return pl.pallas_call(
        functools.partial(_fused_bwd_kernel, nq=nq, nk=nk, g=g, with_dq=True, **kw),
        grid=grid,
        in_specs=list(ins.values()),
        out_specs=list(outs.values()),
        out_shape=[jax.ShapeDtypeStruct(q.shape, q.dtype), *out_shape],
        scratch_shapes=[pltpu.VMEM((g * nq, static["block_q"], q.shape[3]), jnp.float32),
                        *scratch],
        interpret=interpret,
    )(q, k, v, lse, delta, do, *pos_operands(q_pos, k_pos, q_seg, k_seg))


def _split_bwd(q, k, v, lse, delta, do, q_pos, k_pos, q_seg, k_seg,
               *, interpret, **static):
    """dk/dv from the one-pass kernel without dq, then the q-outer dq kernel."""
    operands = (q, k, v, lse, delta, do, *pos_operands(q_pos, k_pos, q_seg, k_seg))
    (grid, nq, nk, g, ins, outs, _), kw = _bwd_call(q, k, with_dq=False, **static)
    out_shape, scratch = _dkv_outputs(k, v, static["block_k"])
    dk, dv = pl.pallas_call(
        functools.partial(_fused_bwd_kernel, nq=nq, nk=nk, g=g, with_dq=False, **kw),
        grid=grid,
        in_specs=list(ins.values()),
        out_specs=list(outs.values()),
        out_shape=out_shape,
        scratch_shapes=scratch,
        interpret=interpret,
    )(*operands)
    b, h, sq, d = q.shape
    grid, nq, nk, _, ins, outs, _ = dq_geometry(
        b, sq, h, d, k.shape[2], k.shape[1], block_q=static["block_q"],
        block_k=static["block_k"])
    dq = pl.pallas_call(
        functools.partial(_dq_kernel, nk=nk, **kw),
        grid=grid,
        in_specs=list(ins.values()),
        out_specs=outs["dq"],
        out_shape=jax.ShapeDtypeStruct(q.shape, q.dtype),
        scratch_shapes=[pltpu.VMEM((static["block_q"], d), jnp.float32)],
        interpret=interpret,
    )(*operands)
    return dq, dk, dv


# ---------------------------------------------------------------------------
# differentiable jnp replicas: second-order VJP fallback + oracles
# ---------------------------------------------------------------------------


def attention_bwd_ref(
    q, k, v, lse, delta, do, *, causal: bool, window: int = 0,
    q_pos=None, k_pos=None, q_seg=None, k_seg=None,
):
    """jnp replica of the fused backward (differentiable; the 2nd-order path).

    Same inputs and head-major layout as flash_attention_bwd (pos/seg
    optional — implicit arange when omitted); returns (dq, dk, dv) in input
    dtypes.
    """
    b, h, sq, d = q.shape
    kvh, skv = k.shape[1], k.shape[2]
    g = h // kvh
    scale = d**-0.5
    qf = q.astype(jnp.float32).reshape(b, kvh, g, sq, d)
    dof = do.astype(jnp.float32).reshape(b, kvh, g, sq, d)
    kf, vf = k.astype(jnp.float32), v.astype(jnp.float32)
    mask = rf.attention_mask(
        sq, skv, causal, window, q_pos=q_pos, k_pos=k_pos, q_seg=q_seg, k_seg=k_seg
    )[:, None, None]  # (B|1, 1, 1, Sq, Skv)
    s = jnp.einsum("bkgqd,bksd->bkgqs", qf, kf) * scale
    s = jnp.where(mask, s, NEG_INF)
    p = jnp.where(mask, jnp.exp(s - lse.reshape(b, kvh, g, sq, 1)), 0.0)
    dv = jnp.einsum("bkgqs,bkgqd->bksd", p, dof)
    dp = jnp.einsum("bkgqd,bksd->bkgqs", dof, vf)
    ds = p * (dp - delta.reshape(b, kvh, g, sq, 1)) * scale
    dq = jnp.einsum("bkgqs,bksd->bkgqd", ds, kf).reshape(b, h, sq, d)
    dk = jnp.einsum("bkgqs,bkgqd->bksd", ds, qf)
    return dq.astype(q.dtype), dk.astype(k.dtype), dv.astype(v.dtype)


# ---------------------------------------------------------------------------
# contract registration (repro.analysis): on the one-pass path dq is the
# output that would recur once per kv step; its index map parks it until the
# last kv step (its live window on grid axis 2), so every output block is
# written in one run.  Each config is registered at the path
# flash_attention_bwd dispatches it to (use_fused_dq), so the VMEM-BUDGET
# rule sees the working set that really runs.
# ---------------------------------------------------------------------------

CONFIGS = {
    "representative": dict(B=2, S=512, H=8, KV=2, D=64),
    "hostile_gqa_bf16": dict(B=1, S=130, H=4, KV=1, D=32, dtype="bfloat16"),
    # granite-3-2b (GQA 32:8, D=64) at seq 4096: one pass, 4 MiB dq scratch
    "granite_3_2b_seq4096": dict(B=1, S=4096, H=32, KV=8, D=64, dtype="bfloat16"),
    # granite-20b (MQA 48:1, D=128) at seq 4096: a one-pass dq scratch of
    # 96 MiB, so the backward splits
    "granite_20b_seq4096": dict(B=1, S=4096, H=48, KV=1, D=128, dtype="bfloat16"),
}


def _blocks(S, block_q, block_k):
    return min(block_q, S), min(block_k, S)


def _fused_for(S, H, KV, D, dtype, bq, bk):
    from repro.analysis.layout_contracts import itemsize

    return use_fused_dq(H // KV, -(-S // bq), bq, bk, D, itemsize(dtype))


def _operands(ins, outs, shapes, dtype, dq_window=None):
    from repro.analysis.registry import Operand

    def op(name, spec):
        if name.endswith(("_pos", "_seg")):
            return Operand(spec, shapes[name], dtype="int32")
        if name in ("lse", "delta"):
            return Operand(spec, shapes[name], dtype="float32")
        if name == "dq":
            return Operand(spec, shapes[name], dtype=dtype, window=dq_window)
        return Operand(spec, shapes[name], dtype=dtype)

    return ({n: op(n, s) for n, s in ins.items()}, {n: op(n, s) for n, s in outs.items()})


def _analysis_geometry(B, S, H, KV, D, *, dtype="float32",
                       block_q=DEFAULT_BLOCK_Q, block_k=DEFAULT_BLOCK_K, with_dq=None):
    """The dk/dv (+ dq on the one-pass path) kernel; ``with_dq`` overrides
    the dispatch."""
    from repro.analysis.registry import Geometry

    bq, bk = _blocks(S, block_q, block_k)
    if with_dq is None:
        with_dq = _fused_for(S, H, KV, D, dtype, bq, bk)
    grid, nq, nk, g, ins, outs, shapes = bwd_geometry(B, S, H, D, S, KV, block_q=bq,
                                                      block_k=bk, with_dq=with_dq)
    ins, outs = _operands(ins, outs, shapes, dtype, dq_window=(nk - 1, nk - 1))
    dq_scratch = g * nq * bq if with_dq else 0
    return Geometry(grid=grid, ins=ins, outs=outs,
                    scratch_bytes=(dq_scratch + 2 * bk) * D * 4, phase_axis=2)


def _analysis_dq_geometry(B, S, H, KV, D, *, dtype="float32",
                          block_q=DEFAULT_BLOCK_Q, block_k=DEFAULT_BLOCK_K):
    """The split path's dq kernel."""
    from repro.analysis.registry import Geometry

    bq, bk = _blocks(S, block_q, block_k)
    grid, _, _, _, ins, outs, shapes = dq_geometry(B, S, H, D, S, KV, block_q=bq, block_k=bk)
    ins, outs = _operands(ins, outs, shapes, dtype)
    return Geometry(grid=grid, ins=ins, outs=outs, scratch_bytes=bq * D * 4)


def _register():
    from repro.analysis.registry import register_kernel

    oracle = "repro.kernels.flash_attention_bwd.attention_bwd_ref"
    register_kernel("flash_attention_bwd", module=__name__, oracle=oracle,
                    build=_analysis_geometry, configs=CONFIGS)
    # the dq kernel runs only where the backward splits; representative
    # widths are registered forced onto it as well
    register_kernel("flash_attention_bwd_dq", module=__name__, oracle=oracle,
                    build=_analysis_dq_geometry,
                    configs={n: CONFIGS[n] for n in
                             ("representative", "hostile_gqa_bf16", "granite_20b_seq4096")})


_register()
