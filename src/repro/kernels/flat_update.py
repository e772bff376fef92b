"""Pallas TPU kernels: the entire VRGD update as ONE pallas_call.

Each kernel runs a two-phase grid ``(2, n_blocks)`` over the ParamLayout
flat buffer (core/layout.py).  Per-leaf ("layer") scalars are computed as
partial reductions into a (leaf_slots, LANE) VMEM accumulator, one row per
leaf, indexed by the block's leaf id — which the kernel reads from SMEM: the
(n_blocks,) leaf-id map is a scalar-prefetch operand, so the index maps and
the body see it with no (1, 1) VMEM block.

  flat_vr_scale  [r-mean partials] -> [scale]                 (VR-SGD/Mom.)
  flat_vr_adam   [r-mean partials] -> [full update]           (Alg. 3)
  flat_vr_lamb   [r-mean partials] -> [u + trust-norm partials]
  flat_vr_lars   [r-mean partials] -> [u + trust-norm partials]

Phase 0 folds the GSNR normalizer 1/mean(r) into the grid (no jnp prepass
sweeping g and g2 per leaf).  The LAMB/LARS trust ratio needs the norm of
the whole leaf's update before any of it can be applied; the kernel emits u
and the per-leaf norm partials (constant-index output blocks, written back
once after the last step) and a jnp epilogue (``lamb_epilogue`` /
``lars_epilogue``, shared with the sharded path in backend.FlatSpmd)
applies ``-lr * ratio[leaf] * u`` — one fused XLA pass over u.  The kernel
cannot apply it itself: that needs a second visit of every u block, and the
TPU pipeline never reads an output block back from HBM.

PHASE-AWARE INDEX MAPS: each (rows, LANE) operand carries the inclusive
phase window in which the kernel actually reads/writes it (PHASE_WINDOWS —
single source of truth, also replayed by benchmarks.cost_model).  Before its
window the operand's index map PARKS the block index at 0, the block its
window starts on, so consecutive grid steps return the same index and the
pipeline elides the copy: phase 0 reads only g and g2.  A parked output
window is never written while parked, and since it parks on the first block
of its window, that block's single copy-back comes after the kernel wrote it.

Semantics match the per-tensor jnp references (kernels/ref.py) and the jnp
path exactly (tests/test_oracle.py + tests/test_layout.py):
the GSNR ratio derives from the raw group moments (g, g2) but scales the
possibly grad-clipped gradient ga; moments are stored in ``state_dtype``
with all math in f32; zero tail padding (g = ga = w = 0) keeps every
in-kernel reduction exact without masking.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.backend import resolve_interpret
from repro.core.layout import LANE, ParamLayout

_f32 = jnp.float32


def _specs(layout: ParamLayout):
    """(inv-size, scalar, accumulator) BlockSpecs for a (ph, b) grid whose
    index maps also receive the scalar-prefetched leaf-id ref."""
    inv = pl.BlockSpec((layout.leaf_slots, 1), lambda ph, b, lids: (0, 0))
    scal = pl.BlockSpec((1, 8), lambda ph, b, lids: (0, 0))
    acc = pl.BlockSpec((layout.leaf_slots, LANE), lambda ph, b, lids: (0, 0))
    return inv, scal, acc


# Inclusive phase windows per (rows, LANE) operand: the phases in which each
# kernel actually reads/writes it.  SINGLE SOURCE OF TRUTH for the
# phase-aware BlockSpecs below AND for benchmarks.cost_model, which replays
# the index maps to count the DMA savings.  Every window ends in the last
# phase, so operands park only before their window.
PHASE_WINDOWS = {
    "flat_vr_scale": dict(
        ins=dict(g=(0, 1), ga=(1, 1), g2=(0, 1)),
        outs=dict(sg=(1, 1), r=(1, 1)),
    ),
    "flat_vr_adam": dict(
        ins=dict(g=(0, 1), ga=(1, 1), g2=(0, 1), m=(1, 1), v=(1, 1),
                 p=(1, 1), w=(1, 1)),
        outs=dict(upd=(1, 1), m_out=(1, 1), v_out=(1, 1), p_out=(1, 1)),
    ),
    "flat_vr_lamb": dict(
        ins=dict(g=(0, 1), ga=(1, 1), g2=(0, 1), m=(1, 1), v=(1, 1),
                 p=(1, 1), w=(1, 1)),
        outs=dict(u=(1, 1), m_out=(1, 1), v_out=(1, 1), p_out=(1, 1)),
    ),
    "flat_vr_lars": dict(
        ins=dict(g=(0, 1), ga=(1, 1), g2=(0, 1), w=(1, 1)),
        outs=dict(u=(1, 1)),
    ),
}
N_PHASES = 2


def _phased_blk(layout: ParamLayout, lo: int):
    """Row-block spec live from phase ``lo`` on: earlier phases park the
    window at block 0 — the block the window starts on — so the parked steps
    cost no DMA and a parked output is copied back only after its first live
    step wrote it."""
    if lo == 0:
        return pl.BlockSpec((layout.block_rows, LANE), lambda ph, b, lids: (b, 0))
    return pl.BlockSpec(
        (layout.block_rows, LANE), lambda ph, b, lids: (b * (ph >= lo), 0)
    )


def _phased_specs(layout: ParamLayout, name: str):
    """{operand: BlockSpec} dicts (ins, outs) from PHASE_WINDOWS[name]."""
    pw = PHASE_WINDOWS[name]
    for lo, hi in (*pw["ins"].values(), *pw["outs"].values()):
        assert hi == N_PHASES - 1, f"{name}: windows must end in the last phase"
    ins = {k: _phased_blk(layout, lo) for k, (lo, _) in pw["ins"].items()}
    outs = {k: _phased_blk(layout, lo) for k, (lo, _) in pw["outs"].items()}
    return ins, outs


def _leaf_inv(layout: ParamLayout):
    return jnp.asarray(layout.leaf_inv_sizes())


def _scal8(*vals) -> jnp.ndarray:
    """Dynamic scalars packed into one (1, 8) f32 block."""
    v = list(vals) + [0.0] * (8 - len(vals))
    return jnp.stack([jnp.asarray(x, _f32) for x in v]).reshape(1, 8)


def _leaf_scalar(ref, leaf):
    """Read one per-leaf scalar from a (leaf_slots, ...) ref row."""
    return jnp.sum(ref[pl.ds(leaf, 1), :])


def _raw_r(g_ref, g2_ref, gsnr_eps):
    g = g_ref[...].astype(_f32)
    g2 = g2_ref[...].astype(_f32)
    var = jnp.maximum(g2 - g * g, 0.0)
    return (g * g) / (var + gsnr_eps)


def _inv_mean_r(racc_ref, invsz_ref, leaf):
    mean_r = _leaf_scalar(racc_ref, leaf) * _leaf_scalar(invsz_ref, leaf)
    return 1.0 / jnp.maximum(mean_r, 1e-30)


def _accumulate_r(racc_ref, g_ref, g2_ref, leaf, gsnr_eps):
    """Phase 0 / the sharded partials kernel: this block's Σ r_raw into its
    leaf's accumulator row."""
    racc_ref[pl.ds(leaf, 1), :] += jnp.sum(
        _raw_r(g_ref, g2_ref, gsnr_eps), axis=0, keepdims=True
    )


def _call(kernel, lids, n_blocks: int, in_specs, out_specs, out_shape, scratch,
          interpret, *args, phases: int = N_PHASES, aliases=None):
    """A (phases, n_blocks) grid — or a 1-D (n_blocks,) one when phases is
    None — with the (n_blocks,) leaf-id map scalar-prefetched into SMEM.

    ``aliases`` maps an input's index in ``args`` to the output written in
    its place: every aliased pair is read and written at the same block of
    the same grid step, so the update runs in place and a donated optimizer
    state is not copied (three param-sized buffers for VR-LAMB)."""
    grid = (n_blocks,) if phases is None else (phases, n_blocks)
    return pl.pallas_call(
        kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1, grid=grid, in_specs=in_specs,
            out_specs=out_specs, scratch_shapes=scratch,
        ),
        out_shape=out_shape,
        input_output_aliases={i + 1: o for i, o in (aliases or {}).items()},
        interpret=resolve_interpret(interpret),
    )(lids, *args)


def _two_phase_kernel(lids_ref, invsz_ref, g_ref, g2_ref, ga_ref, *rest, compute,
                      gsnr_eps, n_norm_accs):
    """Phase 0: this block's Σ r_raw into its leaf's row of the r scratch
    (the last ref).  Phase 1: ``compute`` — the optimizer's block body
    (_scale_apply, _adam_apply, _lamb_compute, _lars_compute) on its other
    inputs and outputs (``rest[:-1]``).  LAMB/LARS's last two outputs are
    the per-leaf norm accumulators, zeroed with the r scratch on the first
    step and written back once after the last."""
    ph, b = pl.program_id(0), pl.program_id(1)
    racc_ref = rest[-1]
    accs = rest[-1 - n_norm_accs:-1]

    @pl.when((ph == 0) & (b == 0))
    def _init():
        for ref in (racc_ref, *accs):
            ref[...] = jnp.zeros_like(ref)

    leaf = lids_ref[b]

    @pl.when(ph == 0)
    def _reduce():
        _accumulate_r(racc_ref, g_ref, g2_ref, leaf, gsnr_eps)

    @pl.when(ph == 1)
    def _compute():
        compute(leaf, racc_ref, invsz_ref, g_ref, ga_ref, g2_ref, *rest[:-1])


def _launch(layout: ParamLayout, compute, gsnr_eps, n_norm_accs, in_specs, out_specs,
            out_shape, interpret, invsz, g, g2, ga, *args, aliases=None):
    """The (2, n_blocks) launch of _two_phase_kernel over the whole buffer;
    ``aliases`` as in _call (input 3 is ga, 4.. the state)."""
    lids = jnp.asarray(layout.block_leaf_ids())
    return _call(
        functools.partial(_two_phase_kernel, compute=compute, gsnr_eps=gsnr_eps,
                          n_norm_accs=n_norm_accs),
        lids, layout.n_blocks, in_specs, out_specs, out_shape,
        [pltpu.VMEM((layout.leaf_slots, LANE), _f32)], interpret,
        invsz, g, g2, ga, *args, aliases=aliases,
    )


# ---------------------------------------------------------------------------
# VR scale (VR-SGD / VR-Momentum hot path)
# ---------------------------------------------------------------------------


def _scale_apply(leaf, racc_ref, invsz_ref, g_ref, ga_ref, g2_ref, sg_ref, r_ref,
                 *, gamma, eps):
    r = jnp.clip(
        _raw_r(g_ref, g2_ref, eps) * _inv_mean_r(racc_ref, invsz_ref, leaf), gamma, 1.0
    )
    sg_ref[...] = r * ga_ref[...].astype(_f32)
    r_ref[...] = r


@functools.partial(jax.jit, static_argnames=("layout", "gamma", "eps", "interpret"))
def flat_vr_scale(g, ga, g2, layout: ParamLayout, *, gamma, eps,
                  interpret: bool | None = None):
    """Fused (scaled_grad, r) over the whole flat buffer: one launch."""
    inv, _, _ = _specs(layout)
    pin, pout = _phased_specs(layout, "flat_vr_scale")
    sds = jax.ShapeDtypeStruct((layout.n_rows, LANE), _f32)
    return _launch(
        layout, functools.partial(_scale_apply, gamma=gamma, eps=eps), eps, 0,
        [inv, pin["g"], pin["g2"], pin["ga"]], (pout["sg"], pout["r"]), (sds, sds),
        interpret, _leaf_inv(layout), g, g2, ga,
    )


# ---------------------------------------------------------------------------
# VR-Adam (paper Alg. 3): full update incl. weight decay and -lr
# ---------------------------------------------------------------------------


def _adam_math(r_raw, inv_mean, ga_ref, m_ref, v_ref, p_ref, scal_ref, *, b1, b2, b3, gamma, eps):
    """Shared element-wise chain: GSNR r -> p momentum -> ghat -> m/v ->
    bias-corrected Adam direction.  Returns (direction, m', v', p')."""
    bc1, bc2, bc3 = scal_ref[0, 1], scal_ref[0, 2], scal_ref[0, 3]
    ga = ga_ref[...].astype(_f32)
    m = m_ref[...].astype(_f32)
    v = v_ref[...].astype(_f32)
    p = p_ref[...].astype(_f32)
    r = jnp.clip(r_raw * inv_mean, gamma, 1.0)
    p_new = b3 * p + (1.0 - b3) * r
    ghat = (p_new / bc3) * ga
    m_new = b1 * m + (1.0 - b1) * ghat
    v_new = b2 * v + (1.0 - b2) * ghat * ghat
    direction = (m_new / bc1) / (jnp.sqrt(v_new / bc2) + eps)
    return direction, m_new, v_new, p_new


def _adam_apply(leaf, racc_ref, invsz_ref, g_ref, ga_ref, g2_ref, m_ref, v_ref,
                p_ref, w_ref, scal_ref, upd_ref, m_out, v_out, p_out,
                *, b1, b2, b3, eps, wd, gamma, gsnr_eps):
    direction, m_new, v_new, p_new = _adam_math(
        _raw_r(g_ref, g2_ref, gsnr_eps), _inv_mean_r(racc_ref, invsz_ref, leaf),
        ga_ref, m_ref, v_ref, p_ref, scal_ref,
        b1=b1, b2=b2, b3=b3, gamma=gamma, eps=eps,
    )
    upd_ref[...] = -scal_ref[0, 0] * (direction + wd * w_ref[...].astype(_f32))
    m_out[...] = m_new.astype(m_out.dtype)
    v_out[...] = v_new.astype(v_out.dtype)
    p_out[...] = p_new.astype(p_out.dtype)


def _state_shapes(layout: ParamLayout, state_dtype):
    f32 = jax.ShapeDtypeStruct((layout.n_rows, LANE), _f32)
    sd = jax.ShapeDtypeStruct((layout.n_rows, LANE), jnp.dtype(state_dtype))
    return f32, sd


@functools.partial(
    jax.jit,
    static_argnames=(
        "layout", "b1", "b2", "b3", "eps", "wd", "gamma", "gsnr_eps", "state_dtype", "interpret",
    ),
)
def flat_vr_adam(
    g, ga, g2, m, v, p, w, scal, layout: ParamLayout,
    *, b1, b2, b3, eps, wd, gamma, gsnr_eps, state_dtype="float32",
    interpret: bool | None = None,
):
    """One launch for the full VR-Adam step: returns (upd, m', v', p').

    scal = _scal8(lr, bc1, bc2, bc3).  upd already includes weight decay and
    the -lr scale; m'/v'/p' come back in ``state_dtype``.
    """
    inv, scal_spec, _ = _specs(layout)
    pin, pout = _phased_specs(layout, "flat_vr_adam")
    f32, sd = _state_shapes(layout, state_dtype)
    compute = functools.partial(_adam_apply, b1=b1, b2=b2, b3=b3, eps=eps, wd=wd,
                                gamma=gamma, gsnr_eps=gsnr_eps)
    return _launch(
        layout, compute, gsnr_eps, 0,
        [inv] + [pin[n] for n in ("g", "g2", "ga", "m", "v", "p", "w")] + [scal_spec],
        tuple(pout[n] for n in ("upd", "m_out", "v_out", "p_out")), (f32, sd, sd, sd),
        interpret, _leaf_inv(layout), g, g2, ga, m, v, p, w, scal,
        aliases={3: 0, 4: 1, 5: 2, 6: 3},
    )


# ---------------------------------------------------------------------------
# VR-LAMB (paper Alg. 5) / VR-LARS (§4.2): u + per-leaf trust-norm partials
# in the kernel, the trust-ratio apply in a jnp epilogue
# ---------------------------------------------------------------------------


def _add_norms(uacc_ref, wacc_ref, leaf, u, w):
    # padded tail: g = ga = w = 0 -> u = 0, so the partials see exact zeros
    uacc_ref[pl.ds(leaf, 1), :] += jnp.sum(u * u, axis=0, keepdims=True)
    wacc_ref[pl.ds(leaf, 1), :] += jnp.sum(w * w, axis=0, keepdims=True)


def _lamb_compute(leaf, racc_ref, invsz_ref, g_ref, ga_ref, g2_ref, m_ref, v_ref,
                  p_ref, w_ref, scal_ref, u_ref, m_out, v_out, p_out, uacc_ref,
                  wacc_ref, *, b1, b2, b3, eps, wd, gamma, gsnr_eps):
    w = w_ref[...].astype(_f32)
    direction, m_new, v_new, p_new = _adam_math(
        _raw_r(g_ref, g2_ref, gsnr_eps), _inv_mean_r(racc_ref, invsz_ref, leaf),
        ga_ref, m_ref, v_ref, p_ref, scal_ref,
        b1=b1, b2=b2, b3=b3, gamma=gamma, eps=eps,
    )
    u = direction + wd * w
    u_ref[...] = u
    m_out[...] = m_new.astype(m_out.dtype)
    v_out[...] = v_new.astype(v_out.dtype)
    p_out[...] = p_new.astype(p_out.dtype)
    _add_norms(uacc_ref, wacc_ref, leaf, u, w)


def _lars_compute(leaf, racc_ref, invsz_ref, g_ref, ga_ref, g2_ref, w_ref, scal_ref,
                  u_ref, uacc_ref, wacc_ref, *, wd, eps):
    w = w_ref[...].astype(_f32)
    r = jnp.clip(
        _raw_r(g_ref, g2_ref, eps) * _inv_mean_r(racc_ref, invsz_ref, leaf),
        scal_ref[0, 1], 1.0,
    )
    u = r * ga_ref[...].astype(_f32) + wd * w
    u_ref[...] = u
    _add_norms(uacc_ref, wacc_ref, leaf, u, w)


def trust_from_partials(uacc, wacc, *, numer_is_phi: bool, trust: float):
    """Per-leaf LAMB (phi(||w||)) or LARS (trust*||w||) ratio from the
    (leaf_slots, LANE) norm partials.  The phi clamp must stay in lockstep
    with baselines._lamb_phi (the jnp oracle)."""
    un = jnp.sqrt(jnp.sum(uacc, axis=1))
    pn = jnp.sqrt(jnp.sum(wacc, axis=1))
    numer = jnp.clip(pn, 0.0, 10.0) if numer_is_phi else trust * pn
    return jnp.where((pn > 0) & (un > 0), numer / (un + 1e-12), 1.0)


def _per_block(leaf_vals, lids, x):
    """x (rows, LANE) scaled block-wise by its leaf's scalar."""
    nb = lids.shape[0]
    return (leaf_vals[lids][:, None, None] * x.reshape(nb, -1, LANE)).reshape(x.shape)


def lamb_epilogue(u, uacc, wacc, lr, lids):
    """upd = -lr * phi-trust-ratio[leaf] * u — one fused XLA pass over u."""
    ratio = trust_from_partials(uacc, wacc, numer_is_phi=True, trust=0.0)
    return _per_block(-lr * ratio, lids, u)


def lars_epilogue(u, m, uacc, wacc, lr, lids, *, mu, trust):
    """m' = mu * m + trust-ratio[leaf] * u; upd = -lr * m'."""
    ratio = trust_from_partials(uacc, wacc, numer_is_phi=False, trust=trust)
    m_new = mu * m.astype(_f32) + _per_block(ratio, lids, u)
    return -lr * m_new, m_new


@functools.partial(
    jax.jit,
    static_argnames=(
        "layout", "b1", "b2", "b3", "eps", "wd", "gamma", "gsnr_eps", "state_dtype", "interpret",
    ),
)
def flat_vr_lamb(
    g, ga, g2, m, v, p, w, scal, layout: ParamLayout,
    *, b1, b2, b3, eps, wd, gamma, gsnr_eps, state_dtype="float32",
    interpret: bool | None = None,
):
    """One launch for the full VR-LAMB step: returns (upd, m', v', p').

    Two grid phases (r-mean partials; element-wise update + trust-ratio norm
    partials), then the jnp trust-ratio epilogue (-lr * ratio * u).
    """
    inv, scal_spec, acc = _specs(layout)
    pin, pout = _phased_specs(layout, "flat_vr_lamb")
    f32, sd = _state_shapes(layout, state_dtype)
    acc_sds = jax.ShapeDtypeStruct((layout.leaf_slots, LANE), _f32)
    compute = functools.partial(_lamb_compute, b1=b1, b2=b2, b3=b3, eps=eps, wd=wd,
                                gamma=gamma, gsnr_eps=gsnr_eps)
    u, m2, v2, p2, uacc, wacc = _launch(
        layout, compute, gsnr_eps, 2,
        [inv] + [pin[n] for n in ("g", "g2", "ga", "m", "v", "p", "w")] + [scal_spec],
        tuple(pout[n] for n in ("u", "m_out", "v_out", "p_out")) + (acc, acc),
        (f32, sd, sd, sd, acc_sds, acc_sds),
        interpret, _leaf_inv(layout), g, g2, ga, m, v, p, w, scal,
        aliases={3: 0, 4: 1, 5: 2, 6: 3},
    )
    lids = jnp.asarray(layout.block_leaf_ids())
    return lamb_epilogue(u, uacc, wacc, scal[0, 0], lids), m2, v2, p2


@functools.partial(
    jax.jit, static_argnames=("layout", "mu", "wd", "trust", "eps", "interpret")
)
def flat_vr_lars(
    g, ga, g2, m, w, scal, layout: ParamLayout,
    *, mu, wd, trust, eps, interpret: bool | None = None,
):
    """One launch for the full VR-LARS step: returns (upd, m').

    scal = _scal8(lr, gamma) — gamma rides in the scalar block because the
    LARS tests sweep it densely and a static gamma would retrace per value.
    The trust ratio and the heavy-ball momentum fold run in the jnp epilogue.
    """
    inv, scal_spec, acc = _specs(layout)
    pin, pout = _phased_specs(layout, "flat_vr_lars")
    sds = jax.ShapeDtypeStruct((layout.n_rows, LANE), _f32)
    acc_sds = jax.ShapeDtypeStruct((layout.leaf_slots, LANE), _f32)
    u, uacc, wacc = _launch(
        layout, functools.partial(_lars_compute, wd=wd, eps=eps), eps, 2,
        [inv] + [pin[n] for n in ("g", "g2", "ga", "w")] + [scal_spec],
        (pout["u"], acc, acc), (sds, acc_sds, acc_sds),
        interpret, _leaf_inv(layout), g, g2, ga, w, scal, aliases={3: 0},
    )
    lids = jnp.asarray(layout.block_leaf_ids())
    return lars_epilogue(u, m, uacc, wacc, scal[0, 0], lids, mu=mu, trust=trust)


# ---------------------------------------------------------------------------
# contract registration (repro.analysis): replayable geometries built from
# the SAME _specs/_phased_specs/PHASE_WINDOWS the launches above use
# ---------------------------------------------------------------------------


def _analysis_geometry(name: str, *, layout_kind: str = "hostile",
                       state_dtype: str = "float32"):
    from repro.analysis.registry import Geometry, Operand, demo_layout

    layout = demo_layout(layout_kind)
    pw = PHASE_WINDOWS[name]
    pin, pout = _phased_specs(layout, name)
    inv, scal, acc = _specs(layout)
    rows = (layout.n_rows, LANE)
    acc_shape = (layout.leaf_slots, LANE)

    # gradient streams and the f32 outputs stay f32; m/v/p/w ride state_dtype
    def dt(stream):
        return "float32" if stream in ("g", "ga", "g2", "upd", "u", "sg", "r") else state_dtype

    ins = {"inv": Operand(inv, (layout.leaf_slots, 1))}
    for k, win in pw["ins"].items():
        ins[k] = Operand(pin[k], rows, dtype=dt(k), window=win)
    if name != "flat_vr_scale":
        ins["scal"] = Operand(scal, (1, 8))
    outs = {k: Operand(pout[k], rows, dtype=dt(k), window=win)
            for k, win in pw["outs"].items()}
    if name in ("flat_vr_lamb", "flat_vr_lars"):
        outs["uacc"] = Operand(acc, acc_shape)
        outs["wacc"] = Operand(acc, acc_shape)
    return Geometry(
        grid=(N_PHASES, layout.n_blocks),
        ins=ins,
        outs=outs,
        scratch_bytes=layout.leaf_slots * LANE * 4,
        extra=(layout.block_leaf_ids(),),
        phase_axis=0,
    )


def _register():
    from repro.analysis.registry import register_kernel

    oracles = {
        "flat_vr_scale": "vr_scale_ref",
        "flat_vr_adam": "vr_adam_inner_ref",
        "flat_vr_lamb": "vr_lamb_inner_ref",
        "flat_vr_lars": "vr_lars_inner_ref",
    }
    for kname in PHASE_WINDOWS:
        register_kernel(
            kname,
            module=__name__,
            oracle=oracles[kname],
            build=functools.partial(_analysis_geometry, kname),
            configs={
                "representative": dict(layout_kind="aligned"),
                "hostile_ragged": dict(layout_kind="hostile"),
                "hostile_bf16_state": dict(layout_kind="hostile",
                                           state_dtype="bfloat16"),
            },
        )


_register()
