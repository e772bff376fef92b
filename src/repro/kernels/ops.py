"""Jit'd wrappers integrating the Pallas kernels into the optimizer/model
stacks.  Platform handling (real Mosaic lowering on TPU, interpret mode
elsewhere so CPU tests execute the same kernel bodies) is centralized in
repro.backend: ``_interpret`` here delegates to ``backend.default_interpret``
and every wrapper takes an optional ``backend=`` (a repro.backend.Backend)
whose ``interpret_mode()`` overrides the platform probe, plus an optional
``spmd=`` plan (backend.FlatSpmd) that reroutes the flat-buffer calls through
their per-shard shard_map pipelines when the layout actually shards.

Every optimizer entry point here is ONE ``pallas_call`` over the
ParamLayout flat buffer (kernels/flat_update.py, kernels/flat_stats.py) —
no per-leaf dispatch loop, no per-leaf pad/unpad, and no jnp 1/mean(r)
prepass (the mean reduction runs as the kernel's first grid phase).

Every wrapper is required to be bit-for-bit interchangeable (up to f32
rounding and reduction order) with the jnp path in core/vrgd.py /
core/accumulate.py — the differential oracle harness enforces it.  Two
conventions keep the paths aligned:

  * the GSNR ratio derives from the raw group moments (stats.mean, sq_mean)
    but multiplies the gradient actually entering the update (the ``grads``
    argument, which global grad-clip may have rescaled);
  * optimizer moments are stored in ``state_dtype`` (math always f32).

Optimizer state arrives as FlatBuffer nodes (core/layout.py); tree-valued
inputs (tests, the reference-stats plans) are packed on entry.  A tree
whose structure diverges from the param layout fails loudly in
``ParamLayout.check_tree`` instead of deep inside flatten_up_to.
"""
from __future__ import annotations

from typing import Any, Tuple

import jax.numpy as jnp

from repro import backend as backend_mod
from repro import obs
from repro.core.gsnr import GradStats
from repro.core.layout import FlatBuffer, ParamLayout, is_flat
from repro.kernels import flash_attention as fa
from repro.kernels import flat_stats as fs
from repro.kernels import flat_update as fu


def _interpret() -> bool:
    """Delegates to the centralized platform probe (repro.backend)."""
    return backend_mod.default_interpret()


def _interp(backend=None) -> bool:
    return _interpret() if backend is None else backend.interpret_mode()


def _spmd_for(spmd, layout: ParamLayout):
    """The shard plan to use for this layout, or None (gathered path) when
    no plan was given or the buffer doesn't actually shard/divide."""
    return spmd if (spmd is not None and spmd.supports(layout)) else None


def count_pallas_calls(jaxpr) -> int:
    """Number of pallas_call equations anywhere in a (closed) jaxpr,
    recursing into scan/cond/jit sub-jaxprs — the structural check behind
    the one-launch-per-step guarantee (tests/test_layout.py, benchmarks)."""
    jaxpr = getattr(jaxpr, "jaxpr", jaxpr)
    n = 0
    for eqn in jaxpr.eqns:
        if eqn.primitive.name == "pallas_call":
            n += 1
        for v in eqn.params.values():
            vs = v if isinstance(v, (tuple, list)) else (v,)
            for u in vs:
                if hasattr(u, "jaxpr") or hasattr(u, "eqns"):
                    n += count_pallas_calls(u)
    return n


def _layout_for(*trees) -> ParamLayout:
    """The layout governing this update: taken from the first FlatBuffer
    (state/stats built it), else derived from the first pytree."""
    for t in trees:
        if is_flat(t):
            return t.layout
    for t in trees:
        if t is not None:
            return ParamLayout.for_tree(t)
    raise ValueError("no tree or FlatBuffer to derive a ParamLayout from")


def _flat(tree, layout: ParamLayout, dtype=jnp.float32) -> jnp.ndarray:
    """Raw flat buffer for a pytree or FlatBuffer (packing trees on entry)."""
    if is_flat(tree):
        return tree.data
    return layout.pack(tree, dtype)


def _fb(data, layout: ParamLayout) -> FlatBuffer:
    return FlatBuffer(data, layout)


def vr_scale_tree(stats: GradStats, grads, gamma: float, eps: float,
                  backend=None, spmd=None) -> Tuple[Any, Any]:
    """Fused (scaled_grads, r) over the whole parameter set: one launch
    (two per-shard launches + a leaf-scalar psum under an spmd plan).

    r comes from the group moments; it scales ``grads`` (the possibly
    grad-clipped gradient), matching the jnp path in vrgd._scaled_grads.
    Returns FlatBuffers (the VR-SGD/Momentum transforms keep state flat).
    """
    layout = _layout_for(stats.mean, grads)
    g = _flat(stats.mean, layout)
    ga = _flat(grads, layout)
    g2 = _flat(stats.sq_mean, layout)
    plan = _spmd_for(spmd, layout)
    if plan is not None:
        sg, r = plan.vr_scale(g, ga, g2, layout, gamma=gamma, eps=eps)
    else:
        sg, r = fu.flat_vr_scale(
            g, ga, g2, layout, gamma=gamma, eps=eps, interpret=_interp(backend)
        )
    return _fb(sg, layout), _fb(r, layout)


def _bias_corrections(state, b1, b2, b3):
    """(t, bc1, bc2, bc3) exactly as vrgd._vr_adam_dir computes them: all
    three moments correct by the optimizer step t."""
    t = state["step"] + 1
    tf = t.astype(jnp.float32)
    return t, 1 - b1**tf, 1 - b2**tf, 1 - b3**tf


def _state_flats(state, layout, state_dtype, keys=("m", "v", "p")):
    return [_flat(state[k_], layout, jnp.dtype(state_dtype)) for k_ in keys]


def _params_flat(params, layout, like):
    """Packed params for the weight-decay / trust-ratio stream (zeros when
    the transform was called without params — wd is skipped then)."""
    return jnp.zeros_like(like) if params is None else _flat(params, layout)


def vr_adam_update(
    grads, state, stats: GradStats, lr, b1, b2, b3, eps, wd, gamma, gsnr_eps,
    params, state_dtype: str = "float32", backend=None, spmd=None,
):
    """Full VR-Adam update as one launch; matches vrgd.vr_adam's jnp path."""
    t, bc1, bc2, bc3 = _bias_corrections(state, b1, b2, b3)
    layout = _layout_for(state["m"], params, stats.mean)
    g = _flat(stats.mean, layout)
    ga = _flat(grads, layout)
    g2 = _flat(stats.sq_mean, layout)
    m, v, p = _state_flats(state, layout, state_dtype)
    w = _params_flat(params, layout, g)
    use_wd = wd if params is not None else 0.0
    scal = fu._scal8(lr, bc1, bc2, bc3)
    kw = dict(
        b1=b1, b2=b2, b3=b3, eps=eps, wd=use_wd, gamma=gamma, gsnr_eps=gsnr_eps,
        state_dtype=state_dtype,
    )
    plan = _spmd_for(spmd, layout)
    if plan is not None:
        upd, m2, v2, p2 = plan.vr_adam(g, ga, g2, m, v, p, w, scal, layout, **kw)
    else:
        upd, m2, v2, p2 = fu.flat_vr_adam(
            g, ga, g2, m, v, p, w, scal, layout, interpret=_interp(backend), **kw
        )
    new_state = {"step": t, "m": _fb(m2, layout), "v": _fb(v2, layout), "p": _fb(p2, layout)}
    return layout.unpack(upd), new_state


def vr_lamb_update(
    grads, state, stats: GradStats, lr, b1, b2, b3, eps, wd, gamma, gsnr_eps,
    params, state_dtype: str = "float32", backend=None, spmd=None,
):
    """Full VR-LAMB update as one launch; matches vrgd.vr_lamb's jnp path."""
    t, bc1, bc2, bc3 = _bias_corrections(state, b1, b2, b3)
    layout = _layout_for(state["m"], params, stats.mean)
    g = _flat(stats.mean, layout)
    ga = _flat(grads, layout)
    g2 = _flat(stats.sq_mean, layout)
    m, v, p = _state_flats(state, layout, state_dtype)
    w = _params_flat(params, layout, g)
    scal = fu._scal8(lr, bc1, bc2, bc3)
    kw = dict(
        b1=b1, b2=b2, b3=b3, eps=eps, wd=wd, gamma=gamma, gsnr_eps=gsnr_eps,
        state_dtype=state_dtype,
    )
    plan = _spmd_for(spmd, layout)
    if plan is not None:
        upd, m2, v2, p2 = plan.vr_lamb(g, ga, g2, m, v, p, w, scal, layout, **kw)
    else:
        upd, m2, v2, p2 = fu.flat_vr_lamb(
            g, ga, g2, m, v, p, w, scal, layout, interpret=_interp(backend), **kw
        )
    new_state = {"step": t, "m": _fb(m2, layout), "v": _fb(v2, layout), "p": _fb(p2, layout)}
    return layout.unpack(upd), new_state


def vr_lars_update(grads, state, stats: GradStats, lr, mu, wd, trust, gamma, eps,
                   params, backend=None, spmd=None):
    """Full VR-LARS update as one launch; matches vrgd.vr_lars's jnp path
    (vr_scale -> baselines.lars) leaf for leaf."""
    layout = _layout_for(state["m"], params, stats.mean)
    g = _flat(stats.mean, layout)
    ga = _flat(grads, layout)
    g2 = _flat(stats.sq_mean, layout)
    m = _flat(state["m"], layout)
    w = _params_flat(params, layout, g)
    scal = fu._scal8(lr, gamma)
    plan = _spmd_for(spmd, layout)
    if plan is not None:
        upd, m2 = plan.vr_lars(g, ga, g2, m, w, scal, layout,
                               mu=mu, wd=wd, trust=trust, eps=eps)
    else:
        upd, m2 = fu.flat_vr_lars(
            g, ga, g2, m, w, scal, layout,
            mu=mu, wd=wd, trust=trust, eps=eps, interpret=_interp(backend),
        )
    new_state = {"step": state["step"] + 1, "m": _fb(m2, layout)}
    return layout.unpack(upd), new_state


# ---------------------------------------------------------------------------
# k-group moment accumulation (core/accumulate.py scan body)
# ---------------------------------------------------------------------------


def moments_init_flat(layout: ParamLayout):
    """Flat zero carries (g_sum, g2_sum) for the accumulation scan."""
    return layout.zeros(jnp.float32), layout.zeros(jnp.float32)


def moments_accum_flat(g_sum, g2_sum, grads, layout: ParamLayout,
                       backend=None, spmd=None):
    """One fused microbatch update of both flat moment carries (one launch);
    ``grads`` is the raw gradient pytree, packed here: every gradient element
    read and written once per microbatch, 35.6 ms of BERT-large's 0.55 s
    step at k = 8 on one TPU v5e (the chip benchmark's ``grad_pack_ms``)."""
    with obs.scope(obs.STATS_PACK):
        g = _flat(grads, layout)
    plan = _spmd_for(spmd, layout)
    with obs.scope(obs.STATS_ACCUM):
        if plan is not None:
            return plan.moments_accum(g_sum, g2_sum, g, layout)
        return fs.flat_moments_accum(g_sum, g2_sum, g, interpret=_interp(backend))


def moments_finalize_flat(g_sum, g2_sum, k, layout: ParamLayout,
                          backend=None, spmd=None) -> GradStats:
    """Fused /k normalize (one launch) -> GradStats carrying FlatBuffers."""
    plan = _spmd_for(spmd, layout)
    with obs.scope(obs.STATS_FINALIZE):
        if plan is not None:
            mean, sq = plan.moments_finalize(g_sum, g2_sum, k, layout)
        else:
            mean, sq = fs.flat_moments_finalize(g_sum, g2_sum, k, interpret=_interp(backend))
    return GradStats(mean=_fb(mean, layout), sq_mean=_fb(sq, layout), k=k)


def flash_attention(qh, k, v, q_pos=None, k_pos=None, *, q_seg=None, k_seg=None,
                    causal: bool = True, window: int = 0, backend=None):
    """Adapter for models/attention.py: qh (B,S,KV,G,D) -> (B,S,KV,G,D).

    Differentiable: the kernel carries a custom VJP whose backward runs the
    fused Pallas dq and dk/dv kernels (kernels/flash_attention_bwd.py), so
    fused-attention training keeps the whole attention fwd+bwd on the fused
    path.  Positions/segments are explicit kernel operands (packed and
    offset layouts run fused); omitted positions mean the implicit arange
    layout.  Segment ids are derived from the positions when not supplied.
    """
    b, s, kvh, g, d = qh.shape
    q = qh.reshape(b, s, kvh * g, d)
    out = fa.flash_attention(
        q, k, v, q_pos, k_pos, q_seg, k_seg,
        causal=causal, window=window, interpret=_interp(backend),
    )
    return out.reshape(b, s, kvh, g, d)


def flash_decode(qh, k, v, q_pos, k_pos, q_seg, k_seg, *,
                 causal: bool = True, window: int = 0, backend=None):
    """Adapter for models/attention.py decode: qh (B,L,KV,G,D) lanes against
    a paged (B,C,KV,D) cache -> (B,L,KV,G,D).  Forward-only (no VJP); all
    four position/segment operands are required — see kernels/flash_decode.py.
    """
    from repro.kernels import flash_decode as fd

    b, l, kvh, g, d = qh.shape
    q = qh.reshape(b, l, kvh * g, d)
    out = fd.flash_decode(
        q, k, v, q_pos, k_pos, q_seg, k_seg,
        causal=causal, window=window, interpret=_interp(backend),
    )
    return out.reshape(b, l, kvh, g, d)
