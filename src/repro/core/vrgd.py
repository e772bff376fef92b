"""VRGD optimizers (the paper's contribution, §4 + Appendix D).

Each VR optimizer consumes ``GradStats`` (the k-group gradient moments) and
element-wise rescales the gradient by the normalized+clipped GSNR
``r ∈ [gamma, 1]`` before (or, for VR-SGD, inside) the base update:

  VR-SGD      theta <- theta - lr * r * g                      (Alg. 1)
  VR-Momentum r*g into heavy-ball momentum                     (§4.2)
  VR-Adam     p_t = b3*p + (1-b3)*r ; ghat = p̂_t * g ; Adam(ghat)  (Alg. 3)
  VR-LARS     r*g into LARS                                    (§4.2)
  VR-LAMB     VR-Adam direction + LAMB layer-wise trust ratio  (Alg. 5)

The GSNR momentum ``p_t`` (decay b3=0.9) exists so a noisy per-step GSNR
estimate doesn't whipsaw the effective LR (paper §4.2).  Note the paper
applies r to the *gradient entering the moment estimates*, not to the final
update — otherwise m/v would be biased for the next step (paper's remark in
§4.2); we follow that exactly.

``gamma=1.0`` collapses r to exactly 1 (clip floor == ceiling), so every VR
optimizer reduces to its base optimizer — a property test locks this in.

Dispatch is a :class:`repro.backend.Backend` execution plan (the old
boolean is a one-release deprecation shim mapped in repro.backend).  With a
fused ``optimizer`` subsystem the state (m/v/p) lives as ParamLayout flat
buffers (core/layout.py) and every fresh-stats update is ONE fused
``pallas_call`` over the whole parameter set (kernels/flat_update.py via
kernels/ops.py) — per-leaf mean(r) and trust-ratio reductions run as grid
phases inside the kernel, so there is no jnp prepass and no per-leaf
dispatch loop.  An optional ``spmd`` plan (``Backend.shard(mesh, rules)``)
reroutes those calls through per-shard shard_map pipelines on FSDP-sharded
buffer rows.  The jnp path here is the oracle.
"""
from __future__ import annotations

from typing import Any, Callable, Optional

import jax
import jax.numpy as jnp

from repro.backend import Backend, resolve_backend
from repro.core import baselines as B
from repro.core.gsnr import GradStats, gsnr_scale
from repro.core.layout import FlatBuffer, ParamLayout, is_flat

PyTree = Any
_tm = jax.tree_util.tree_map


def _flat_zeros_fn(params, state_dtype: str = "float32"):
    """() -> FlatBuffer of zeros in the params layout (flat-state init)."""
    layout = ParamLayout.for_tree(params)
    sd = jnp.dtype(state_dtype)
    return lambda: FlatBuffer(layout.zeros(sd), layout)


def _unpacked(x):
    """Normalize a possibly-flat value to a pytree: updates cross back into
    tree land at the transform boundary (the trainer adds them to the
    tree-valued params), and the reference paths accept FlatBuffer grads
    from a fused-stats plan by unpacking them on entry."""
    return x.unpack() if is_flat(x) else x


def _require(stats: Optional[GradStats]) -> GradStats:
    if stats is None:
        raise ValueError("VR optimizers require GradStats (mean + sq_mean); see core/accumulate.py")
    return stats


def _scaled_grads(grads, stats, gamma, eps, fused=False, backend=None, spmd=None):
    stats = _require(stats)
    if fused:
        from repro.kernels import ops as kops

        return kops.vr_scale_tree(stats, grads, gamma, eps, backend=backend, spmd=spmd)
    grads = _unpacked(grads)
    r = gsnr_scale(stats, gamma, eps)
    return _tm(lambda r_, g: r_ * g, r, grads), r


def vr_sgd(lr_fn: Callable, gamma: float = 0.1, eps: float = 1e-12,
           backend: Optional[Backend] = None, *, spmd=None, use_pallas=None) -> B.Transform:
    bk = resolve_backend(backend, use_pallas=use_pallas, where="vrgd.vr_sgd")
    fused = bk.fused("optimizer")

    def init(params):
        return {"step": jnp.zeros((), jnp.int32)}

    def update(grads, state, params=None, stats=None):
        lr = lr_fn(state["step"])
        sg, _r = _scaled_grads(grads, stats, gamma, eps, fused, bk, spmd)
        upd = _tm(lambda g: -lr * g, sg)
        return _unpacked(upd), {"step": state["step"] + 1}

    return B.Transform(init, update)


def vr_momentum(
    lr_fn: Callable, mu: float = 0.9, gamma: float = 0.1, eps: float = 1e-12,
    backend: Optional[Backend] = None, *, spmd=None, use_pallas=None,
) -> B.Transform:
    bk = resolve_backend(backend, use_pallas=use_pallas, where="vrgd.vr_momentum")
    fused = bk.fused("optimizer")

    def init(params):
        z = _flat_zeros_fn(params)() if fused else _tm(jnp.zeros_like, params)
        return {"step": jnp.zeros((), jnp.int32), "m": z}

    def update(grads, state, params=None, stats=None):
        lr = lr_fn(state["step"])
        sg, _r = _scaled_grads(grads, stats, gamma, eps, fused, bk, spmd)
        m = _tm(lambda m_, g: mu * m_ + g, state["m"], sg)
        upd = _tm(lambda m_: -lr * m_, m)
        return _unpacked(upd), {"step": state["step"] + 1, "m": m}

    return B.Transform(init, update)


def _vr_adam_dir(grads, state, stats, b1, b2, b3, eps, gamma, gsnr_eps, state_dtype="float32"):
    """Shared VR-Adam machinery (Alg. 3 lines 8-17). Returns (dir, new_state).

    Moments are *stored* in state_dtype (bf16 halves optimizer HBM) but all
    math runs in f32.
    """
    t = state["step"] + 1
    tf = t.astype(jnp.float32)
    f32 = lambda tree: _tm(lambda x: x.astype(jnp.float32), tree)
    sd = jnp.dtype(state_dtype)
    store = lambda tree: _tm(lambda x: x.astype(sd), tree)
    r = gsnr_scale(_require(stats), gamma, gsnr_eps)
    p = _tm(lambda p_, r_: b3 * p_ + (1 - b3) * r_, f32(state["p"]), r)
    phat = _tm(lambda p_: p_ / (1 - b3**tf), p)
    ghat = _tm(lambda ph, g: ph * g, phat, grads)
    m = _tm(lambda m_, g: b1 * m_ + (1 - b1) * g, f32(state["m"]), ghat)
    v = _tm(lambda v_, g: b2 * v_ + (1 - b2) * jnp.square(g), f32(state["v"]), ghat)
    direction = _tm(
        lambda m_, v_: (m_ / (1 - b1**tf)) / (jnp.sqrt(v_ / (1 - b2**tf)) + eps), m, v
    )
    return direction, {"step": t, "m": store(m), "v": store(v), "p": store(p)}


def vr_adam(
    lr_fn: Callable,
    b1: float = 0.9,
    b2: float = 0.999,
    b3: float = 0.9,
    eps: float = 1e-8,
    wd: float = 0.0,
    gamma: float = 0.1,
    gsnr_eps: float = 1e-12,
    backend: Optional[Backend] = None,
    state_dtype: str = "float32",
    *,
    spmd=None,
    use_pallas=None,
) -> B.Transform:
    bk = resolve_backend(backend, use_pallas=use_pallas, where="vrgd.vr_adam")
    fused = bk.fused("optimizer")

    def init(params):
        sd = jnp.dtype(state_dtype)
        if fused:
            z = _flat_zeros_fn(params, state_dtype)
        else:
            z = lambda: _tm(lambda x: jnp.zeros(x.shape, sd), params)
        return {"step": jnp.zeros((), jnp.int32), "m": z(), "v": z(), "p": z()}

    def update(grads, state, params=None, stats=None):
        lr = lr_fn(state["step"])
        if fused:
            from repro.kernels import ops as kops

            return kops.vr_adam_update(
                grads, state, _require(stats), lr, b1, b2, b3, eps, wd, gamma, gsnr_eps,
                params, state_dtype, backend=bk, spmd=spmd,
            )
        d, new_state = _vr_adam_dir(
            _unpacked(grads), state, stats, b1, b2, b3, eps, gamma, gsnr_eps, state_dtype
        )
        if wd and params is not None:
            d = _tm(lambda d_, p_: d_ + wd * p_, d, params)
        upd = _tm(lambda d_: -lr * d_, d)
        return _unpacked(upd), new_state

    return B.Transform(init, update)


def vr_lars(
    lr_fn: Callable,
    mu: float = 0.9,
    wd: float = 1e-4,
    trust: float = 0.001,
    gamma: float = 0.1,
    eps: float = 1e-12,
    backend: Optional[Backend] = None,
    *,
    spmd=None,
    use_pallas=None,
) -> B.Transform:
    bk = resolve_backend(backend, use_pallas=use_pallas, where="vrgd.vr_lars")
    fused = bk.fused("optimizer")
    base = B.lars(lr_fn, mu=mu, wd=wd, trust=trust)

    def init(params):
        if fused:
            return {"step": jnp.zeros((), jnp.int32), "m": _flat_zeros_fn(params)()}
        return base.init(params)

    def update(grads, state, params, stats=None):
        if fused:
            from repro.kernels import ops as kops

            return kops.vr_lars_update(
                grads, state, _require(stats), lr_fn(state["step"]), mu, wd, trust,
                gamma, eps, params, backend=bk, spmd=spmd,
            )
        sg, _r = _scaled_grads(grads, stats, gamma, eps, False)
        return base.update(sg, state, params)

    return B.Transform(init, update)


def vr_lamb(
    lr_fn: Callable,
    b1: float = 0.9,
    b2: float = 0.999,
    b3: float = 0.9,
    eps: float = 1e-6,
    wd: float = 0.01,
    gamma: float = 0.1,
    gsnr_eps: float = 1e-12,
    backend: Optional[Backend] = None,
    state_dtype: str = "float32",
    *,
    spmd=None,
    use_pallas=None,
) -> B.Transform:
    bk = resolve_backend(backend, use_pallas=use_pallas, where="vrgd.vr_lamb")
    fused = bk.fused("optimizer")

    def init(params):
        sd = jnp.dtype(state_dtype)
        if fused:
            z = _flat_zeros_fn(params, state_dtype)
        else:
            z = lambda: _tm(lambda x: jnp.zeros(x.shape, sd), params)
        return {"step": jnp.zeros((), jnp.int32), "m": z(), "v": z(), "p": z()}

    def update(grads, state, params, stats=None):
        lr = lr_fn(state["step"])
        if fused:
            from repro.kernels import ops as kops

            return kops.vr_lamb_update(
                grads, state, _require(stats), lr, b1, b2, b3, eps, wd, gamma,
                gsnr_eps, params, state_dtype, backend=bk, spmd=spmd,
            )
        d, new_state = _vr_adam_dir(
            _unpacked(grads), state, stats, b1, b2, b3, eps, gamma, gsnr_eps, state_dtype
        )

        def one(d_, p_):
            u = d_ + wd * p_
            pn, un = B._tensor_norm(p_), B._tensor_norm(u)
            ratio = jnp.where((pn > 0) & (un > 0), B._lamb_phi(pn) / (un + 1e-12), 1.0)
            return -lr * ratio * u

        upd = _tm(one, d, params)
        return upd, new_state

    return B.Transform(init, update)


# ---------------------------------------------------------------------------
# factory
# ---------------------------------------------------------------------------


def make_optimizer(cfg, backend: Optional[Backend] = None, *, spmd=None,
                   use_pallas=None, effective_batch: Optional[int] = None) -> B.Transform:
    """OptimizerConfig -> Transform (base or VR per cfg.name).

    backend: the execution plan (repro.backend.Backend; also accepts a
    ParallelismConfig / Config, or a legacy bool — deprecated, warns once).
    spmd: optional Backend.shard(...) plan; the fused flat-buffer calls then
    run per-shard under shard_map on FSDP-sharded buffer rows.
    effective_batch: the LIVE global batch this optimizer will step at; with
    cfg.base_batch set, the schedule peak rescales through cfg.lr_scale_rule
    (train/autoscale.py rebuilds the optimizer when k changes, so the LR
    tracks the batch instead of the config's static value).
    """
    from repro.core.schedule import make_schedule

    bk = resolve_backend(backend, use_pallas=use_pallas, where="make_optimizer")
    lr_fn = make_schedule(cfg, effective_batch=effective_batch)
    g, ge = cfg.gamma, cfg.gsnr_eps
    table = {
        "sgd": lambda: B.sgd(lr_fn),
        "momentum": lambda: B.momentum(lr_fn, cfg.momentum),
        "adam": lambda: B.adam(lr_fn, cfg.b1, cfg.b2, cfg.eps, cfg.weight_decay),
        "lars": lambda: B.lars(lr_fn, cfg.momentum, cfg.weight_decay),
        "lamb": lambda: B.lamb(lr_fn, cfg.b1, cfg.b2, cfg.eps, cfg.weight_decay),
        "vr_sgd": lambda: vr_sgd(lr_fn, g, ge, bk, spmd=spmd),
        "vr_momentum": lambda: vr_momentum(lr_fn, cfg.momentum, g, ge, bk, spmd=spmd),
        "vr_adam": lambda: vr_adam(
            lr_fn, cfg.b1, cfg.b2, cfg.b3, cfg.eps, cfg.weight_decay, g, ge, bk,
            cfg.state_dtype, spmd=spmd,
        ),
        "vr_lars": lambda: vr_lars(
            lr_fn, cfg.momentum, cfg.weight_decay, gamma=g, eps=ge, backend=bk,
            spmd=spmd,
        ),
        "vr_lamb": lambda: vr_lamb(
            lr_fn, cfg.b1, cfg.b2, cfg.b3, cfg.eps, cfg.weight_decay, g, ge, bk,
            cfg.state_dtype, spmd=spmd,
        ),
    }
    if cfg.name not in table:
        raise KeyError(f"unknown optimizer {cfg.name!r}")
    return table[cfg.name]()
