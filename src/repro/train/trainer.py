"""Training loop: builds the jitted train_step wiring VRGD stats into the
optimizer, with optional mesh sharding (pjit) and the two GSNR sources.

The train step is the paper's Algorithm 1/3/5 end to end:

  1. gradient moments over k groups   (microbatch scan | data-axis shard_map)
  2. GSNR -> normalize -> clip        (inside the VR optimizer transform)
  3. element-wise scaled update

Baseline optimizers take the plain gradient path (single backward, no Σg²),
so VR-vs-base step-time overhead is measurable (benchmarks/bench_overhead.py).
"""
from __future__ import annotations

import time
from typing import Any, Callable, Dict, Iterable, Optional, Tuple

import jax
import jax.numpy as jnp

from repro import obs
from repro.backend import resolve_backend
from repro.configs.base import Config
from repro.core import grad_only, grad_stats, make_optimizer, stats_summary
from repro.core.distributed import device_grad_stats_fn
from repro.models import init_params
from repro.models.common import global_norm
from repro.models.moe import MOE_ROWS
from repro.train.loss import make_loss_fn
from repro.train.train_state import TrainState

_tm = jax.tree_util.tree_map


def _shard_plan(backend, mesh):
    """Backend.shard over the active rules (or fresh defaults for the mesh):
    the flat-buffer optimizer/stats pallas_calls then run per-shard on the
    FSDP-sharded buffer rows instead of gathering (supports() falls back
    gracefully when the buffer doesn't shard or divide)."""
    if mesh is None:
        return None
    from repro.sharding.rules import rules_for

    return backend.shard(mesh, rules_for(mesh))


def make_train_step(
    cfg: Config,
    loss_fn: Optional[Callable] = None,
    mesh=None,
    log_gsnr: bool = False,
    noise_scale: bool = False,
) -> Tuple[Callable, Any]:
    """Returns (train_step(state, batch) -> (state, metrics), optimizer).

    noise_scale=True adds the gradient-noise-scale readings (noise/g2_small,
    noise/g2_big, noise/tr_sigma, noise/g2, noise/b_simple — plus the live
    lr) to the metrics of every fresh-stats step.  They are jnp reductions
    over the already-materialized moment carry (core/noise_scale.py), so the
    step's pallas_call count is unchanged; on the data_axis source the two
    norm readings are taken inside shard_map from the reduced moments (on
    row shards, one psum of the two scalars; core/distributed.py).
    """
    opt_cfg = cfg.optimizer
    bk = resolve_backend(cfg.parallel, where="make_train_step")
    spmd = _shard_plan(bk, mesh)
    # thread the LIVE effective batch: with cfg.optimizer.base_batch set the
    # schedule peak rescales through the sqrt/linear rule instead of going
    # stale on whatever batch the config was first written with
    opt = make_optimizer(opt_cfg, backend=bk, spmd=spmd, effective_batch=cfg.global_batch)
    loss_fn = loss_fn or make_loss_fn(cfg)
    is_vr = opt_cfg.is_vr
    use_device_stats = is_vr and opt_cfg.gsnr_source == "data_axis" and mesh is not None
    if use_device_stats:
        stats_fn = device_grad_stats_fn(
            lambda p, b: loss_fn(p, b), mesh, has_aux=True, backend=bk,
            with_noise_terms=noise_scale, rules=spmd.rules,
        )
    if noise_scale:
        from repro.core import noise_scale as ns
        from repro.core.schedule import make_schedule

        lr_dbg = make_schedule(opt_cfg, effective_batch=cfg.global_batch)

    def train_step(state: TrainState, batch, fresh_stats: bool = True) -> Tuple[TrainState, Dict]:
        # every step takes fresh statistics; benchmarks/chip/program.py still
        # passes True as a third argument, the only value accepted
        if not fresh_stats:
            raise ValueError("train_step computes fresh GSNR statistics on every step")
        noise_est = None
        if is_vr:
            if use_device_stats and noise_scale:
                loss, aux, stats, nterms = stats_fn(state.params, batch)
                noise_est = ns.estimate_from_terms(
                    g2_small=nterms[1], g2_big=nterms[0],
                    b_small=cfg.global_batch / stats.k, b_big=cfg.global_batch,
                )
            elif use_device_stats:
                loss, aux, stats = stats_fn(state.params, batch)
            else:
                loss, aux, stats = grad_stats(
                    loss_fn, state.params, batch, opt_cfg.k, has_aux=True,
                    backend=bk, spmd=spmd,
                )
            if noise_scale and noise_est is None:
                noise_est = ns.estimate(
                    stats, b_small=cfg.global_batch / stats.k, b_big=cfg.global_batch
                )
            grads = stats.mean
        else:
            loss, aux, grads = grad_only(loss_fn, state.params, batch, has_aux=True)
            stats = None
        if aux and MOE_ROWS in aux:
            # aux is the microbatches' mean: the step's rows are k times it
            obs.count(obs.MOE_ROWS, aux[MOE_ROWS] * (opt_cfg.k if is_vr else 1))
        with obs.scope(obs.OPTIMIZER):
            gnorm = global_norm(grads)
            if opt_cfg.grad_clip > 0:
                scale = jnp.minimum(1.0, opt_cfg.grad_clip / (gnorm + 1e-9))
                grads = _tm(lambda g: g * scale, grads)
            upd, opt_state = opt.update(grads, state.opt_state, state.params, stats=stats)
            params = _tm(lambda p, u: (p + u).astype(p.dtype), state.params, upd)
            unorm = global_norm(upd)
        metrics = {
            "loss": loss,
            "grad_norm": gnorm,
            "update_norm": unorm,
            **(aux or {}),
        }
        if log_gsnr and stats is not None:
            metrics.update(stats_summary(stats, opt_cfg.gamma))
        if noise_scale:
            metrics["lr"] = lr_dbg(state.step)
            if noise_est is not None:
                metrics.update(
                    {
                        "noise/g2_small": noise_est.g2_small,
                        "noise/g2_big": noise_est.g2_big,
                        "noise/tr_sigma": noise_est.tr_sigma,
                        "noise/g2": noise_est.g2,
                        "noise/b_simple": noise_est.b_simple,
                    }
                )
        # _replace keeps dynamic fields (autoscale's k) flowing through
        return state._replace(params=params, opt_state=opt_state, step=opt_state["step"]), metrics

    return train_step, opt


def init_state(cfg: Config, key=None, params=None) -> TrainState:
    key = key if key is not None else jax.random.PRNGKey(cfg.seed)
    if params is None:
        params = init_params(cfg.model, key, scan_layers=cfg.parallel.scan_layers)
    # the Backend plan must thread through here too: a fused-optimizer plan's
    # init produces FlatBuffer moments, and the state structure has to match
    # the transform make_train_step builds (a pytree-state checkpoint still
    # restores into either — see train/checkpoint.py).
    opt = make_optimizer(
        cfg.optimizer,
        backend=resolve_backend(cfg.parallel, where="init_state"),
        effective_batch=cfg.global_batch,
    )
    opt_state = opt.init(params)
    return TrainState(params, opt_state, jnp.zeros((), jnp.int32))


def _live_tokens(batch) -> float:
    """Real (non-pad) token count of a batch: explicit mask > packed
    positions (pad rows carry position -1, train/loss.py) > every element of
    the targets/tokens leaf > leading dim for non-token batches."""
    if isinstance(batch, dict):
        if "mask" in batch:
            return float(jnp.sum(batch["mask"] > 0))
        if "positions" in batch:
            return float(jnp.sum(batch["positions"] >= 0))
        for key in ("targets", "tokens"):
            if key in batch:
                import numpy as _np

                return float(_np.asarray(batch[key]).size)
    leaves = jax.tree_util.tree_leaves(batch)
    return float(leaves[0].shape[0]) if leaves else 1.0


def eval_loss(cfg: Config, loss_fn, params, batches: Iterable) -> float:
    """Mean loss over an eval stream (generalization-gap measurements).

    Each batch's token-mean loss is weighted by its REAL (non-pad) token
    count, so a ragged/padded final batch counts in proportion to the tokens
    it actually holds instead of skewing the average with a full batch's
    weight.

    ``batches`` may also be an IndexedPackedDataset (repro.data.memmap): one
    finite epoch pass is evaluated (epoch_batches), whose padded final batch
    weighs exactly its live tokens — multi-run A/Bs can then share one
    on-disk cache instead of re-synthesizing eval docs per run."""
    if hasattr(batches, "epoch_batches"):
        batches = batches.epoch_batches()
    f = jax.jit(lambda p, b: loss_fn(p, b)[0])
    total = weight = 0.0
    for b in batches:
        w = _live_tokens(b)
        total += float(f(params, b)) * w
        weight += w
    return total / max(weight, 1.0)


def train_loop(
    cfg: Config,
    batches: Iterable,
    steps: int,
    state: Optional[TrainState] = None,
    loss_fn: Optional[Callable] = None,
    log_every: int = 0,
    log_gsnr: bool = False,
):
    """Simple driver used by examples/benchmarks. Returns (state, history)."""
    loss_fn = loss_fn or make_loss_fn(cfg)
    step_fn, _ = make_train_step(cfg, loss_fn, log_gsnr=log_gsnr)
    step = jax.jit(step_fn, donate_argnums=0)
    state = state or init_state(cfg)
    history = []
    it = iter(batches)
    t0 = time.time()
    for i in range(steps):
        state, metrics = step(state, next(it))
        if log_every and (i % log_every == 0 or i == steps - 1):
            m = {k_: float(v) for k_, v in metrics.items()}
            m["step"], m["wall"] = i, time.time() - t0
            history.append(m)
            print(
                f"  step {i:5d} loss {m['loss']:.4f} |g| {m['grad_norm']:.3f}"
                + (f" gsnr {m.get('gsnr/mean', 0):.3f}" if "gsnr/mean" in m else "")
                + (f" pack {m['pack_efficiency']:.2f}" if "pack_efficiency" in m else "")
            )
    return state, history
