"""Mixture-of-Experts layers: a top-k router, then one of two dispatches.

**Dropless** (``MoEConfig.capacity_factor`` None, :func:`apply_moe_dropless`):
the layer holds a share of the experts (``n_held`` from ``first_held``;
all by default) and routes over all ``n_experts``.  The (token, choice) rows
whose expert is held are sorted by expert and run through the SwiGLU experts
as grouped matmuls (kernels/grouped_matmul.py), which spend work on those
rows only; each row's output goes back to its token weighted by its
renormalized gate.  Nothing is dropped, and the experts held elsewhere add
nothing here: what a layer returns is its share's part of the output, as one
chip of an expert-parallel layer computes it before the exchange.  The row
buffer has the worst case's N * top_k rows.

**Capacity-bounded** (a ``capacity_factor``, :func:`apply_moe`): dispatch
uses the gather/scatter ("dropping") formulation rather than GShard
one-hot einsums: position-in-expert comes from a cumsum over the routing
one-hot, tokens beyond capacity fall into a sacrificial slot that is sliced
off, and the combine is a weighted gather.  Buffer memory is O(E*C*d) instead
of O(S*E*C).  Under pjit the expert buffers are sharded over the mesh: the
expert dim maps to the "model" axis when divisible (llama4: 128/16=8 experts
per device, dispatch lowers to an all-to-all), otherwise experts stay
replicated and each expert's d_ff is tensor-parallel (mixtral: 8 experts on a
16-way axis).

``apply_moe_dense`` is the oracle used by tests: all experts computed for all
tokens, no capacity drops.
"""
from __future__ import annotations

import functools
import math
from typing import Dict, Tuple

import jax
import jax.numpy as jnp

from repro import obs
from repro.backend import resolve_interpret
from repro.configs.base import MoEConfig
from repro.kernels.grouped_matmul import grouped_matmul
from repro.models.common import normal_init
from repro.models.mlp import apply_mlp, mlp_init
from repro.sharding.rules import constrain, constrain_like_param

# aux key of the dropless layer: the (token, choice) rows routed to held experts
MOE_ROWS = "moe_rows"


def moe_init(key, d_model: int, d_ff: int, act: str, cfg: MoEConfig) -> Dict:
    kr, ki, kg, kd, ks = jax.random.split(key, 5)
    e = cfg.held
    p = {
        "router": normal_init(kr, (d_model, cfg.n_experts)),
        "expert_wi": normal_init(ki, (e, d_model, d_ff), fan_in=d_model),
        "expert_wd": normal_init(kd, (e, d_ff, d_model), fan_in=d_ff),
    }
    if act == "swiglu":
        p["expert_wg"] = normal_init(kg, (e, d_model, d_ff), fan_in=d_model)
    for i in range(cfg.n_shared_experts):
        p[f"shared_{i}"] = mlp_init(jax.random.fold_in(ks, i), d_model, d_ff, act)
    return p


def _route(p: Dict, xf: jnp.ndarray, cfg: MoEConfig):
    """xf: (N, d) -> (weights (N,k), experts (N,k), aux dict).

    The router matmul runs in the compute dtype — upcasting xf to f32 first
    materializes (and, under pjit, ALL-GATHERS) a full-width f32 copy of the
    token buffer (§Perf llama4: ~1 TB/dev/step). Only the (N, E) logits are
    carried in f32 for the softmax/top-k.
    """
    logits = (xf @ p["router"].astype(xf.dtype)).astype(jnp.float32)  # (N, E)
    probs = jax.nn.softmax(logits, axis=-1)
    w, idx = jax.lax.top_k(probs, cfg.top_k)  # (N, k)
    w = w / jnp.maximum(jnp.sum(w, axis=-1, keepdims=True), 1e-9)
    # Switch-style load-balance loss over the router distribution
    sel = jax.nn.one_hot(idx, cfg.n_experts, dtype=jnp.float32).sum(axis=1)  # (N, E)
    frac_routed = sel.mean(axis=0) / cfg.top_k
    mean_prob = probs.mean(axis=0)
    lb = cfg.n_experts * jnp.sum(frac_routed * mean_prob)
    z = jnp.mean(jnp.square(jax.nn.logsumexp(logits, axis=-1)))
    aux = {
        "moe_lb_loss": cfg.router_aux_weight * lb,
        "moe_z_loss": cfg.router_z_weight * z,
    }
    return w, idx, sel, aux


def apply_moe(p: Dict, x: jnp.ndarray, act: str, cfg: MoEConfig,
              interpret=None) -> Tuple[jnp.ndarray, Dict]:
    """The layer ``cfg`` selects; ``interpret`` runs the dropless layer's
    kernel in the Pallas interpreter (None: off the TPU only)."""
    if cfg.dropless:
        return apply_moe_dropless(p, x, act, cfg, interpret)
    b, s, d = x.shape
    n = b * s
    xf = x.reshape(n, d)
    w, idx, sel, aux = _route(p, xf, cfg)
    e, k = cfg.n_experts, cfg.top_k
    cap = int(math.ceil(n * k / e * cfg.capacity_factor))

    # position of each (token, choice) within its expert's buffer
    onehot = jax.nn.one_hot(idx.reshape(-1), e, dtype=jnp.int32)  # (N*k, E)
    pos = (jnp.cumsum(onehot, axis=0) - onehot)  # exclusive prefix count per expert
    pos = jnp.take_along_axis(pos, idx.reshape(-1, 1), axis=1).reshape(n, k)
    kept = pos < cap
    slot = jnp.where(kept, pos, cap)  # dropped -> sacrificial slot `cap`

    # dispatch: (E, cap+1, d)
    buf = jnp.zeros((e, cap + 1, d), x.dtype)
    tok_idx = jnp.broadcast_to(jnp.arange(n)[:, None], (n, k)).reshape(-1)
    buf = buf.at[idx.reshape(-1), slot.reshape(-1)].set(xf[tok_idx])
    buf = buf[:, :cap]
    buf = constrain(buf, ("experts", "expert_cap", None))

    # expert computation (E, cap, d_ff).
    # §Perf note: pinning expert-weight copies (f32 or bf16) to the param
    # sharding via with_sharding_constraint was tried and REFUTED twice —
    # GSPMD canonicalized both to the same HLO and materialized ~40 GiB of
    # extra weight copies with zero collective change (EXPERIMENTS.md §Perf).
    dtype = x.dtype
    h = jnp.einsum("ecd,edf->ecf", buf, p["expert_wi"].astype(dtype))
    if act == "swiglu":
        h = jax.nn.silu(jnp.einsum("ecd,edf->ecf", buf, p["expert_wg"].astype(dtype))) * h
    else:
        h = jax.nn.gelu(h)
    out_buf = jnp.einsum("ecf,efd->ecd", h, p["expert_wd"].astype(dtype))
    out_buf = constrain(out_buf, ("experts", "expert_cap", None))

    # combine: weighted gather; dropped slots read the zero pad row
    out_buf = jnp.concatenate([out_buf, jnp.zeros((e, 1, d), x.dtype)], axis=1)
    gathered = out_buf[idx.reshape(-1), slot.reshape(-1)].reshape(n, k, d)
    out = jnp.sum(gathered * w[..., None].astype(x.dtype), axis=1)

    for key_ in sorted(p):
        if key_.startswith("shared_"):
            out = out + apply_mlp(p[key_], xf, act)
    # expert utilisation metric (fraction of capacity used)
    aux["moe_util"] = jnp.minimum(sel.sum(axis=0), cap).sum() / (e * cap)
    return out.reshape(b, s, d), aux


# The row permutations of the dropless layer, each with a gather for its
# transpose: autodiff would transpose a gather into a scatter-add, which the
# TPU runs far below its bandwidth.


@functools.partial(jax.custom_vjp, nondiff_argnums=(3,))
def _dispatch(xf, order, back, k: int):
    """(N*k, d): sorted row r holds token order[r] // k."""
    return xf[order // k]


def _dispatch_fwd(xf, order, back, k):
    return _dispatch(xf, order, back, k), (order, back)


def _dispatch_bwd(k, res, g):
    order, back = res
    # each token's k rows, found through the inverse permutation, summed
    dx = jnp.sum(g[back].reshape(-1, k, g.shape[-1]), axis=1, dtype=jnp.float32)
    return dx.astype(g.dtype), None, None


_dispatch.defvjp(_dispatch_fwd, _dispatch_bwd)


@jax.custom_vjp
def _unsort(rows, order, back):
    """(N*k, d) in (token, choice) order: the inverse of the sort."""
    return rows[back]


def _unsort_fwd(rows, order, back):
    return _unsort(rows, order, back), (order, back)


def _unsort_bwd(res, g):
    order, back = res
    return g[order], None, None


_unsort.defvjp(_unsort_fwd, _unsort_bwd)


def _experts(p: Dict, rows: jnp.ndarray, sizes: jnp.ndarray, act: str, interpret: bool):
    """The held experts on rows sorted by expert (``sizes`` rows each)."""
    dtype = rows.dtype
    h = grouped_matmul(rows, p["expert_wi"].astype(dtype), sizes, interpret)
    if act == "swiglu":
        h = jax.nn.silu(grouped_matmul(rows, p["expert_wg"].astype(dtype), sizes, interpret)) * h
    else:
        h = jax.nn.gelu(h)
    return grouped_matmul(h, p["expert_wd"].astype(dtype), sizes, interpret)


def apply_moe_dropless(p: Dict, x: jnp.ndarray, act: str, cfg: MoEConfig,
                       interpret=None) -> Tuple[jnp.ndarray, Dict]:
    """The held share's part of the layer's output, every routed row
    computed (module docstring).  ``aux[MOE_ROWS]``: the rows routed to
    held experts."""
    b, s, d = x.shape
    n, k, held = b * s, cfg.top_k, cfg.held
    xf = x.reshape(n, d)
    with obs.scope(obs.MOE):
        with obs.scope(obs.MOE_ROUTE):
            w, idx, _sel, aux = _route(p, xf, cfg)
        with obs.scope(obs.MOE_DISPATCH):
            local = idx.reshape(-1) - cfg.first_held  # (N*k,) held experts: 0..held-1
            key = jnp.where((local >= 0) & (local < held), local, held)  # absent: last
            order = jnp.argsort(key, stable=True)  # sorted row -> (token, choice)
            back = jnp.argsort(order)  # (token, choice) -> sorted row
            sizes = jnp.sum(key[:, None] == jnp.arange(held), axis=0, dtype=jnp.int32)
            routed = jnp.sum(sizes)
            live = (jnp.arange(n * k) < routed)[:, None]  # sorted rows a held expert takes
            rows = jnp.where(live, _dispatch(xf, order, back, k), 0)
        with obs.scope(obs.MOE_EXPERTS):
            out_rows = _experts(p, rows, sizes, act, resolve_interpret(interpret))
        with obs.scope(obs.MOE_COMBINE):
            out_rows = jnp.where(live, out_rows, 0)
            gathered = _unsort(out_rows, order, back).reshape(n, k, d)
            out = jnp.sum(gathered * w[..., None].astype(x.dtype), axis=1)
    for key_ in sorted(p):
        if key_.startswith("shared_"):
            out = out + apply_mlp(p[key_], xf, act)
    aux[MOE_ROWS] = routed.astype(jnp.float32)
    return out.reshape(b, s, d), aux


def apply_moe_dense(p: Dict, x: jnp.ndarray, act: str, cfg: MoEConfig) -> Tuple[jnp.ndarray, Dict]:
    """Oracle: every held expert on every token, exact top-k combine, no
    drops; experts held elsewhere add nothing."""
    b, s, d = x.shape
    xf = x.reshape(-1, d)
    w, idx, _sel, aux = _route(p, xf, cfg)
    dtype = x.dtype
    h = jnp.einsum("nd,edf->enf", xf, p["expert_wi"].astype(dtype))
    if act == "swiglu":
        h = jax.nn.silu(jnp.einsum("nd,edf->enf", xf, p["expert_wg"].astype(dtype))) * h
    else:
        h = jax.nn.gelu(h)
    all_out = jnp.einsum("enf,efd->end", h, p["expert_wd"].astype(dtype))  # (E_held, N, d)
    # (N, E_held): each held expert's gate, 0 where no choice took it
    gates = jnp.sum(w[..., None] * jax.nn.one_hot(idx - cfg.first_held, cfg.held), axis=1)
    out = jnp.einsum("end,ne->nd", all_out, gates.astype(dtype))
    for key_ in sorted(p):
        if key_.startswith("shared_"):
            out = out + apply_mlp(p[key_], xf, act)
    return out.reshape(b, s, d), aux
