"""Differential oracle harness for the Pallas kernel family.

Every Pallas kernel in src/repro/kernels/ has a pure-jnp reference
(kernels/ref.py per tensor, core/vrgd.py + core/accumulate.py for the full
transforms).  This module is the shared machinery that sweeps
kernel vs. reference over the hostile input grid the kernels must survive:

  * shapes: scalar-ish, non-tile-aligned trailing dims, multi-block leaves,
    and partial edge blocks (rows % FLAT_BLOCK_ROWS != 0 — the case that poisons
    in-kernel reductions if padding is mishandled);
  * dtypes: f32 and bf16 gradients / optimizer state;
  * gamma edge cases: gamma=1.0 must collapse every VR optimizer to its base
    optimizer (clip floor == ceiling), gamma→0 leaves the ratio free;
  * grad-clip divergence: the GSNR ratio derives from raw moments but scales
    the clipped gradient (g_apply != g).

It is dependency-free on purpose: ``property_cases`` is a seeded loop, not a
hypothesis strategy, so the suite collects and runs on a bare interpreter
(hypothesis, if installed, is simply not needed).  All kernels execute in
Pallas interpret mode on CPU — the same kernel bodies Mosaic lowers on TPU.
"""
from __future__ import annotations

from typing import Iterable, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np

# Shapes chosen against the flat layout's (64-row block, LANE=128) tiling:
#   7        sub-lane sliver (single partial row)
#   (33, 5)  2-D leaf, non-tile-aligned trailing dim
#   1000     several rows, ragged tail
#   4096     exactly 32 full rows, half of one block
#   (3,5,7)  3-D leaf, everything ragged
#   40000    313 rows -> 5 blocks, the last partial
#   70000    547 rows -> 9 blocks, the last partial
SHAPES: Tuple[Tuple[int, ...], ...] = (
    (7,), (33, 5), (1000,), (4096,), (3, 5, 7), (40000,), (70000,)
)
GAMMAS: Tuple[float, ...] = (0.1, 0.5, 1.0)
DTYPES = (jnp.float32, jnp.bfloat16)


def tol_for(dtype) -> dict:
    """allclose tolerances: f32 kernels match to rounding; bf16 inputs lose
    ~8 mantissa bits before the f32 math starts."""
    if dtype == jnp.float32:
        return dict(atol=2e-5, rtol=2e-4)
    return dict(atol=3e-2, rtol=3e-2)


def assert_trees_close(got, want, msg: str = "", **tol) -> None:
    """allclose over matching pytrees/tuples, with leaf-indexed error messages."""
    gl = jax.tree_util.tree_leaves(got)
    wl = jax.tree_util.tree_leaves(want)
    assert len(gl) == len(wl), f"{msg}: leaf count {len(gl)} != {len(wl)}"
    for i, (a, b) in enumerate(zip(gl, wl)):
        np.testing.assert_allclose(
            np.asarray(a, np.float32), np.asarray(b, np.float32),
            err_msg=f"{msg} [leaf {i}]", **tol,
        )


def gsnr_inputs(shape: Sequence[int], seed: int, dtype=jnp.float32, clip_scale=None):
    """A coherent (g, g_apply, g2) triple: g2 >= g² so variance is sane.

    clip_scale simulates global grad-clip: g_apply = clip_scale * g (the jnp
    oracle path scales the applied gradient but derives r from raw moments).
    """
    ks = jax.random.split(jax.random.PRNGKey(seed), 3)
    g = (jax.random.normal(ks[0], tuple(shape)) * 0.2).astype(dtype)
    g2 = (
        jnp.square(g.astype(jnp.float32))
        + jax.random.uniform(ks[1], tuple(shape)) * 0.05
    ).astype(dtype)
    ga = g if clip_scale is None else (g.astype(jnp.float32) * clip_scale).astype(dtype)
    return g, ga, g2


def opt_state_inputs(shape: Sequence[int], seed: int, state_dtype=jnp.float32):
    """Random (m, v, p, w) optimizer-state leaves; v, p nonneg like real state."""
    ks = jax.random.split(jax.random.PRNGKey(seed + 1000), 4)
    m = (jax.random.normal(ks[0], tuple(shape)) * 0.05).astype(state_dtype)
    v = (jax.random.uniform(ks[1], tuple(shape)) * 0.01).astype(state_dtype)
    p = jax.random.uniform(ks[2], tuple(shape)).astype(state_dtype)
    w = jax.random.normal(ks[3], tuple(shape))
    return m, v, p, w


def property_cases(n: int, seed: int = 0) -> Iterable[dict]:
    """Dependency-free replacement for a hypothesis strategy: n deterministic
    random cases of (shape, gamma, clip_scale, dtype) drawn from a seeded rng."""
    rng = np.random.RandomState(seed)
    for i in range(n):
        size = int(rng.randint(1, 3000))
        yield {
            "shape": (size,),
            "gamma": float(rng.uniform(0.01, 1.0)),
            "clip_scale": float(rng.uniform(0.2, 1.5)) if rng.rand() < 0.5 else None,
            "dtype": jnp.float32 if rng.rand() < 0.8 else jnp.bfloat16,
            "seed": int(rng.randint(0, 2**31)),
        }


# ---------------------------------------------------------------------------
# Flash-attention differential harness (fwd + custom-VJP grads vs
# ref.attention_ref under jax.grad)
# ---------------------------------------------------------------------------

# (B, S, H, KV, D, causal, window) against (BLOCK_Q=128, BLOCK_K=128) tiling:
#   130     partial edge blocks on both q and kv grids
#   128     seq == block (single full block)
#   1       single one-row partial block (degenerate seq)
#   200/100 window crossing a partial block boundary, non-block-aligned
#   KV=1    MQA (GQA group == H)
ATTN_GRAD_CASES: Tuple[Tuple, ...] = (
    (2, 128, 4, 4, 64, True, 0),     # seq == block, no GQA
    (1, 130, 4, 1, 32, True, 0),     # partial blocks + MQA (group == H)
    (1, 256, 8, 2, 64, True, 64),    # GQA 4:1, block-aligned window
    (1, 200, 6, 3, 32, True, 100),   # ragged seq + non-aligned window
    (1, 1, 2, 1, 16, True, 0),       # seq 1: one partial row
    (1, 64, 4, 4, 128, False, 0),    # bidirectional
)


def attention_inputs(case: Sequence, seed: int = 0, dtype=jnp.float32):
    """(q, k, v, t) for one ATTN_GRAD_CASES entry; t is a fixed f32 cotangent
    projection so scalar losses exercise a dense do."""
    b, s, h, kvh, d = case[:5]
    ks = jax.random.split(jax.random.PRNGKey(seed), 4)
    q = jax.random.normal(ks[0], (b, s, h, d), dtype)
    k = jax.random.normal(ks[1], (b, s, kvh, d), dtype)
    v = jax.random.normal(ks[2], (b, s, kvh, d), dtype)
    t = jax.random.normal(ks[3], (b, s, h, d), jnp.float32)
    return q, k, v, t


def run_attention_grads(case: Sequence, seed: int = 0, dtype=jnp.float32):
    """Forward + (dq, dk, dv) for the Pallas kernel and the jnp oracle.

    Returns ((out_k, out_r), (grads_k, grads_r)); grads come from jax.grad of
    sum(out * t) so the kernel's custom VJP runs its fused backward kernels.
    """
    from repro.kernels import ref
    from repro.kernels.flash_attention import flash_attention

    b, s, h, kvh, d, causal, window = case
    q, k, v, t = attention_inputs(case, seed, dtype)

    def loss(fn):
        return lambda q_, k_, v_: jnp.sum(
            fn(q_, k_, v_, causal=causal, window=window).astype(jnp.float32) * t
        )

    out_k = flash_attention(q, k, v, causal=causal, window=window)
    out_r = ref.attention_ref(q, k, v, causal=causal, window=window)
    grads_k = jax.grad(loss(flash_attention), argnums=(0, 1, 2))(q, k, v)
    grads_r = jax.grad(loss(ref.attention_ref), argnums=(0, 1, 2))(q, k, v)
    return (out_k, out_r), (grads_k, grads_r)


# ---------------------------------------------------------------------------
# Packed-sequence differential harness: explicit position/segment layouts
# (the hostile grid the position-aware kernels are certified against)
# ---------------------------------------------------------------------------

# Each case: (B, S, H, KV, D, window, rows) with rows = per-batch-row tuples
# of (doc_len, position_offset) documents; tokens after the documents are a
# padded tail (position -1).  Layouts chosen against BLOCK=128 tiling:
#   * ragged multi-segment packs (boundaries inside a block),
#   * a segment boundary EXACTLY at the 128 block edge,
#   * single-token segments (degenerate one-row documents),
#   * a fully-padded tail long enough to cover a whole dead tile,
#   * offset (kv-cache continuation) positions,
#   * MQA (KV=1) and GQA over packed rows,
#   * a sliding window crossing packed-document boundaries,
#   * B=2 with a DIFFERENT packing per batch row.
PACKED_ATTN_CASES = {
    "multi_segment": (1, 200, 4, 2, 32, 0, (((70, 0), (55, 0), (40, 0)),)),
    "block_edge": (1, 256, 4, 4, 32, 0, (((128, 0), (128, 0)),)),
    "single_token_segs": (
        1, 130, 4, 2, 32, 0, (((1, 0), (1, 0), (1, 0), (60, 0), (1, 0), (40, 0), (1, 0)),),
    ),
    "padded_tail_mqa": (1, 192, 4, 1, 32, 0, (((100, 0), (28, 0)),)),
    "offset_cached": (1, 130, 4, 2, 32, 0, (((130, 100),),)),
    "window_packed": (1, 200, 6, 3, 32, 37, (((120, 0), (60, 0)),)),
    "two_rows_differ": (2, 160, 4, 2, 32, 0, (((90, 0), (50, 0), (20, 0)), ((160, 0),))),
}
PACKED_SMOKE = ("multi_segment", "block_edge", "padded_tail_mqa")


def packed_positions(seq: int, docs: Sequence[Tuple[int, int]]) -> np.ndarray:
    """1-D int32 positions: concatenated ``offset + arange(len)`` document
    runs, -1 on the padded tail."""
    pos = np.full(seq, -1, np.int32)
    o = 0
    for n, off in docs:
        if o + n > seq:
            raise ValueError(f"docs overflow seq {seq}")
        pos[o : o + n] = off + np.arange(n, dtype=np.int32)
        o += n
    return pos


def packed_case_inputs(case: Sequence, seed: int = 0, dtype=jnp.float32):
    """(q, k, v, pos, t) for one PACKED_ATTN_CASES entry (self-attention:
    k_pos == q_pos == ``pos``)."""
    b, s, h, kvh, d, window, rows = case
    assert len(rows) == b
    pos = jnp.asarray(np.stack([packed_positions(s, r) for r in rows]))
    ks = jax.random.split(jax.random.PRNGKey(seed), 4)
    q = jax.random.normal(ks[0], (b, s, h, d), dtype)
    k = jax.random.normal(ks[1], (b, s, kvh, d), dtype)
    v = jax.random.normal(ks[2], (b, s, kvh, d), dtype)
    t = jax.random.normal(ks[3], (b, s, h, d), jnp.float32)
    return q, k, v, pos, t


def run_packed_attention_grads(case: Sequence, seed: int = 0, dtype=jnp.float32):
    """Forward + (dq, dk, dv), Pallas kernel vs jnp oracle, on one packed
    layout (explicit positions, derived segments, causal)."""
    from repro.kernels import ref
    from repro.kernels.flash_attention import flash_attention

    window = case[5]
    q, k, v, pos, t = packed_case_inputs(case, seed, dtype)

    def kfn(q_, k_, v_):
        return flash_attention(q_, k_, v_, pos, pos, causal=True, window=window)

    def rfn(q_, k_, v_):
        return ref.attention_ref(
            q_, k_, v_, causal=True, window=window, q_pos=pos, k_pos=pos
        )

    def loss(fn):
        return lambda q_, k_, v_: jnp.sum(fn(q_, k_, v_).astype(jnp.float32) * t)

    out_k, out_r = kfn(q, k, v), rfn(q, k, v)
    grads_k = jax.grad(loss(kfn), argnums=(0, 1, 2))(q, k, v)
    grads_r = jax.grad(loss(rfn), argnums=(0, 1, 2))(q, k, v)
    return (out_k, out_r), (grads_k, grads_r)


# ---------------------------------------------------------------------------
# One-leaf flat runs of the single-launch kernels (the per-tensor sweeps run
# the kernels the chip runs, each on a ParamLayout of one leaf)
# ---------------------------------------------------------------------------


def one_leaf(x, dtype=jnp.float32):
    """(layout, packed buffer) for the one-leaf tree ``x``, in ``dtype``."""
    from repro.core.layout import ParamLayout

    layout = ParamLayout.for_tree(x)
    return layout, layout.pack(x, dtype)


def flat_vr_scale(g, g2, gamma, eps, g_apply=None):
    """(scaled_grad, r) for one tensor through flat_update.flat_vr_scale."""
    from repro.kernels import flat_update as fu

    ga = g if g_apply is None else g_apply
    layout, gf = one_leaf(g)
    sg, r = fu.flat_vr_scale(gf, one_leaf(ga)[1], one_leaf(g2)[1], layout,
                             gamma=gamma, eps=eps)
    return layout.unpack(sg), layout.unpack(r)


def lamb_trust(u2, w2):
    """LAMB's phi-trust ratio from the exact norm sums (baselines.lamb)."""
    from repro.core.baselines import _lamb_phi

    pn, un = jnp.sqrt(w2), jnp.sqrt(u2)
    return jnp.where((pn > 0) & (un > 0), _lamb_phi(pn) / (un + 1e-12), 1.0)


def lars_trust(u2, w2, trust):
    """LARS's trust ratio from the exact norm sums (baselines.lars)."""
    pn, gn = jnp.sqrt(w2), jnp.sqrt(u2)
    return jnp.where((pn > 0) & (gn > 0), trust * pn / (gn + 1e-12), 1.0)


# ---------------------------------------------------------------------------
# Per-leaf reference dispatch: the jnp per-tensor references of
# kernels/ref.py looped over the leaves, the oracle the single-launch flat
# path is differentially certified against over multi-leaf trees
# ---------------------------------------------------------------------------


def _map_unzip(fn, ref_tree, *rest_trees):
    leaves, treedef = jax.tree_util.tree_flatten(ref_tree)
    rests = [treedef.flatten_up_to(t) for t in rest_trees]
    outs = [fn(*args) for args in zip(leaves, *rests)]
    return (
        treedef.unflatten([o[0] for o in outs]),
        treedef.unflatten([o[1] for o in outs]),
    )


def per_leaf_vr_scale(stats, grads, gamma, eps):
    """Per-leaf (scaled_grads, r) from ref.vr_scale_ref."""
    from repro.kernels import ref

    return _map_unzip(
        lambda g, g2, ga: ref.vr_scale_ref(g, g2, gamma, eps, g_apply=ga),
        stats.mean, stats.sq_mean, grads,
    )


def per_leaf_vr_adam_update(
    grads, state, stats, lr, b1, b2, b3, eps, wd, gamma, gsnr_eps, params,
    state_dtype="float32",
):
    """Per-leaf VR-Adam step from ref.vr_adam_inner_ref."""
    from repro.kernels import ref
    from repro.kernels.ops import _bias_corrections

    _tm = jax.tree_util.tree_map
    t, bc1, bc2, bc3 = _bias_corrections(state, b1, b2, b3)
    sd = jnp.dtype(state_dtype)
    leaves_g, treedef = jax.tree_util.tree_flatten(stats.mean)
    rest = [treedef.flatten_up_to(t_) for t_ in
            (grads, stats.sq_mean, state["m"], state["v"], state["p"])]
    dirs, ms, vs, ps = [], [], [], []
    for g, ga, g2, m, v, p in zip(leaves_g, *rest):
        d_, m_, v_, p_ = ref.vr_adam_inner_ref(
            g, g2, m, v, p, b1=b1, b2=b2, b3=b3, eps=eps, gamma=gamma,
            gsnr_eps=gsnr_eps, bc1=bc1, bc2=bc2, bc3=bc3, g_apply=ga,
        )
        dirs.append(d_); ms.append(m_.astype(sd)); vs.append(v_.astype(sd)); ps.append(p_.astype(sd))
    unf = treedef.unflatten
    d = unf(dirs)
    if wd and params is not None:
        d = _tm(lambda d_, p_: d_ + wd * p_, d, params)
    upd = _tm(lambda d_: -lr * d_, d)
    return upd, {"step": t, "m": unf(ms), "v": unf(vs), "p": unf(ps)}


def per_leaf_vr_lamb_update(
    grads, state, stats, lr, b1, b2, b3, eps, wd, gamma, gsnr_eps, params,
    state_dtype="float32",
):
    """Per-leaf VR-LAMB step from ref.vr_lamb_inner_ref."""
    from repro.kernels import ref
    from repro.kernels.ops import _bias_corrections

    t, bc1, bc2, bc3 = _bias_corrections(state, b1, b2, b3)
    sd = jnp.dtype(state_dtype)
    leaves_g, treedef = jax.tree_util.tree_flatten(stats.mean)
    rest = [treedef.flatten_up_to(t_) for t_ in
            (grads, stats.sq_mean, state["m"], state["v"], state["p"], params)]
    upds, ms, vs, ps = [], [], [], []
    for g, ga, g2, m, v, p, w in zip(leaves_g, *rest):
        u, m_, v_, p_, u2, w2 = ref.vr_lamb_inner_ref(
            g, ga, g2, m, v, p, w, b1=b1, b2=b2, b3=b3, eps=eps, wd=wd,
            gamma=gamma, gsnr_eps=gsnr_eps, bc1=bc1, bc2=bc2, bc3=bc3,
        )
        upds.append(-lr * lamb_trust(u2, w2) * u)
        ms.append(m_.astype(sd)); vs.append(v_.astype(sd)); ps.append(p_.astype(sd))
    unf = treedef.unflatten
    return unf(upds), {"step": t, "m": unf(ms), "v": unf(vs), "p": unf(ps)}


def per_leaf_vr_lars_update(grads, state, stats, lr, mu, wd, trust, gamma, eps, params):
    """Per-leaf VR-LARS step from ref.vr_lars_inner_ref."""
    from repro.kernels import ref

    leaves_g, treedef = jax.tree_util.tree_flatten(stats.mean)
    rest = [treedef.flatten_up_to(t_) for t_ in (grads, stats.sq_mean, state["m"], params)]
    ms = []
    for g, ga, g2, m, w in zip(leaves_g, *rest):
        u, u2, w2 = ref.vr_lars_inner_ref(g, ga, g2, w, wd=wd, gamma=gamma, eps=eps)
        ms.append(mu * m + lars_trust(u2, w2, trust) * u)
    m_new = treedef.unflatten(ms)
    upd = jax.tree_util.tree_map(lambda m_: -lr * m_, m_new)
    return upd, {"step": state["step"] + 1, "m": m_new}


def unpack_state(state):
    """Optimizer state with any FlatBuffer moments expanded to pytrees."""
    from repro.core.layout import unpack_tree

    return unpack_tree(state)


def hostile_params(seed: int = 0, dtype=jnp.float32):
    """A param tree whose leaves sweep the hostile shape grid (non-aligned,
    multi-block, partial edge blocks) including a tuple-valued node."""
    ks = jax.random.split(jax.random.PRNGKey(seed), len(SHAPES))
    leaves = [
        (jax.random.normal(k_, s) * 0.5).astype(dtype) for k_, s in zip(ks, SHAPES)
    ]
    return {"a": leaves[0], "pair": (leaves[1], leaves[2]), "b": leaves[3],
            "c": {"d": leaves[4], "e": leaves[5]}, "f": leaves[6]}


def run_flat_vs_per_leaf(
    name: str,
    steps: int = 2,
    state_dtype: str = "float32",
    gamma: float = 0.1,
    clip_scale=None,
    lr: float = 0.01,
    wd: float = 0.01,
    seed: int = 0,
):
    """Step the flat single-launch transform against the per-leaf jnp
    references in lockstep over the hostile-shape param tree.

    Returns (upd_per_leaf, upd_flat, state_per_leaf, state_flat_unpacked).
    """
    from repro.configs.base import OptimizerConfig
    from repro.core import GradStats, make_optimizer

    params = hostile_params(seed)
    _tm = jax.tree_util.tree_map
    gmean = _tm(lambda x: x * 0.01, params)
    sq = _tm(lambda x: jnp.square(x) + 1e-3, gmean)
    stats = GradStats(mean=gmean, sq_mean=sq, k=8)
    grads = gmean if clip_scale is None else _tm(lambda x: x * clip_scale, gmean)
    cfg = OptimizerConfig(name=name, lr=lr, schedule="constant", weight_decay=wd,
                          gamma=gamma, state_dtype=state_dtype)
    o_f = make_optimizer(cfg, use_pallas=True)
    s_f = o_f.init(params)
    # per-leaf reference state: plain pytree moments in state_dtype
    sd = jnp.dtype(state_dtype)
    z = lambda: _tm(lambda x: jnp.zeros(x.shape, sd), params)
    zero = jnp.zeros((), jnp.int32)
    if name == "vr_lars":
        s_r = {"step": zero, "m": _tm(lambda x: jnp.zeros(x.shape, jnp.float32), params)}
        ref_update = lambda s: per_leaf_vr_lars_update(
            grads, s, stats, lr, 0.9, wd, 0.001, gamma, 1e-12, params)
    elif name == "vr_adam":
        s_r = {"step": zero, "m": z(), "v": z(), "p": z()}
        ref_update = lambda s: per_leaf_vr_adam_update(
            grads, s, stats, lr, 0.9, 0.999, 0.9, 1e-6, wd, gamma, 1e-12, params, state_dtype)
    else:  # vr_lamb
        s_r = {"step": zero, "m": z(), "v": z(), "p": z()}
        ref_update = lambda s: per_leaf_vr_lamb_update(
            grads, s, stats, lr, 0.9, 0.999, 0.9, 1e-6, wd, gamma, 1e-12, params, state_dtype)
    u_r = u_f = None
    for _ in range(steps):
        u_r, s_r = ref_update(s_r)
        u_f, s_f = o_f.update(grads, s_f, params, stats=stats)
    return u_r, u_f, s_r, unpack_state(s_f)


# ---------------------------------------------------------------------------
# Transform-level differential runner (make_optimizer jnp vs Pallas)
# ---------------------------------------------------------------------------


def run_transform_pair(
    name: str,
    steps: int = 3,
    state_dtype: str = "float32",
    gamma: float = 0.1,
    clip_scale=None,
    lr: float = 0.01,
    wd: float = 0.01,
    seed: int = 0,
):
    """Step the jnp and Pallas variants of one optimizer in lockstep.

    Returns (updates_jnp, updates_pallas, state_jnp, state_pallas) from the
    final step.  clip_scale scales the applied gradient away from stats.mean.
    """
    from repro.configs.base import OptimizerConfig
    from repro.core import GradStats, make_optimizer

    key = jax.random.PRNGKey(seed)
    params = {
        "w": jax.random.normal(key, (33, 7)),
        "b": jax.random.normal(jax.random.fold_in(key, 1), (5,)),
    }
    gmean = jax.tree_util.tree_map(lambda x: x * 0.01, params)
    sq = jax.tree_util.tree_map(lambda x: jnp.square(x) + 1e-3, gmean)
    stats = GradStats(mean=gmean, sq_mean=sq, k=8)
    grads = (
        gmean
        if clip_scale is None
        else jax.tree_util.tree_map(lambda x: x * clip_scale, gmean)
    )
    cfg = OptimizerConfig(
        name=name, lr=lr, schedule="constant", weight_decay=wd,
        gamma=gamma, state_dtype=state_dtype,
    )
    o_j = make_optimizer(cfg, use_pallas=False)
    o_k = make_optimizer(cfg, use_pallas=True)
    s_j, s_k = o_j.init(params), o_k.init(params)
    u_j = u_k = None
    for _ in range(steps):
        u_j, s_j = o_j.update(grads, s_j, params, stats=stats)
        u_k, s_k = o_k.update(grads, s_k, params, stats=stats)
    return u_j, u_k, s_j, s_k


def run_base_collapse(name: str, steps: int = 3, seed: int = 0):
    """gamma=1.0 clips r to exactly 1: the VR optimizer (Pallas path) must
    reproduce its base optimizer step for step count ``steps``.

    Returns (updates_base, updates_vr_pallas)."""
    from repro.configs.base import OptimizerConfig
    from repro.core import GradStats, make_optimizer

    base_name = name.replace("vr_", "")
    key = jax.random.PRNGKey(seed)
    params = {
        "w": jax.random.normal(key, (33, 7)),
        "b": jax.random.normal(jax.random.fold_in(key, 1), (5,)),
    }
    grads = jax.tree_util.tree_map(lambda x: x * 0.01, params)
    sq = jax.tree_util.tree_map(lambda x: jnp.square(x) + 1e-3, grads)
    stats = GradStats(mean=grads, sq_mean=sq, k=8)
    # b3 momentum on a constant r=1 is bias-corrected back to exactly 1, so
    # even VR-Adam/LAMB collapse (p̂ = 1 for every t).
    cfg_v = OptimizerConfig(name=name, lr=0.01, schedule="constant",
                            weight_decay=0.01, gamma=1.0)
    cfg_b = OptimizerConfig(name=base_name, lr=0.01, schedule="constant",
                            weight_decay=0.01)
    o_b = make_optimizer(cfg_b)
    o_v = make_optimizer(cfg_v, use_pallas=True)
    s_b, s_v = o_b.init(params), o_v.init(params)
    u_b = u_v = None
    for _ in range(steps):
        u_b, s_b = o_b.update(grads, s_b, params, stats=stats)
        u_v, s_v = o_v.update(grads, s_v, params, stats=stats)
    return u_b, u_v
