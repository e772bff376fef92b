"""Differential oracle sweeps: every Pallas kernel vs its jnp reference.

Uses the tests/oracle.py harness (dependency-free property loops, interpret
mode on CPU).  Covers the acceptance grid: non-tile-aligned shapes, partial
edge blocks, f32/bf16 state, gamma=1.0 base-optimizer collapse and grad-clip
divergence (g_apply != g).  The per-tensor sweeps run the single-launch flat
kernels the chip runs, each on a one-leaf ParamLayout.
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

import oracle
from repro.kernels import flat_spmd as fsp
from repro.kernels import flat_stats as fs
from repro.kernels import flat_update as fu
from repro.kernels import ref

ADAM_KW = dict(b1=0.9, b2=0.999, b3=0.9, eps=1e-8, gamma=0.1, gsnr_eps=1e-12)
LAMB_KW = dict(b1=0.9, b2=0.999, b3=0.9, eps=1e-6, wd=0.01, gamma=0.1, gsnr_eps=1e-12)
LARS_KW = dict(wd=1e-4, gamma=0.1, eps=1e-12)
BC = dict(bc1=0.19, bc2=0.002, bc3=0.19)


def _packed(*xs, dtype=jnp.float32):
    return [oracle.one_leaf(x, dtype)[1] for x in xs]


# ---------------------------------------------------------------------------
# per-tensor sweeps: the flat kernels on one leaf
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("shape", oracle.SHAPES, ids=str)
@pytest.mark.parametrize("dtype", oracle.DTYPES, ids=("f32", "bf16"))
def test_vr_scale_oracle(shape, dtype):
    for gamma in oracle.GAMMAS:
        for clip in (None, 0.37):
            g, ga, g2 = oracle.gsnr_inputs(shape, seed=sum(shape), dtype=dtype,
                                           clip_scale=clip)
            got = oracle.flat_vr_scale(g, g2, gamma, 1e-12, g_apply=ga)
            want = ref.vr_scale_ref(g, g2, gamma, 1e-12, g_apply=ga)
            oracle.assert_trees_close(
                got, want, msg=f"vr_scale {shape} {dtype} gamma={gamma} clip={clip}",
                **oracle.tol_for(dtype),
            )
            if gamma == 1.0:  # clip floor == ceiling: r must be exactly 1
                np.testing.assert_allclose(np.asarray(got[1]), 1.0)


@pytest.mark.parametrize("shape", oracle.SHAPES, ids=str)
@pytest.mark.parametrize("state_dtype", oracle.DTYPES, ids=("f32", "bf16"))
def test_vr_adam_inner_oracle(shape, state_dtype):
    g, ga, g2 = oracle.gsnr_inputs(shape, seed=1, clip_scale=0.9)
    m, v, p, _ = oracle.opt_state_inputs(shape, seed=2, state_dtype=state_dtype)
    layout, gf = oracle.one_leaf(g)
    sd = jnp.dtype(state_dtype)
    # lr = -1 and no weight decay: the kernel's update is the Adam direction
    outs = fu.flat_vr_adam(
        gf, *_packed(ga, g2), *_packed(m, v, p, dtype=sd), jnp.zeros_like(gf),
        fu._scal8(-1.0, BC["bc1"], BC["bc2"], BC["bc3"]), layout,
        wd=0.0, state_dtype=sd.name, **ADAM_KW,
    )
    got = tuple(layout.unpack(x) for x in outs)
    want = ref.vr_adam_inner_ref(g, g2, m, v, p, g_apply=ga, **ADAM_KW, **BC)
    oracle.assert_trees_close(
        got, want, msg=f"vr_adam {shape} {state_dtype}", **oracle.tol_for(state_dtype)
    )


@pytest.mark.parametrize("shape", oracle.SHAPES, ids=str)
def test_vr_lamb_inner_oracle(shape):
    """Includes the partial-edge-block shapes (40000, 70000): the in-kernel
    norm reduction must see exact zeros in the padded tail, not garbage."""
    g, ga, g2 = oracle.gsnr_inputs(shape, seed=3, clip_scale=0.8)
    m, v, p, w = oracle.opt_state_inputs(shape, seed=4)
    u, m_, v_, p_, u2, w2 = ref.vr_lamb_inner_ref(g, ga, g2, m, v, p, w, **LAMB_KW, **BC)
    layout, gf = oracle.one_leaf(g)
    # lr = -1 / (the reference's trust ratio): a kernel whose norms agree
    # returns u itself as the update
    lr = -1.0 / oracle.lamb_trust(u2, w2)
    outs = fu.flat_vr_lamb(gf, *_packed(ga, g2, m, v, p, w),
                           fu._scal8(lr, BC["bc1"], BC["bc2"], BC["bc3"]), layout,
                           **LAMB_KW)
    got = tuple(layout.unpack(x) for x in outs)
    oracle.assert_trees_close(got, (u, m_, v_, p_), msg=f"vr_lamb {shape}",
                              atol=1e-3, rtol=1e-3)


@pytest.mark.parametrize("shape", oracle.SHAPES, ids=str)
def test_vr_lars_inner_oracle(shape):
    g, ga, g2 = oracle.gsnr_inputs(shape, seed=5, clip_scale=0.6)
    _, _, _, w = oracle.opt_state_inputs(shape, seed=6)
    u, u2, w2 = ref.vr_lars_inner_ref(g, ga, g2, w, **LARS_KW)
    layout, gf = oracle.one_leaf(g)
    # zero momentum and lr = -1 / (the reference's trust ratio): the update
    # is u itself where the kernel's norms agree
    lr = -1.0 / oracle.lars_trust(u2, w2, 0.001)
    upd, _ = fu.flat_vr_lars(gf, *_packed(ga, g2), jnp.zeros_like(gf), *_packed(w),
                             fu._scal8(lr, LARS_KW["gamma"]), layout, mu=0.9,
                             wd=LARS_KW["wd"], trust=0.001, eps=LARS_KW["eps"])
    oracle.assert_trees_close(layout.unpack(upd), u, msg=f"vr_lars {shape}",
                              atol=1e-3, rtol=1e-3)


@pytest.mark.parametrize("shape", oracle.SHAPES, ids=str)
@pytest.mark.parametrize("dtype", oracle.DTYPES, ids=("f32", "bf16"))
def test_grad_stats_moments_oracle(shape, dtype):
    """k fused accumulation steps + fused /k finalize == the jnp scan body."""
    k = 4
    layout, gs = oracle.one_leaf(jnp.zeros(shape))
    g2s = jnp.zeros_like(gs)
    gs_ref = jnp.zeros(shape, jnp.float32)
    g2s_ref = jnp.zeros_like(gs_ref)
    for i in range(k):
        g, _, _ = oracle.gsnr_inputs(shape, seed=100 + i, dtype=dtype)
        gs, g2s = fs.flat_moments_accum(gs, g2s, oracle.one_leaf(g, dtype)[1], layout)
        gs_ref, g2s_ref = ref.moments_accum_ref(gs_ref, g2s_ref, g)
    got = tuple(layout.unpack(x) for x in fs.flat_moments_finalize(gs, g2s, k, layout))
    want = ref.moments_finalize_ref(gs_ref, g2s_ref, k)
    oracle.assert_trees_close(
        got, want, msg=f"moments {shape} {dtype}", **oracle.tol_for(dtype)
    )


def test_vr_scale_property_loop():
    """Seeded random grid (the hypothesis-free property sweep): r bounded in
    [gamma, 1] and sg == r * g_apply for arbitrary shapes/gammas/clips."""
    for case in oracle.property_cases(25, seed=7):
        g, ga, g2 = oracle.gsnr_inputs(
            case["shape"], case["seed"], case["dtype"], case["clip_scale"]
        )
        sg, r = oracle.flat_vr_scale(g, g2, case["gamma"], 1e-12, g_apply=ga)
        r_np = np.asarray(r)
        assert np.all(r_np >= case["gamma"] - 1e-5), case
        assert np.all(r_np <= 1 + 1e-5), case
        np.testing.assert_allclose(
            np.asarray(sg, np.float32),
            np.asarray(r * ga.astype(jnp.float32), np.float32),
            atol=3e-2 if case["dtype"] == jnp.bfloat16 else 1e-5,
        )


@pytest.mark.parametrize("shape", oracle.SHAPES, ids=str)
@pytest.mark.parametrize("name", ("vr_lamb", "vr_lars"))
def test_shard_kernels_match_the_per_tensor_refs(name, shape):
    """backend.FlatSpmd's per-shard pipeline — the kernels bert-large.p1-dp4
    runs — on one shard of a one-leaf layout, where its psums are the
    identity: the r partials, then the LAMB / LARS compute and the shared
    epilogue.  The compute's u, state and norm partials match the per-tensor
    reference; the update and state match the gathered single launch."""
    g, ga, g2 = oracle.gsnr_inputs(shape, seed=7, clip_scale=0.8)
    m, v, p, w = oracle.opt_state_inputs(shape, seed=8)
    layout, gf = oracle.one_leaf(g)
    ga_f, g2_f, m_f, v_f, p_f, w_f = _packed(ga, g2, m, v, p, w)
    lids = jnp.asarray(layout.block_leaf_ids())
    invsz = jnp.asarray(layout.leaf_inv_sizes())
    if name == "vr_lamb":
        scal = fu._scal8(0.01, BC["bc1"], BC["bc2"], BC["bc3"])
        racc = fsp.leaf_r_partials(gf, g2_f, lids, layout, gsnr_eps=LAMB_KW["gsnr_eps"])
        computed = fsp.vr_lamb_compute(gf, ga_f, g2_f, m_f, v_f, p_f, w_f, scal, racc,
                                       lids, invsz, layout, **LAMB_KW)
        u, m2, v2, p2, uacc, wacc = computed
        stepped = (fu.lamb_epilogue(u, uacc, wacc, scal[0, 0], lids), m2, v2, p2)
        gathered = fu.flat_vr_lamb(gf, ga_f, g2_f, m_f, v_f, p_f, w_f, scal, layout,
                                   **LAMB_KW)
        want = ref.vr_lamb_inner_ref(g, ga, g2, m, v, p, w, **LAMB_KW, **BC)
    else:
        scal = fu._scal8(0.01, LARS_KW["gamma"])
        racc = fsp.leaf_r_partials(gf, g2_f, lids, layout, gsnr_eps=LARS_KW["eps"])
        computed = fsp.vr_lars_compute(gf, ga_f, g2_f, w_f, scal, racc, lids, invsz,
                                       layout, wd=LARS_KW["wd"], eps=LARS_KW["eps"])
        u, uacc, wacc = computed
        stepped = fu.lars_epilogue(u, m_f, uacc, wacc, scal[0, 0], lids, mu=0.9,
                                   trust=0.001)
        gathered = fu.flat_vr_lars(gf, ga_f, g2_f, m_f, w_f, scal, layout, mu=0.9,
                                   wd=LARS_KW["wd"], trust=0.001, eps=LARS_KW["eps"])
        want = ref.vr_lars_inner_ref(g, ga, g2, w, **LARS_KW)
    # u and the state on the leaf's elements; each norm partial summed
    got = tuple(layout.unpack(x) for x in computed[:-2]) + tuple(
        jnp.sum(acc[0]) for acc in computed[-2:])
    oracle.assert_trees_close(got, want, msg=f"{name} shard vs ref {shape}",
                              atol=1e-3, rtol=1e-3)
    # the sharded-vs-gathered tolerance of tests/test_spmd_flat.py: the
    # epilogue runs outside the gathered launch's jit here, ~1 ulp apart
    oracle.assert_trees_close(stepped, gathered, msg=f"{name} shard vs gathered {shape}",
                              rtol=3e-6, atol=1e-8)


# ---------------------------------------------------------------------------
# flash attention: fused fwd + custom-VJP backward kernels vs ref.attention_ref
# under jax.grad (the training-path certification grid)
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("case", oracle.ATTN_GRAD_CASES, ids=str)
@pytest.mark.parametrize("dtype", oracle.DTYPES, ids=("f32", "bf16"))
def test_flash_attention_grad_oracle(case, dtype):
    """Fwd outputs AND dq/dk/dv of the custom VJP must match autodiff of the
    naive oracle over the hostile grid: partial edge blocks, MQA/GQA ratios,
    non-block-aligned windows, seq 1, seq == block, bf16 inputs."""
    (out_k, out_r), (grads_k, grads_r) = oracle.run_attention_grads(
        case, seed=sum(case[:5]), dtype=dtype
    )
    tol = dict(atol=2e-3, rtol=2e-3) if dtype == jnp.float32 else dict(atol=5e-2, rtol=5e-2)
    oracle.assert_trees_close(out_k, out_r, msg=f"attn fwd {case}", **tol)
    for name, a, b in zip(("dq", "dk", "dv"), grads_k, grads_r):
        oracle.assert_trees_close(a, b, msg=f"attn {name} {case}", **tol)


# the split backward (dk/dv kernel + q-outer dq kernel), which
# flash_attention_bwd takes where the one-pass dq scratch would not fit VMEM
SPLIT_BWD_CASES = {
    **{str(c): (c, None) for c in oracle.ATTN_GRAD_CASES},
    **{n: (oracle.PACKED_ATTN_CASES[n], n) for n in oracle.PACKED_SMOKE},
}


@pytest.mark.parametrize("name", list(SPLIT_BWD_CASES))
def test_split_dq_backward_matches_one_pass_and_oracle(name):
    """Forced onto the split path, the backward gives the one-pass kernel's
    dk/dv bit for bit and its dq to float rounding, and both match the jnp
    replica attention_bwd_ref."""
    from repro.kernels import flash_attention_bwd as fab
    from repro.kernels import ref
    from repro.kernels.flash_attention import bhsd, resolve_positions

    case, packed = SPLIT_BWD_CASES[name]
    if packed is None:
        b, s, _, _, _, causal, window = case
        q, k, v, t = oracle.attention_inputs(case, seed=sum(case[:5]))
        pos = None
    else:
        b, s, window, causal = case[0], case[1], case[5], True
        q, k, v, pos, t = oracle.packed_case_inputs(case, seed=sum(case[:5]))
    qp, kp, qs, ks = (jnp.broadcast_to(x, (b, s))
                      for x in resolve_positions(pos, pos, s, s))
    out, lse = ref.attention_fwd_ref(q, k, v, causal=causal, window=window,
                                     q_pos=qp, k_pos=kp, q_seg=qs, k_seg=ks)
    do = bhsd(t)
    delta = jnp.sum(do * bhsd(out), axis=-1, keepdims=True)
    args = (bhsd(q), bhsd(k), bhsd(v), lse[..., None], delta, do)
    kw = dict(causal=causal, window=window, block_q=min(128, s), block_k=min(128, s),
              interpret=True, implicit=pos is None)
    one = fab._one_pass_bwd(*args, qp, kp, qs, ks, **kw)
    two = fab._split_bwd(*args, qp, kp, qs, ks, **kw)
    want = fab.attention_bwd_ref(*args, causal=causal, window=window,
                                 q_pos=qp, k_pos=kp, q_seg=qs, k_seg=ks)
    np.testing.assert_array_equal(np.asarray(two[1]), np.asarray(one[1]))
    np.testing.assert_array_equal(np.asarray(two[2]), np.asarray(one[2]))
    oracle.assert_trees_close(two[0], one[0], msg=f"split dq {name}", atol=1e-6, rtol=1e-6)
    for gname, a, w in zip(("dq", "dk", "dv"), two, want):
        oracle.assert_trees_close(a, w, msg=f"split {gname} {name}", atol=2e-3, rtol=2e-3)


def test_flash_attention_fully_masked_rows_are_zero():
    """A query row with NO valid kv position (here: q past the end of a short
    kv sequence under window=1, hitting the partial first kv block) must give
    exactly 0 forward output and exactly 0, finite gradients — the old
    max(l, 1e-30) clamp silently produced a uniform average over kv.

    Sq != Skv now requires explicit positions (the implicit-arange alignment
    is ambiguous and raises — see test_bwd_rejects_implicit_sq_neq_skv)."""
    from repro.kernels import ref
    from repro.kernels.flash_attention import flash_attention

    ks = jax.random.split(jax.random.PRNGKey(3), 3)
    q = jax.random.normal(ks[0], (1, 8, 2, 16))
    k = jax.random.normal(ks[1], (1, 4, 2, 16))
    v = jax.random.normal(ks[2], (1, 4, 2, 16))
    qp = jnp.arange(8, dtype=jnp.int32)[None]
    kp = jnp.arange(4, dtype=jnp.int32)[None]
    out = flash_attention(q, k, v, qp, kp, causal=True, window=1)
    exp = ref.attention_ref(q, k, v, causal=True, window=1, q_pos=qp, k_pos=kp)
    # rows 4.. have no kv with kpos == qpos: exactly zero, kernel and oracle
    np.testing.assert_array_equal(np.asarray(out)[:, 4:], 0.0)
    np.testing.assert_array_equal(np.asarray(exp)[:, 4:], 0.0)
    oracle.assert_trees_close(out, exp, msg="fully-masked fwd", atol=2e-3, rtol=2e-3)
    dq = jax.grad(
        lambda q_: jnp.sum(flash_attention(q_, k, v, qp, kp, causal=True, window=1))
    )(q)
    assert bool(jnp.all(jnp.isfinite(dq)))
    np.testing.assert_array_equal(np.asarray(dq)[:, 4:], 0.0)


# ---------------------------------------------------------------------------
# packed-sequence certification grid: explicit positions + derived segments,
# kernel vs ref.attention_fwd_ref under jax.grad (tests/oracle.py harness).
# The smoke subset runs in tier-1; the exhaustive grid (every hostile layout
# x dtype) is `slow`.
# ---------------------------------------------------------------------------

PACKED_TOL = dict(atol=2e-3, rtol=2e-3)


def _assert_packed_case(name, dtype):
    case = oracle.PACKED_ATTN_CASES[name]
    (out_k, out_r), (grads_k, grads_r) = oracle.run_packed_attention_grads(
        case, seed=sum(case[:5]), dtype=dtype
    )
    tol = PACKED_TOL if dtype == jnp.float32 else dict(atol=5e-2, rtol=5e-2)
    oracle.assert_trees_close(out_k, out_r, msg=f"packed fwd {name}", **tol)
    for gname, a, b in zip(("dq", "dk", "dv"), grads_k, grads_r):
        oracle.assert_trees_close(a, b, msg=f"packed {gname} {name}", **tol)


@pytest.mark.parametrize("name", oracle.PACKED_SMOKE)
def test_packed_attention_grad_oracle_smoke(name):
    """Tier-1 subset of the packed grid: multi-segment ragged pack, segment
    boundary exactly at the 128 block edge, fully-padded tail + MQA."""
    _assert_packed_case(name, jnp.float32)


@pytest.mark.slow
@pytest.mark.parametrize("name", sorted(oracle.PACKED_ATTN_CASES))
@pytest.mark.parametrize("dtype", oracle.DTYPES, ids=("f32", "bf16"))
def test_packed_attention_grad_oracle_full(name, dtype):
    """The exhaustive hostile grid: every packed layout (single-token
    segments, offset/cached positions, windows crossing document boundaries,
    per-row differing packings) x f32/bf16, fwd AND dq/dk/dv."""
    _assert_packed_case(name, dtype)


def test_packed_cross_segment_attention_is_zero():
    """Cross-document attention in a packed row is PROVABLY zero: perturbing
    document 2's k/v leaves document 1's outputs bitwise unchanged (masked
    scores are the constant NEG_INF either way, so even the accumulation
    order is identical), and the dk/dv of a loss that reads only document 1
    vanish identically on document 2's rows."""
    from repro.kernels.flash_attention import flash_attention

    case = oracle.PACKED_ATTN_CASES["multi_segment"]
    n0 = case[6][0][0][0]  # first document length
    q, k, v, pos, _ = oracle.packed_case_inputs(case, seed=11)
    out = flash_attention(q, k, v, pos, pos, causal=True)
    k2 = k.at[:, n0:].multiply(-3.0)
    v2 = v.at[:, n0:].add(7.0)
    out2 = flash_attention(q, k2, v2, pos, pos, causal=True)
    np.testing.assert_array_equal(np.asarray(out[:, :n0]), np.asarray(out2[:, :n0]))

    def doc1_loss(k_, v_):
        return jnp.sum(flash_attention(q, k_, v_, pos, pos, causal=True)[:, :n0])

    dk, dv = jax.grad(doc1_loss, argnums=(0, 1))(k, v)
    np.testing.assert_array_equal(np.asarray(dk)[:, n0:], 0.0)
    np.testing.assert_array_equal(np.asarray(dv)[:, n0:], 0.0)
    assert float(jnp.max(jnp.abs(dk))) > 0  # doc-1 rows do carry gradient


def test_packed_padded_tail_rows_are_zero():
    """Pad rows (position -1) emit exactly 0 forward output and exactly 0,
    finite gradients on the fused path — including the fully dead tile the
    padded_tail_mqa layout parks beyond the 128 block edge."""
    from repro.kernels.flash_attention import flash_attention

    case = oracle.PACKED_ATTN_CASES["padded_tail_mqa"]
    used = sum(n for n, _ in case[6][0])
    q, k, v, pos, _ = oracle.packed_case_inputs(case, seed=4)
    out = flash_attention(q, k, v, pos, pos, causal=True)
    np.testing.assert_array_equal(np.asarray(out)[:, used:], 0.0)
    dq = jax.grad(lambda q_: jnp.sum(flash_attention(q_, k, v, pos, pos, causal=True)))(q)
    assert bool(jnp.all(jnp.isfinite(dq)))
    np.testing.assert_array_equal(np.asarray(dq)[:, used:], 0.0)


def test_packed_grad_of_grad_composes():
    """Second-order autodiff through the packed fused path falls back to the
    jnp replicas WITH the packed positions — segments must gate the 2nd-order
    math too, not just the first-order kernels."""
    from repro.kernels import ref
    from repro.kernels.flash_attention import flash_attention

    ks = jax.random.split(jax.random.PRNGKey(5), 3)
    q = jax.random.normal(ks[0], (1, 48, 4, 16))
    k = jax.random.normal(ks[1], (1, 48, 2, 16))
    v = jax.random.normal(ks[2], (1, 48, 2, 16))
    pos = jnp.asarray(oracle.packed_positions(48, ((30, 0), (18, 0))))[None]

    def gradnorm(fn):
        f = lambda q_: jnp.sum(jnp.tanh(fn(q_)))
        return lambda q_: jnp.sum(jax.grad(f)(q_) ** 2)

    gg_k = jax.grad(gradnorm(lambda q_: flash_attention(q_, k, v, pos, pos, causal=True)))(q)
    gg_r = jax.grad(
        gradnorm(lambda q_: ref.attention_ref(q_, k, v, causal=True, q_pos=pos, k_pos=pos))
    )(q)
    oracle.assert_trees_close(gg_k, gg_r, msg="packed grad-of-grad", atol=2e-3, rtol=2e-3)


def test_tile_reachable_never_kills_live_tiles():
    """Seeded fuzz pinning the dead-tile predicates to the mask: whenever
    tile_reachable(...) is False, tile_mask(...) must be all-False for the
    same sanitized pos/seg vectors (a false kill silently zeroes real
    attention), and for implicit arange layouts the dynamic predicate may
    never be stricter than the static grid-index one."""
    from repro.kernels.flash_attention import (
        tile_mask,
        tile_reachable,
        tile_reachable_static,
    )

    rng = np.random.RandomState(0)
    bq = bk = 8
    for trial in range(200):
        causal = bool(rng.rand() < 0.7)
        window = int(rng.choice((0, 1, 3, 11)))
        mode = rng.rand()
        if mode < 0.5:  # random packed-ish: arange runs + pads
            def mk(n):
                pos = np.full(n, -1, np.int64)
                o = 0
                while o < n and rng.rand() < 0.9:
                    ln = int(rng.randint(1, n - o + 1))
                    pos[o : o + ln] = rng.randint(0, 4) + np.arange(ln)
                    o += ln
                seg = np.cumsum(np.concatenate([[1], pos[1:] != pos[:-1] + 1])) - 1
                seg = np.where(pos < 0, -1, seg)
                return jnp.asarray(pos), jnp.asarray(seg)

            qp, qs = mk(bq)
            kp, ks = mk(bk)
        else:  # fully random sanitized vectors (hostile, non-monotonic)
            qp = jnp.asarray(rng.randint(-1, 12, bq))
            kp = jnp.asarray(rng.randint(-1, 12, bk))
            qs = jnp.asarray(np.where(np.asarray(qp) < 0, -1, rng.randint(0, 3, bq)))
            ks = jnp.asarray(np.where(np.asarray(kp) < 0, -2, rng.randint(0, 3, bk)))
        live = bool(tile_reachable(qp, kp, qs, ks, causal, window))
        mask_any = bool(jnp.any(tile_mask(qp, kp, qs, ks, causal, window)))
        assert live or not mask_any, (trial, causal, window, qp, kp, qs, ks)
    # implicit arange over a 2x2 tile grid: dynamic predicate == static
    for causal in (False, True):
        for window in (0, 3):
            for iq in range(2):
                for ik in range(2):
                    qp = jnp.arange(iq * bq, (iq + 1) * bq)
                    kp = jnp.arange(ik * bk, (ik + 1) * bk)
                    zs = jnp.zeros(bq, jnp.int32)
                    dyn = bool(tile_reachable(qp, kp, zs, zs, causal, window))
                    st = tile_reachable_static(iq, ik, bq, bk, causal, window)
                    st = True if st is None else bool(st)
                    assert dyn == st, (causal, window, iq, ik)


def test_cross_stream_segments_need_explicit_ids():
    """Derived segment ids are per-stream ordinals, so a query block
    continuing document 2 of a multi-document kv cache MUST pass explicit
    q_seg/k_seg (the derived q_seg=0 would match the cache's document 0).
    The explicit path is certified against the oracle; the derived path is
    shown to differ — the documented reason the contract exists."""
    from repro.kernels import ref
    from repro.kernels.flash_attention import flash_attention

    ks = jax.random.split(jax.random.PRNGKey(13), 3)
    q = jax.random.normal(ks[0], (1, 3, 2, 16))
    k = jax.random.normal(ks[1], (1, 16, 2, 16))
    v = jax.random.normal(ks[2], (1, 16, 2, 16))
    # cache: doc0 = positions 0..9, doc1 = positions 0..5; q continues doc1
    k_pos = jnp.asarray(np.concatenate([np.arange(10), np.arange(6)]))[None]
    k_seg = jnp.asarray([[0] * 10 + [1] * 6])
    q_pos = jnp.asarray([[6, 7, 8]])
    q_seg = jnp.asarray([[1, 1, 1]])
    out = flash_attention(q, k, v, q_pos, k_pos, q_seg, k_seg, causal=True)
    exp = ref.attention_ref(
        q, k, v, causal=True, q_pos=q_pos, k_pos=k_pos, q_seg=q_seg, k_seg=k_seg
    )
    oracle.assert_trees_close(out, exp, msg="cross-stream explicit segs", atol=2e-3, rtol=2e-3)
    # doc0's keys at positions 6..8 exist, so attending the WRONG document
    # would produce a different (nonzero-masked) result: the derived-ordinal
    # call must differ, which is exactly why explicit ids are required here
    derived = flash_attention(q, k, v, q_pos, k_pos, causal=True)
    assert float(jnp.max(jnp.abs(out - derived))) > 1e-3


def test_bwd_rejects_implicit_sq_neq_skv():
    """Sq != Skv with implicit positions is a loud ValueError (the old kernel
    silently start-aligned the two aranges — 'wrong-shape' semantics under
    the end-aligned cache convention); explicit positions make the same
    shapes first-class and must match the oracle."""
    from repro.kernels import flash_attention_bwd as fab
    from repro.kernels import ref
    from repro.kernels.flash_attention import bhsd, flash_attention

    ks = jax.random.split(jax.random.PRNGKey(7), 3)
    q = jax.random.normal(ks[0], (1, 130, 4, 16))
    k = jax.random.normal(ks[1], (1, 64, 2, 16))
    v = jax.random.normal(ks[2], (1, 64, 2, 16))
    with pytest.raises(ValueError, match="Sq == Skv"):
        flash_attention(q, k, v, causal=True)
    with pytest.raises(ValueError, match="Sq == Skv"):
        jax.grad(lambda q_: jnp.sum(flash_attention(q_, k, v, causal=True)))(q)
    # the residual contract is validated too: a mis-shaped lse fails loudly
    # instead of reducing garbage into dk/dv (head-major kernel layout)
    qh, kh, vh = (bhsd(x) for x in (q, k, v))
    with pytest.raises(ValueError, match="lse"):
        fab.check_bwd_shapes(
            qh, kh, vh, jnp.zeros((1, 4, 64, 1)), jnp.zeros((1, 4, 130, 1)), qh
        )
    # explicit positions: the same shapes are well-defined and certified
    qp = jnp.arange(130, dtype=jnp.int32)[None]
    kp = jnp.arange(64, dtype=jnp.int32)[None]
    out = flash_attention(q, k, v, qp, kp, causal=True)
    exp = ref.attention_ref(q, k, v, causal=True, q_pos=qp, k_pos=kp)
    oracle.assert_trees_close(out, exp, msg="explicit sq!=skv fwd", atol=2e-3, rtol=2e-3)
    gk = jax.grad(lambda a: jnp.sum(flash_attention(a, k, v, qp, kp, causal=True)))(q)
    gr = jax.grad(lambda a: jnp.sum(ref.attention_ref(a, k, v, causal=True, q_pos=qp, k_pos=kp)))(q)
    oracle.assert_trees_close(gk, gr, msg="explicit sq!=skv dq", atol=2e-3, rtol=2e-3)


def test_flash_attention_grad_of_grad_composes():
    """The custom VJP must compose under jax.grad twice: second-order autodiff
    falls back to the differentiable jnp replicas instead of erroring on a
    non-differentiable pallas_call."""
    from repro.kernels import ref
    from repro.kernels.flash_attention import flash_attention

    ks = jax.random.split(jax.random.PRNGKey(5), 3)
    q = jax.random.normal(ks[0], (1, 48, 4, 16))
    k = jax.random.normal(ks[1], (1, 48, 2, 16))
    v = jax.random.normal(ks[2], (1, 48, 2, 16))

    def gradnorm(fn):
        f = lambda q_: jnp.sum(jnp.tanh(fn(q_, k, v, causal=True)))
        return lambda q_: jnp.sum(jax.grad(f)(q_) ** 2)

    gg_k = jax.grad(gradnorm(flash_attention))(q)
    gg_r = jax.grad(gradnorm(ref.attention_ref))(q)
    oracle.assert_trees_close(gg_k, gg_r, msg="grad-of-grad", atol=2e-3, rtol=2e-3)


# ---------------------------------------------------------------------------
# transform level: make_optimizer(use_pallas=True) vs the jnp oracle path
# ---------------------------------------------------------------------------

VR_NAMES = ("vr_sgd", "vr_momentum", "vr_adam", "vr_lars", "vr_lamb")


@pytest.mark.parametrize("name", VR_NAMES)
def test_transform_pallas_matches_jnp(name):
    u_j, u_k, s_j, s_k = oracle.run_transform_pair(name, steps=3, clip_scale=0.37)
    oracle.assert_trees_close(u_k, u_j, msg=name, atol=1e-5, rtol=1e-3)
    # the flat path stores moments as FlatBuffers; unpacked leaves must come
    # back in the same dtype the jnp state carries
    s_k = oracle.unpack_state(s_k)
    for a, b in zip(jax.tree_util.tree_leaves(s_j), jax.tree_util.tree_leaves(s_k)):
        assert a.dtype == b.dtype, (name, a.dtype, b.dtype)


@pytest.mark.parametrize("name", ("vr_adam", "vr_lamb"))
def test_transform_bf16_state_dtype(name):
    """bf16 moment storage: Pallas path must cast m/v/p back to state_dtype
    (the seed bug left them f32, silently doubling optimizer HBM)."""
    u_j, u_k, s_j, s_k = oracle.run_transform_pair(name, steps=3, state_dtype="bfloat16")
    oracle.assert_trees_close(u_k, u_j, msg=name, atol=2e-2, rtol=2e-2)
    for part in ("m", "v", "p"):
        assert s_k[part].dtype == jnp.bfloat16, (name, part, s_k[part].dtype)
        for leaf in jax.tree_util.tree_leaves(s_k[part].unpack()):
            assert leaf.dtype == jnp.bfloat16, (name, part, leaf.dtype)


@pytest.mark.parametrize("name", VR_NAMES)
def test_gamma_one_collapses_to_base(name):
    u_b, u_v = oracle.run_base_collapse(name, steps=3)
    oracle.assert_trees_close(u_v, u_b, msg=f"{name} gamma=1", atol=1e-5, rtol=1e-4)


# ---------------------------------------------------------------------------
# flat single-launch path vs per-leaf loops over the jnp per-tensor
# references (tests/oracle.py)
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("name", ("vr_adam", "vr_lamb", "vr_lars"))
@pytest.mark.parametrize("clip", (None, 0.37), ids=("noclip", "clip"))
def test_flat_matches_per_leaf_kernels(name, clip):
    """The one-pallas_call flat update must agree with the per-leaf jnp
    references leaf for leaf over the hostile shape grid (non-tile-aligned
    leaves, partial edge blocks, tuple-valued pytree nodes)."""
    u_r, u_f, s_r, s_f = oracle.run_flat_vs_per_leaf(name, steps=2, clip_scale=clip)
    oracle.assert_trees_close(u_f, u_r, msg=f"{name} upd", atol=1e-5, rtol=1e-3)
    for part in ("m", "v", "p") if name != "vr_lars" else ("m",):
        oracle.assert_trees_close(
            s_f[part], s_r[part], msg=f"{name} {part}", atol=1e-5, rtol=1e-3
        )


@pytest.mark.parametrize("name", ("vr_adam", "vr_lamb"))
def test_flat_matches_per_leaf_bf16_state(name):
    u_r, u_f, s_r, s_f = oracle.run_flat_vs_per_leaf(name, steps=2, state_dtype="bfloat16")
    oracle.assert_trees_close(u_f, u_r, msg=f"{name} bf16 upd", atol=2e-2, rtol=2e-2)
    for leaf in jax.tree_util.tree_leaves(s_f["m"]):
        assert leaf.dtype == jnp.bfloat16


def test_flat_scale_matches_per_leaf_kernels():
    """flat_vr_scale vs per-leaf ref.vr_scale_ref on the hostile param tree."""
    from repro.core import GradStats
    from repro.kernels import ops as kops

    params = oracle.hostile_params(seed=3)
    g = jax.tree_util.tree_map(lambda x: x * 0.02, params)
    sq = jax.tree_util.tree_map(lambda x: jnp.square(x) + 1e-3, g)
    stats = GradStats(mean=g, sq_mean=sq, k=8)
    ga = jax.tree_util.tree_map(lambda x: x * 0.7, g)
    sg_f, r_f = kops.vr_scale_tree(stats, ga, 0.1, 1e-12)
    sg_r, r_r = oracle.per_leaf_vr_scale(stats, ga, 0.1, 1e-12)
    oracle.assert_trees_close(sg_f.unpack(), sg_r, msg="sg", atol=1e-6, rtol=1e-4)
    oracle.assert_trees_close(r_f.unpack(), r_r, msg="r", atol=1e-6, rtol=1e-4)


# ---------------------------------------------------------------------------
# accumulation level: fused scan body == jnp scan body
# ---------------------------------------------------------------------------


def _quad_loss(p, b):
    x, y = b
    pred = x @ p["w"] + p["b"]
    return jnp.mean((pred - y) ** 2), {"mae": jnp.mean(jnp.abs(pred - y))}


def test_fused_grad_stats_matches_jnp_scan():
    from repro.core.accumulate import grad_stats

    key = jax.random.PRNGKey(0)
    X = jax.random.normal(key, (64, 10))
    Y = X @ jnp.arange(1.0, 11.0)
    params = {"w": jnp.ones(10) * 0.3, "b": jnp.zeros(())}
    l1, a1, s1 = grad_stats(_quad_loss, params, (X, Y), 8, has_aux=True)
    l2, a2, s2 = grad_stats(_quad_loss, params, (X, Y), 8, has_aux=True, use_pallas=True)
    np.testing.assert_allclose(float(l1), float(l2), rtol=1e-6)
    np.testing.assert_allclose(np.asarray(a1["mae"]), np.asarray(a2["mae"]), rtol=1e-6)
    s2t = s2.as_tree()  # flat path carries FlatBuffer stats
    oracle.assert_trees_close(s2t.mean, s1.mean, msg="mean", atol=1e-7, rtol=1e-5)
    oracle.assert_trees_close(s2t.sq_mean, s1.sq_mean, msg="sq_mean", atol=1e-7, rtol=1e-5)
    assert s2.k == s1.k == 8


def test_fused_paths_with_tuple_pytree():
    """Param pytrees containing tuple nodes must not confuse the flat packing
    (a 2-tuple param tree once scrambled Σg and Σg² across leaves in the old
    per-leaf dispatch — the ParamLayout is anchored to the tree structure)."""
    from repro.core import GradStats, ParamLayout
    from repro.kernels import ops as kops

    g = (jnp.full((4,), 2.0), jnp.full((3, 3), 3.0))  # params tree IS a 2-tuple
    layout = ParamLayout.for_tree(g)
    g_sum, g2_sum = kops.moments_init_flat(layout)
    g_sum, g2_sum = kops.moments_accum_flat(g_sum, g2_sum, g, layout)
    stats1 = kops.moments_finalize_flat(g_sum, g2_sum, 1, layout)
    mean, sq = stats1.mean.unpack(), stats1.sq_mean.unpack()
    np.testing.assert_allclose(np.asarray(mean[0]), 2.0)
    np.testing.assert_allclose(np.asarray(mean[1]), 3.0)
    np.testing.assert_allclose(np.asarray(sq[0]), 4.0)
    np.testing.assert_allclose(np.asarray(sq[1]), 9.0)

    stats = GradStats(
        mean=g, sq_mean=jax.tree_util.tree_map(lambda x: jnp.square(x) + 0.1, g), k=4
    )
    sg_fb, r_fb = kops.vr_scale_tree(stats, g, 0.1, 1e-12)
    sg = sg_fb.unpack()
    want0, _ = ref.vr_scale_ref(g[0], stats.sq_mean[0], 0.1, 1e-12)
    want1, _ = ref.vr_scale_ref(g[1], stats.sq_mean[1], 0.1, 1e-12)
    np.testing.assert_allclose(np.asarray(sg[0]), np.asarray(want0), rtol=1e-5)
    np.testing.assert_allclose(np.asarray(sg[1]), np.asarray(want1), rtol=1e-5)


def test_fused_train_step_end_to_end():
    """cfg.parallel.use_pallas threads through trainer -> accumulate ->
    optimizer -> ATTENTION (fwd + custom-VJP bwd kernels): one full VR-LAMB
    train step matches the jnp pipeline.

    compute_dtype is pinned to f32 so the comparison stays at rounding
    tolerance: the flash kernel does all internal math in f32 while the jnp
    attention path rounds through bf16 einsums, a legitimate (and separately
    oracle-bounded) divergence under the bf16 default."""
    import dataclasses

    from repro.configs import get_smoke
    from repro.data import lm_batches
    from repro.train import init_state, make_loss_fn, make_train_step

    cfg0 = get_smoke("granite-3-2b").replace(global_batch=8, seq_len=16)
    cfg0 = cfg0.replace(optimizer=dataclasses.replace(cfg0.optimizer, name="vr_lamb", k=4))
    batch = next(iter(lm_batches(cfg0.model.vocab_size, 8, 16, seed=0)))
    outs = {}
    for pallas in (False, True):
        cfg = cfg0.replace(parallel=dataclasses.replace(
            cfg0.parallel, use_pallas=pallas, compute_dtype="float32"))
        state = init_state(cfg)
        step_fn, _ = make_train_step(cfg, make_loss_fn(cfg))
        new_state, metrics = jax.jit(step_fn)(state, batch)
        outs[pallas] = (new_state.params, metrics)
    oracle.assert_trees_close(outs[True][0], outs[False][0], msg="params", atol=1e-5, rtol=1e-4)
    np.testing.assert_allclose(
        float(outs[True][1]["loss"]), float(outs[False][1]["loss"]), rtol=1e-5
    )
