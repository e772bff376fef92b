"""Pallas kernel sweeps: shapes x dtypes against the pure-jnp oracles.

Property sweeps are dependency-free seeded loops — hypothesis is NOT
required.  The exhaustive differential grid lives in tests/test_oracle.py;
the optimizer sweeps here run the flat kernels on one-leaf layouts too.
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

import oracle
from repro.kernels import flat_update as fu
from repro.kernels import ref
from repro.kernels.flash_attention import flash_attention

SIZES = [7, 128, 1000, 4096, 12345]
DTYPES = [jnp.float32, jnp.bfloat16]


@pytest.mark.parametrize("n", SIZES)
@pytest.mark.parametrize("dtype", DTYPES)
def test_vr_scale_sweep(n, dtype):
    key = jax.random.PRNGKey(n)
    g = (jax.random.normal(key, (n,)) * 0.2).astype(dtype)
    g2 = (jnp.square(g.astype(jnp.float32)) + jax.random.uniform(jax.random.fold_in(key, 1), (n,)) * 0.05).astype(dtype)
    sg, r = oracle.flat_vr_scale(g, g2, 0.1, 1e-12)
    sg_r, r_r = ref.vr_scale_ref(g, g2, 0.1, 1e-12)
    tol = 1e-5 if dtype == jnp.float32 else 2e-2
    np.testing.assert_allclose(np.asarray(sg, np.float32), np.asarray(sg_r, np.float32), atol=tol, rtol=tol)
    np.testing.assert_allclose(np.asarray(r), np.asarray(r_r), atol=tol, rtol=tol)


def test_vr_scale_property():
    """Seeded property loop: r bounded in [gamma, 1] and sg == r * g."""
    rng = np.random.RandomState(0)
    for _ in range(20):
        n = rng.randint(4, 301)
        gamma = float(rng.uniform(0.01, 0.99))
        g = jnp.asarray(rng.uniform(-2, 2, n).astype(np.float32))
        g2 = jnp.square(g) + 0.01
        sg, r = oracle.flat_vr_scale(g, g2, gamma, 1e-12)
        assert np.all(np.asarray(r) >= gamma - 1e-5)
        assert np.all(np.asarray(r) <= 1 + 1e-5)
        np.testing.assert_allclose(np.asarray(sg), np.asarray(r * g), atol=1e-5)


@pytest.mark.parametrize("n", [64, 2048, 9999])
def test_vr_adam_sweep(n):
    key = jax.random.PRNGKey(n)
    ks = jax.random.split(key, 5)
    g = jax.random.normal(ks[0], (n,)) * 0.1
    g2 = jnp.square(g) + jax.random.uniform(ks[1], (n,)) * 0.01
    m = jax.random.normal(ks[2], (n,)) * 0.05
    v = jax.random.uniform(ks[3], (n,)) * 0.01
    p = jax.random.uniform(ks[4], (n,))
    kw = dict(b1=0.9, b2=0.999, b3=0.9, eps=1e-8, gamma=0.1, gsnr_eps=1e-12)
    layout, gf = oracle.one_leaf(g)
    # lr = -1 and no weight decay: the kernel's update is the Adam direction
    outs = fu.flat_vr_adam(
        gf, gf, *(oracle.one_leaf(x)[1] for x in (g2, m, v, p)), jnp.zeros_like(gf),
        fu._scal8(-1.0, 0.19, 0.002, 0.19), layout, wd=0.0, **kw,
    )
    outs = [layout.unpack(x) for x in outs]
    refs = ref.vr_adam_inner_ref(g, g2, m, v, p, bc1=0.19, bc2=0.002, bc3=0.19, **kw)
    for name, a, b in zip("direction/m/v/p".split("/"), outs, refs):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), atol=1e-4, rtol=1e-4, err_msg=name)


def test_vr_adam_kernel_equals_jnp_optimizer_path():
    """The use_pallas VR-Adam transform == the jnp VR-Adam transform."""
    from repro.configs.base import OptimizerConfig
    from repro.core import GradStats, make_optimizer

    key = jax.random.PRNGKey(0)
    params = {"a": jax.random.normal(key, (33, 7)), "b": jax.random.normal(key, (5,))}
    g = jax.tree_util.tree_map(lambda x: x * 0.01, params)
    sq = jax.tree_util.tree_map(lambda x: jnp.square(x) + 0.001, g)
    stats = GradStats(mean=g, sq_mean=sq, k=8)
    cfg = OptimizerConfig(name="vr_adam", lr=0.01, schedule="constant", weight_decay=0.01)
    o_j = make_optimizer(cfg, use_pallas=False)
    o_k = make_optimizer(cfg, use_pallas=True)
    s_j, s_k = o_j.init(params), o_k.init(params)
    for _ in range(3):
        u_j, s_j = o_j.update(g, s_j, params, stats=stats)
        u_k, s_k = o_k.update(g, s_k, params, stats=stats)
    for a, b in zip(jax.tree_util.tree_leaves(u_j), jax.tree_util.tree_leaves(u_k)):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), atol=1e-5, rtol=1e-4)


ATTN_CASES = [
    # (B, Sq, Skv, H, KV, D, causal, window)
    (2, 128, 128, 4, 4, 64, True, 0),
    (1, 256, 256, 8, 2, 64, True, 64),
    (2, 130, 130, 4, 1, 32, True, 0),       # partial blocks + MQA
    (1, 64, 64, 4, 4, 128, False, 0),        # bidirectional
    (1, 384, 384, 6, 3, 32, True, 100),      # window not block-aligned
]


@pytest.mark.parametrize("case", ATTN_CASES)
@pytest.mark.parametrize("dtype", DTYPES)
def test_flash_attention_sweep(case, dtype):
    b, sq, skv, h, kvh, d, causal, window = case
    key = jax.random.PRNGKey(hash(case) % 2**31)
    ks = jax.random.split(key, 3)
    q = jax.random.normal(ks[0], (b, sq, h, d), dtype)
    k = jax.random.normal(ks[1], (b, skv, kvh, d), dtype)
    v = jax.random.normal(ks[2], (b, skv, kvh, d), dtype)
    out = flash_attention(q, k, v, causal=causal, window=window)
    exp = ref.attention_ref(q, k, v, causal=causal, window=window)
    tol = 2e-3 if dtype == jnp.float32 else 2e-2
    np.testing.assert_allclose(
        np.asarray(out, np.float32), np.asarray(exp, np.float32), atol=tol, rtol=tol
    )


def _docs_positions(b, s, docs):
    """(B, S) int32 packed positions: per-document aranges, -1 padding."""
    row = np.full(s, -1, np.int32)
    i = 0
    for n in docs:
        row[i:i + n] = np.arange(n)
        i += n
    return jnp.asarray(np.tile(row, (b, 1)))


ONE_HEAD = dict(block_h=1, block_q=32, block_k=32)

# name: ((B, Sq, Skv, H, KV, D), causal, positions, geometry a, geometry b).
# positions: None (implicit arange), a tuple of document lengths (packed
# self-attention), or "cross" (Sq != Skv, explicit aranges, zero segments).
# A geometry of {} is the one the kernels choose from the shapes.
GEOMETRY_CASES = {
    "gqa_s200_tiles": ((1, 200, 200, 4, 2, 32), True, None,
                       dict(block_q=64, block_k=64), dict(block_q=128, block_k=32)),
    "mha_heads_per_step": ((2, 128, 128, 8, 8, 32), False, (70, 58), {}, ONE_HEAD),
    "gqa_docs_cross_tiles": ((1, 1024, 1024, 8, 2, 32), True, (300, 400, 250), {},
                             dict(block_h=1, block_q=128, block_k=128)),
    "gqa_part_of_group": ((1, 256, 256, 8, 2, 32), True, (100, 90, 50),
                          dict(block_h=2, block_q=64, block_k=128), ONE_HEAD),
    "partial_edges_s130": ((1, 130, 130, 4, 2, 32), True, None,
                           dict(block_q=128, block_k=128), dict(ONE_HEAD, block_q=64)),
    "partial_edges_s200": ((1, 200, 200, 4, 2, 32), True, (120, 80), {},
                           dict(block_h=1, block_q=64, block_k=48)),
    "mqa_split_backward": ((1, 136, 136, 6, 1, 16), True, (60, 76), {}, ONE_HEAD),
    "cross_attention": ((2, 96, 160, 4, 2, 32), False, "cross", {},
                        dict(block_h=1, block_q=32, block_k=64)),
}


@pytest.mark.parametrize("name", list(GEOMETRY_CASES))
def test_flash_attention_block_size_invariance(name, monkeypatch):
    """Forward output and (dq, dk, dv) do not depend on the grid geometry:
    a block of heads and shape-sized tiles against one head per step and
    small tiles, to float32 rounding."""
    from repro.analysis.launch_manifest import _count
    from repro.kernels import flash_attention_bwd as fab

    (b, sq, skv, h, kvh, d), causal, layout, geo_a, geo_b = GEOMETRY_CASES[name]
    ks = jax.random.split(jax.random.PRNGKey(sum(map(ord, name))), 4)
    q = jax.random.normal(ks[0], (b, sq, h, d))
    k = jax.random.normal(ks[1], (b, skv, kvh, d))
    v = jax.random.normal(ks[2], (b, skv, kvh, d))
    t = jax.random.normal(ks[3], (b, sq, h, d))
    if layout is None:
        pos = {}
    elif layout == "cross":
        pos = dict(q_pos=jnp.broadcast_to(jnp.arange(sq, dtype=jnp.int32), (b, sq)),
                   k_pos=jnp.broadcast_to(jnp.arange(skv, dtype=jnp.int32), (b, skv)),
                   q_seg=jnp.zeros((b, sq), jnp.int32), k_seg=jnp.zeros((b, skv), jnp.int32))
    else:
        p = _docs_positions(b, sq, layout)
        pos = dict(q_pos=p, k_pos=p)
    if name == "mqa_split_backward":  # as where the group's dq does not fit VMEM
        monkeypatch.setattr(fab, "use_fused_dq", lambda *a: False)

    def run(geo):
        attn = lambda q_, k_, v_: flash_attention(q_, k_, v_, **pos, causal=causal, **geo)
        grads = jax.grad(lambda *a: jnp.sum(attn(*a) * t), argnums=(0, 1, 2))
        return attn(q, k, v), grads(q, k, v), _count(grads, q, k, v)

    out_a, grads_a, launches = run(geo_a)
    out_b, grads_b, _ = run(geo_b)
    assert launches == (3 if name == "mqa_split_backward" else 2)
    np.testing.assert_allclose(np.asarray(out_a), np.asarray(out_b), atol=1e-5, rtol=1e-5)
    for a, r in zip(grads_a, grads_b):
        np.testing.assert_allclose(np.asarray(a), np.asarray(r), atol=1e-5, rtol=1e-5)


# (B, S, H, KV, D) of the benchmark cells' attention calls
GRANITE_3_2B = (1, 4096, 32, 8, 64)
BERT_LARGE = (8, 128, 16, 16, 64)
GRANITE_20B = (1, 4096, 48, 1, 128)


def _chosen(shape, itemsize=2):
    """(forward blocks, grid steps), (backward blocks, grid steps, one pass)
    at the blocks the kernels choose for ``shape``."""
    from math import prod

    from repro.kernels import flash_attention_bwd as fab
    from repro.kernels.flash_attention import fwd_blocks, fwd_geometry

    b, s, h, kvh, d = shape
    fb = fwd_blocks(s, s, h, kvh, d, itemsize)
    fwd = fwd_geometry(b, s, h, d, s, kvh, block_q=fb[1], block_k=fb[2], with_lse=True,
                       block_h=fb[0])[0]
    hb, bq, bk = bb = fab.bwd_blocks(s, s, h, kvh, d, itemsize)
    one_pass = fab.use_fused_dq(h // kvh, -(-s // bq), hb, bq, bk, d, itemsize)
    bwd = fab.bwd_geometry(b, s, h, d, s, kvh, block_q=bq, block_k=bk, block_h=hb,
                           with_dq=one_pass)[0]
    return (fb, prod(fwd)), (bb, prod(bwd), one_pass)


def test_shape_chosen_geometry_at_the_cells_shapes():
    """The cells' calls take few, large grid steps: granite-3-2b's forward
    and backward at most 1,024 steps a call (32,768 at one head x 128 x
    128), each a whole kv group of 4 heads at least; bert-large's forward
    at most 16; granite-3-2b's backward keeps the one-pass kernel and
    granite-20b's (MQA 48:1) splits."""
    (fb, fwd), (bb, bwd, one_pass) = _chosen(GRANITE_3_2B)
    assert fwd <= 1024 and bwd <= 1024 and one_pass
    assert fb[0] % 4 == 0 and bb[0] % 4 == 0
    assert _chosen(BERT_LARGE)[0][1] <= 16
    assert not _chosen(GRANITE_20B)[1][2]


def _cell_layout(config: str):
    """The flat ParamLayout of a chip benchmark configuration, from shapes
    alone: its family's ``ModelConfig`` through the program's
    ``init_params`` under ``jax.eval_shape``."""
    import json
    import pathlib
    import sys

    from repro.configs import ParallelismConfig
    from repro.core.layout import ParamLayout
    from repro.models import init_params

    root = pathlib.Path(__file__).resolve().parents[1]
    if str(root) not in sys.path:
        sys.path.insert(0, str(root))
    from benchmarks.chip.spec import family

    conf = json.loads((root / "benchmarks" / "chip" / "configs" / f"{config}.json").read_text())
    model = family(conf).model_config(conf)
    scan = ParallelismConfig().scan_layers
    return ParamLayout.for_tree(jax.eval_shape(
        lambda: init_params(model, jax.random.PRNGKey(0), scan_layers=scan)))


@pytest.mark.parametrize("config", ["bert-large", "granite-3-2b", "mellum2-12b-a2.5b"])
def test_statistics_sweeps_take_few_grid_steps_at_the_cells_shapes(config):
    """At the cells' flat layouts (~2.6-2.7 M rows, ~41,000 64-row layout
    blocks) every statistics sweep takes at most 2,700 grid steps, whose
    double-buffered windows fit the VMEM budget with room for the body."""
    from repro.analysis.layout_contracts import DOUBLE_BUFFER, VMEM_BUDGET_BYTES
    from repro.kernels import flat_stats as fs

    layout = _cell_layout(config)
    assert layout.n_blocks > 40_000
    for kname, streams in fs.STREAMS.items():
        (steps,), tile = fs._sweep(kname, layout.n_rows)
        rows = tile.block_shape[0]
        assert steps <= 2_700 and steps * rows >= layout.n_rows > (steps - 1) * rows, kname
        assert DOUBLE_BUFFER * streams * rows * 128 * 4 <= fs.SWEEP_VMEM_BYTES, kname
        assert fs.SWEEP_VMEM_BYTES < VMEM_BUDGET_BYTES["tpu"]


@pytest.mark.parametrize("block_h", [3, 8])
def test_a_head_block_must_fit_the_heads(block_h):
    # 4 query heads over 2 kv heads: 3 splits a group, 8 is more than H
    q = jnp.zeros((1, 16, 4, 8))
    k = jnp.zeros((1, 16, 2, 8))
    with pytest.raises(ValueError, match="block_h"):
        flash_attention(q, k, k, block_h=block_h)


def _paged_cache_case(key, b, c, lanes, kvh, d, n_fill):
    """Random paged cache: n_fill arrival-ordered slots holding 2 interleaved
    segments per row, rest empty (kpos/kseg = -1); lanes continue segment 0/1."""
    ks = jax.random.split(key, 5)
    k = jax.random.normal(ks[0], (b, c, kvh, d))
    v = jax.random.normal(ks[1], (b, c, kvh, d))
    k_seg = np.full((b, c), -1, np.int32)
    k_pos = np.full((b, c), -1, np.int32)
    counts = np.zeros((b, 2), np.int32)
    rng = np.random.RandomState(0)
    for bi in range(b):
        for s in range(n_fill):
            seg = int(rng.randint(0, 2))
            k_seg[bi, s] = seg
            k_pos[bi, s] = counts[bi, seg]
            counts[bi, seg] += 1
    h = kvh * 2
    q = jax.random.normal(ks[2], (b, lanes, h, d))
    q_pos = np.stack([counts[:, i % 2] for i in range(lanes)], axis=1).astype(np.int32)
    q_seg = np.broadcast_to(np.arange(lanes, dtype=np.int32) % 2, (b, lanes)).copy()
    return q, k, v, jnp.asarray(q_pos), jnp.asarray(k_pos), jnp.asarray(q_seg), jnp.asarray(k_seg)


@pytest.mark.parametrize("lanes", [1, 3, 8])
@pytest.mark.parametrize("window", [0, 5])
def test_flash_decode_matches_paged_ref(lanes, window):
    """Fused decode over an arrival-ordered multi-segment cache == the jnp
    paged oracle, for lane counts below/at the f32 sublane pad (8)."""
    from repro.kernels.flash_decode import flash_decode

    q, k, v, q_pos, k_pos, q_seg, k_seg = _paged_cache_case(
        jax.random.PRNGKey(3), b=2, c=48, lanes=lanes, kvh=2, d=32, n_fill=30
    )
    out = flash_decode(q, k, v, q_pos, k_pos, q_seg, k_seg, causal=True, window=window)
    exp = ref.decode_attention_ref(
        q, k, v, q_pos, k_pos, q_seg, k_seg, causal=True, window=window
    )
    assert out.shape == q.shape
    np.testing.assert_allclose(np.asarray(out), np.asarray(exp), atol=2e-3, rtol=2e-3)


def test_flash_decode_idle_lanes_and_empty_slots_emit_zero():
    """Idle lanes (q_pos < 0) emit exactly 0; empty cache slots (kpos = -1)
    never contribute (a cache with extra empty slots matches a tight one)."""
    from repro.kernels.flash_decode import flash_decode

    q, k, v, q_pos, k_pos, q_seg, k_seg = _paged_cache_case(
        jax.random.PRNGKey(4), b=1, c=40, lanes=4, kvh=1, d=16, n_fill=24
    )
    q_pos = q_pos.at[0, 2].set(-1)  # idle lane
    q_seg = q_seg.at[0, 2].set(-1)
    out = flash_decode(q, k, v, q_pos, k_pos, q_seg, k_seg)
    assert np.all(np.asarray(out[0, 2]) == 0.0)
    # slots past n_fill are empty: truncating them changes nothing
    out_tight = flash_decode(
        q, k[:, :24], v[:, :24], q_pos, k_pos[:, :24], q_seg, k_seg[:, :24]
    )
    np.testing.assert_allclose(np.asarray(out), np.asarray(out_tight), atol=1e-6)


def test_flash_decode_bf16_pads_to_dtype_sublane():
    """The q-tile sublane multiple is dtype-derived (32 // itemsize: f32 ->
    8, bf16 -> 16), not a hard-coded 8 — a bf16 decode must pad its lane
    axis to 16 and still match the paged oracle.  Regression for the
    half-height bf16 q tile a fixed f32 sublane count would hand Mosaic."""
    from repro.kernels.flash_decode import _sublane, flash_decode

    assert _sublane(jnp.float32) == 8
    assert _sublane(jnp.bfloat16) == 16

    q, k, v, q_pos, k_pos, q_seg, k_seg = _paged_cache_case(
        jax.random.PRNGKey(5), b=2, c=48, lanes=3, kvh=2, d=32, n_fill=30
    )
    qb, kb, vb = (x.astype(jnp.bfloat16) for x in (q, k, v))
    out = flash_decode(qb, kb, vb, q_pos, k_pos, q_seg, k_seg, causal=True)
    exp = ref.decode_attention_ref(qb, kb, vb, q_pos, k_pos, q_seg, k_seg,
                                   causal=True)
    assert out.shape == qb.shape and out.dtype == jnp.bfloat16
    np.testing.assert_allclose(
        np.asarray(out, np.float32), np.asarray(exp, np.float32),
        atol=2e-2, rtol=2e-2,
    )


def test_flash_decode_requires_explicit_operands():
    from repro.kernels.flash_decode import flash_decode

    q = jnp.zeros((1, 1, 2, 16))
    k = v = jnp.zeros((1, 8, 2, 16))
    with pytest.raises(ValueError, match="required"):
        flash_decode(q, k, v, None, jnp.zeros((1, 8), jnp.int32), None, None)
