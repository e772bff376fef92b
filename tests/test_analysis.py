"""The kernel contract checker, tier-1.

Two halves:

  * the GREEN pass — ``repro.analysis.check.run_checks()`` over every
    registered kernel at every config, including hostile ones and the
    traced launch manifest, must return zero findings on the committed
    kernels;
  * MUTATION tests — each contract rule must actually fire, by rule ID,
    when fed a geometry violating exactly that invariant (a checker whose
    rules never fire is indistinguishable from one that checks nothing).

Plus differential tests pinning the kernels/ref.py oracles that only the
ORACLE-REF rule ("every fused kernel has a jnp oracle") reads.
"""
import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.experimental import pallas as pl

from repro.analysis import rules
from repro.analysis.check import run_checks
from repro.analysis.registry import (
    FetchMap,
    Geometry,
    KernelSpec,
    Operand,
    all_kernels,
    demo_layout,
)


def _rules_of(findings):
    return {f.rule for f in findings}


# ---------------------------------------------------------------------------
# the green pass
# ---------------------------------------------------------------------------


def test_committed_kernels_pass_the_full_contract_check():
    findings = run_checks(fast=False)
    assert findings == [], "\n".join(str(f) for f in findings)


def test_registry_covers_every_kernel_module():
    kernels = all_kernels()
    assert len(kernels) >= 16
    modules = {k.module for k in kernels.values()}
    for mod in ("flash_attention", "flash_attention_bwd", "flash_decode",
                "flat_update", "flat_stats", "flat_spmd"):
        assert any(m.endswith(mod) for m in modules), f"no kernels from {mod}"


def test_registry_coverage_clean_on_the_real_tree():
    assert rules.check_registry_coverage() == []


def test_every_kernel_declares_a_resolvable_oracle():
    for kspec in all_kernels().values():
        assert rules.check_oracle(kspec) == [], kspec.name


# ---------------------------------------------------------------------------
# mutations: one per rule ID
# ---------------------------------------------------------------------------


def _geom(**kw):
    base = dict(grid=(4,), ins={}, outs={})
    base.update(kw)
    return Geometry(**base)


def test_mutation_rank1_tile_is_caught():
    # a (128,) iota-shaped block: Mosaic tiling needs >= 2 dims
    g = _geom(ins={"x": Operand(pl.BlockSpec((128,), lambda i: (i,)), (512,))})
    assert "LAYOUT-RANK" in _rules_of(rules.check_geometry("mut", "rank1", g))


def test_mutation_half_height_bf16_tile_is_caught():
    # an 8-row tile is a full f32 tile but HALF a bf16 tile — the dtype-
    # derived sublane rule must fire where a hard-coded 8 would pass it
    spec = pl.BlockSpec((8, 128), lambda i: (i, 0))
    ok = _geom(ins={"x": Operand(spec, (64, 128), dtype="float32")})
    bad = _geom(ins={"x": Operand(spec, (64, 128), dtype="bfloat16")})
    assert rules.check_geometry("mut", "f32", ok) == []
    assert "LAYOUT-SUBLANE" in _rules_of(rules.check_geometry("mut", "bf16", bad))
    # a block dim equal to the array's own dim is legal at any size
    whole = _geom(ins={"x": Operand(spec, (8, 128), dtype="bfloat16")})
    assert rules.check_geometry("mut", "whole", whole) == []


# the pre-fix operand specs the TPU compiler refused: each must now fire
PRE_FIX_SPECS = {
    # attention pos/seg row: (1, block) over (B, S) — sublane dim 1 != B
    "row": (pl.BlockSpec((1, 128), lambda i: (i, 0)), (8, 512), "int32",
            "LAYOUT-SUBLANE"),
    # LSE residual: (1, 1, block_q) over (B, H, S) — sublane dim 1 != H
    "lse": (pl.BlockSpec((1, 1, 128), lambda i: (0, i, 0)), (8, 16, 512), "float32",
            "LAYOUT-SUBLANE"),
    # q tile: (1, block_q, 1, D) over (B, S, H, D) — sublane dim 1 != H
    "bshd_tile": (pl.BlockSpec((1, 128, 1, 64), lambda i: (0, 0, i, 0)),
                  (8, 128, 16, 64), "bfloat16", "LAYOUT-SUBLANE"),
    # flat-update leaf id: (1, 1) over (n_blocks + 1, 1) — sublane dim 1
    "leaf_id": (pl.BlockSpec((1, 1), lambda i: (i, 0)), (41, 1), "int32",
                "LAYOUT-SUBLANE"),
    # a head-dim-wide slice of a (S, H*D) array: minor dim 64 != 1024
    "lane": (pl.BlockSpec((128, 64), lambda i: (0, i)), (128, 1024), "float32",
             "LAYOUT-LANE"),
}


@pytest.mark.parametrize("case", list(PRE_FIX_SPECS))
def test_mutation_pre_fix_block_shapes_are_caught(case):
    spec, shape, dtype, rule = PRE_FIX_SPECS[case]
    g = _geom(ins={"x": Operand(spec, shape, dtype=dtype)})
    assert _rules_of(rules.check_geometry("mut", case, g)) == {rule}


def test_mutation_write_to_parked_block_is_caught():
    # output declared live only in phase 1 of a (2, 4) grid, but its index
    # map keeps walking blocks while parked -> phase 0 copies back blocks the
    # kernel never wrote
    layout = demo_layout("aligned")
    live_everywhere = pl.BlockSpec((layout.block_rows, 128), lambda ph, b: (b, 0))
    g = _geom(grid=(2, layout.n_blocks), phase_axis=0,
              outs={"o": Operand(live_everywhere, (layout.n_rows, 128), window=(1, 1))})
    assert "REVISIT-WRITE" in _rules_of(rules.check_geometry("mut", "parked", g))
    # parking on the window's first block is the safe form
    parked = pl.BlockSpec((layout.block_rows, 128), lambda ph, b: (b * ph, 0))
    ok = _geom(grid=(2, layout.n_blocks), phase_axis=0,
               outs={"o": Operand(parked, (layout.n_rows, 128), window=(1, 1))})
    assert rules.check_geometry("mut", "parked_ok", ok) == []


def test_mutation_parked_input_drift_is_caught():
    layout = demo_layout("aligned")
    live_everywhere = pl.BlockSpec((layout.block_rows, 128), lambda ph, b: (b, 0))
    g = _geom(grid=(2, layout.n_blocks), phase_axis=0,
              ins={"x": Operand(live_everywhere, (layout.n_rows, 128), window=(1, 1))})
    assert "REVISIT-PARK" in _rules_of(rules.check_geometry("mut", "drift", g))


def test_mutation_undeclared_output_revisit_is_caught():
    # the REAL fused-backward geometry with dq's parking stripped: written
    # through the plain q-block map on every kv step, its block recurs once
    # per kv block
    ks = all_kernels()["flash_attention_bwd"]
    geom = ks.build(**ks.configs["representative"])
    outs = dict(geom.outs)
    outs["dq"] = dataclasses.replace(outs["dq"], spec=geom.ins["q"].spec, window=None)
    mutated = dataclasses.replace(geom, outs=outs)
    found = rules.check_geometry("flash_attention_bwd", "mut", mutated)
    assert _rules_of(found) == {"REVISIT-RACE"}
    assert any("dq" in f.detail for f in found)


def test_mutation_out_of_bounds_fetch_is_caught():
    fetch = np.array([[0, 1, 3]], np.int32)  # 3 >= n_blocks
    g = _geom(fetch_maps={"kv": FetchMap(fetch, n_blocks=3)})
    assert "FETCH-BOUNDS" in _rules_of(rules.check_geometry("mut", "oob", g))


def test_mutation_backward_fetch_jump_is_caught():
    fetch = np.array([[0, 2, 1]], np.int32)  # non-monotone
    g = _geom(fetch_maps={"kv": FetchMap(fetch, n_blocks=3)})
    assert "FETCH-FILL" in _rules_of(rules.check_geometry("mut", "jump", g))


def test_mutation_self_fetch_liveness_mismatch_is_caught():
    # tile (0,1) claims live but fetches block 0 — the kernel's liveness
    # predicate (fetch[ik] == ik) would skip a live tile
    fetch = np.array([[0, 0, 2]], np.int32)
    live = np.array([[True, True, True]])
    g = _geom(fetch_maps={"kv": FetchMap(fetch, live=live, n_blocks=3)})
    assert "FETCH-FILL" in _rules_of(rules.check_geometry("mut", "lie", g))


def test_mutation_non_identity_dense_fetch_is_caught():
    fetch = np.array([[0, 0, 1]], np.int32)
    g = _geom(fetch_maps={"kv": FetchMap(fetch, n_blocks=3, dense_identity=True)})
    assert "FETCH-IDENTITY" in _rules_of(rules.check_geometry("mut", "dense", g))


def test_mutation_vmem_overflow_is_caught():
    # the real attention geometry against a toy 64 KiB budget
    ks = all_kernels()["flash_attention_fwd"]
    geom = ks.build(**ks.configs["representative"])
    found = rules.check_geometry("flash_attention_fwd", "mut", geom,
                                 budget=64 * 1024)
    assert _rules_of(found) == {"VMEM-BUDGET"}


def test_mutation_one_pass_backward_at_granite_20b_overflows_vmem():
    # forced onto the one-pass path at granite-20b widths, seq 4096, the dq
    # scratch alone is 96 MiB: the path flash_attention_bwd must not take
    ks = all_kernels()["flash_attention_bwd"]
    geom = ks.build(**ks.configs["granite_20b_seq4096"], with_dq=True)
    found = rules.check_geometry("flash_attention_bwd", "mut", geom)
    assert _rules_of(found) == {"VMEM-BUDGET"}


def _rule_count(geom):
    """The VMEM-BUDGET rule's count: double-buffered block windows + scratch."""
    from repro.analysis.layout_contracts import DOUBLE_BUFFER, itemsize
    from repro.analysis.replay import _blk_bytes

    return DOUBLE_BUFFER * sum(_blk_bytes(op.spec, itemsize(op.dtype))
                               for _, op, _ in geom.operands()) + geom.scratch_bytes


def _given_blocks(cfg):
    return {k: cfg.get(k) for k in ("block_h", "block_q", "block_k")}


@pytest.mark.parametrize("config", ["representative", "hostile_gqa_bf16",
                                    "granite_3_2b_seq4096", "granite_20b_seq4096"])
def test_backward_dispatch_follows_the_vmem_rule(config):
    # the dispatcher's working-set estimate bounds the VMEM-BUDGET rule's
    # count of the one-pass geometry (the estimate lane-pads the operand
    # windows the rule counts at their block shapes), and it splits exactly
    # where that estimate passes the budget
    from repro.analysis.layout_contracts import VMEM_BUDGET_BYTES, itemsize
    from repro.kernels import flash_attention_bwd as fab

    cfg = fab.CONFIGS[config]
    geom = all_kernels()["flash_attention_bwd"].build(**cfg, with_dq=True)
    s, d, g, size = cfg["S"], cfg["D"], cfg["H"] // cfg["KV"], itemsize(cfg.get("dtype", "float32"))
    hb, bq, bk = fab.bwd_blocks(s, s, cfg["H"], cfg["KV"], d, size, **_given_blocks(cfg))
    estimate = fab.fused_vmem_bytes(g, -(-s // bq), hb, bq, bk, d, size)
    assert _rule_count(geom) <= estimate
    fused = fab.use_fused_dq(g, -(-s // bq), hb, bq, bk, d, size)
    assert fused == (estimate <= VMEM_BUDGET_BYTES["tpu"])
    assert fused == (config != "granite_20b_seq4096")


@pytest.mark.parametrize("config", ["representative", "hostile_packed_bf16",
                                    "granite_3_2b_seq4096", "bert_large_seq128"])
def test_forward_blocks_fit_the_vmem_rule(config):
    # the forward's chosen blocks fit its working-set estimate, which bounds
    # the VMEM-BUDGET rule's count of the same geometry
    from repro.analysis.layout_contracts import VMEM_BUDGET_BYTES, itemsize
    from repro.kernels import flash_attention as fa

    ks = all_kernels()["flash_attention_fwd"]
    cfg = ks.configs[config]
    s, d, g, size = cfg["S"], cfg["D"], cfg["H"] // cfg["KV"], itemsize(cfg.get("dtype", "float32"))
    hb, bq, bk = fa.fwd_blocks(s, s, cfg["H"], cfg["KV"], d, size, **_given_blocks(cfg))
    estimate = fa.fwd_vmem_bytes(hb, fa.kv_block(hb, g), bq, bk, d, size)
    assert _rule_count(ks.build(**cfg)) <= estimate <= VMEM_BUDGET_BYTES["tpu"]


def test_mutation_missing_oracle_is_caught():
    ghost = KernelSpec(name="ghost", module="tests", oracle="no_such_ref",
                       build=lambda: None, configs={})
    assert _rules_of(rules.check_oracle(ghost)) == {"ORACLE-REF"}
    bare = KernelSpec(name="bare", module="tests", oracle=None,
                      build=lambda: None, configs={})
    assert _rules_of(rules.check_oracle(bare)) == {"ORACLE-REF"}


def test_mutation_unregistered_pallas_module_is_caught(tmp_path):
    """A kernels/ module with a pl.pallas_call site that the registry never
    imports must trip REGISTRY-COVERAGE — and ONLY that rule — while a
    docstring mentioning pallas_call must not."""
    (tmp_path / "rogue.py").write_text(
        '"""Docstring mentioning pallas_call — not a call site."""\n'
        "from jax.experimental import pallas as pl\n\n"
        "def run(x):\n"
        "    return pl.pallas_call(lambda r: None, out_shape=x)(x)\n"
    )
    (tmp_path / "innocent.py").write_text(
        '"""Counts pallas_call equations in a jaxpr (no call site here)."""\n'
        "def count(): return 0\n"
    )
    # not imported at all -> dodges the checker
    found = rules.check_registry_coverage(
        kernel_dir=tmp_path, package="fake.kernels",
        known_modules=(), registered=set())
    assert _rules_of(found) == {"REGISTRY-COVERAGE"}
    assert [f.kernel for f in found] == ["fake.kernels.rogue"]
    assert "not in registry.KERNEL_MODULES" in found[0].detail
    # imported but registers nothing -> still a finding, different detail
    found = rules.check_registry_coverage(
        kernel_dir=tmp_path, package="fake.kernels",
        known_modules=("fake.kernels.rogue",), registered=set())
    assert _rules_of(found) == {"REGISTRY-COVERAGE"}
    assert "registers no kernel" in found[0].detail
    # imported AND registering -> clean
    assert rules.check_registry_coverage(
        kernel_dir=tmp_path, package="fake.kernels",
        known_modules=("fake.kernels.rogue",),
        registered={"fake.kernels.rogue"}) == []


def test_mutation_launch_count_drift_is_caught():
    from repro.analysis import launch_manifest as lm

    got = lm.traced_counts()
    assert set(got) == set(lm.TRACED)
    assert lm.check_launches() == []
    # simulate a fusion regression: the manifest says 1, tracing says 2
    orig = dict(lm.LAUNCHES)
    try:
        lm.LAUNCHES["flat_update"] += 1
        found = lm.check_launches()
        assert _rules_of(found) == {"LAUNCH-COUNT"}
        assert any(f.kernel == "flat_update" for f in found)
    finally:
        lm.LAUNCHES.clear()
        lm.LAUNCHES.update(orig)


# ---------------------------------------------------------------------------
# the oracles this PR added (ORACLE-REF gap): differential vs the kernels
# ---------------------------------------------------------------------------


def test_flat_pack_square_matches_ref():
    from repro.kernels.flat_stats import flat_pack_square
    from repro.kernels.ref import pack_square_ref

    layout = demo_layout("hostile")
    gf = jax.random.normal(jax.random.PRNGKey(0), (layout.n_rows, 128))
    got = jax.jit(lambda x: flat_pack_square(x, layout))(gf)
    want = pack_square_ref(gf)
    assert got.shape == (2, layout.n_rows, 128)
    np.testing.assert_array_equal(np.asarray(got), np.asarray(want))


def test_gsnr_r_raw_ref_is_the_scale_numerator():
    # vr_scale_ref == clip(normalized gsnr_r_raw_ref) * g: the partials
    # oracle and the apply oracle must describe the same quantity
    from repro.kernels.ref import gsnr_r_raw_ref, vr_scale_ref

    key = jax.random.PRNGKey(3)
    g = jax.random.normal(key, (64, 128))
    g2 = jnp.square(g) + jnp.abs(jax.random.normal(jax.random.fold_in(key, 1),
                                                   (64, 128))) * 0.1
    r_raw = gsnr_r_raw_ref(g, g2, 1e-8)
    r = jnp.clip(r_raw / jnp.maximum(jnp.mean(r_raw), 1e-30), 0.1, 1.0)
    sg, r_got = vr_scale_ref(g, g2, gamma=0.1, eps=1e-8)
    np.testing.assert_allclose(np.asarray(r_got), np.asarray(r), rtol=1e-6)
    np.testing.assert_allclose(np.asarray(sg), np.asarray(r * g), rtol=1e-6)
