"""Structural cost model: per-step block visits, HBM bytes, and MXU FLOPs,
counted by REPLAYING the real grid specs and index maps — not estimated.

The Pallas/Mosaic pipelining rule this counts: an operand's copy-in (and an
output's copy-out) is elided whenever its index map returns the SAME block
index as the previous grid step.  So the model walks every grid in row-major
order (last dimension fastest — the Pallas iteration order), calls each
BlockSpec's actual ``index_map`` with concrete python ints (plus the concrete
fetch array for the scalar-prefetch forward maps), and counts a DMA exactly
when the returned index changes.  Geometry comes from the kernels' own
single-source-of-truth builders:

  * kernels/flash_attention.fwd_geometry   (+ kv_fetch_blocks fetch maps)
  * kernels/flash_attention_bwd.bwd_geometry
  * kernels/flat_update.PHASE_WINDOWS / _phased_specs / _specs
  * kernels/flat_stats._blk

so a kernel-side grid or index-map change shows up here without touching the
model.  MXU FLOPs are matmul counts per LIVE tile pair (dead packed tiles
are pl.when-skipped) times 2*block_q*block_k*D per matmul.

Baselines are replayed the same way from the superseded geometries (kept
here, clearly marked): the split dq + dkv backward pair this PR fused, an
identity fetch map (dead tiles still DMA'd), and phase-blind flat-update
specs (every operand fetched in every phase).  ``check_claims`` gates the
PR's claimed reductions on the COUNTED numbers:

  * backward recompute MXU (the s/dp matmuls redone from q/k):  >= 1.9x down
  * flat-update (vr_lamb) HBM block-visit bytes:                >= 40% down

``compute()`` emits the machine-readable record bench_overhead merges into
BENCH_flat_state.json; ``benchmarks.run --check-regression`` recomputes it
(pure host arithmetic, no kernel execution) and fails if the counted
hbm_bytes_per_step / mxu_flops_per_step regressed >5% vs the committed file.
"""
from __future__ import annotations

import os
import sys
from typing import Dict, Tuple

import numpy as np

# THE grid walker — shared with repro.analysis (the contract checker's
# revisit-race detector replays the same geometry the same way; keeping a
# second walker here is exactly the drift the analysis pass exists to catch)
from repro.analysis.replay import _blk_bytes, replay_dma  # noqa: F401

# One canonical config for both writing the BENCH record and the regression
# check — matches bench_overhead.packed_attention's full (non-fast) shape so
# the structural numbers describe the same kernels the latency rows time.
ATTN_CONFIG = dict(B=2, S=512, H=8, KV=2, D=64, block_q=128, block_k=128,
                   causal=True, window=0, docs=(256, 170, 54), elem_bytes=4)
# --fast bench runs shrink the measured attention shape; the cost record
# follows so the config-consistency guard (common.check_configs_agree) holds
# within a fast-written BENCH file too.
ATTN_CONFIG_FAST = dict(ATTN_CONFIG, B=1, S=256, H=4, KV=2, D=32,
                        docs=(128, 85, 27))
FLAT_CONFIG = dict(params="oracle.hostile_params", state_dtype="float32",
                   elem_bytes=4, optimizers=("flat_vr_scale", "flat_vr_adam",
                                             "flat_vr_lamb", "flat_vr_lars"))


def _total_bytes(rep: Dict[str, dict]) -> int:
    return sum(r["bytes"] for r in rep.values())


def _matmul_flops(n_matmuls: int, block_q: int, block_k: int, d: int) -> int:
    # every matmul in these kernels contracts a (block_q, block_k) tile pair
    # against D: s/dp/dq are (bq x d)(d x bk)-shaped, pv/dv/dk (bq x bk)(bk x d)
    # — identical 2*bq*bk*d FLOP count either way.
    return n_matmuls * 2 * block_q * block_k * d


def _packed_fetch(cfg: dict):
    """Concrete (fetch, live) for the bench's packed layout, via the
    kernel's own kv_fetch_blocks (the exact arrays _fwd_call prefetches)."""
    import jax.numpy as jnp

    from repro.kernels.flash_attention import kv_fetch_blocks, resolve_positions

    b, s = cfg["B"], cfg["S"]
    pos_row = np.full(s, -1, np.int32)
    o = 0
    for n in cfg["docs"]:
        pos_row[o:o + n] = np.arange(n)
        o += n
    pos = jnp.asarray(np.broadcast_to(pos_row, (b, s)))
    q_pos, k_pos, q_seg, k_seg = resolve_positions(pos, pos, s, s)
    fetch, live = kv_fetch_blocks(
        q_pos, k_pos, q_seg, k_seg, causal=cfg["causal"], window=cfg["window"],
        block_q=cfg["block_q"], block_k=cfg["block_k"],
    )
    return np.asarray(fetch), np.asarray(live)


def attention_fwd_cost(cfg: dict = ATTN_CONFIG) -> dict:
    from repro.kernels.flash_attention import fwd_geometry

    b, s, h, kvh, d = cfg["B"], cfg["S"], cfg["H"], cfg["KV"], cfg["D"]
    bq, bk, eb = cfg["block_q"], cfg["block_k"], cfg["elem_bytes"]
    grid, nq, nk, g, ins, outs, _ = fwd_geometry(
        b, s, h, d, s, kvh, block_q=bq, block_k=bk, with_lse=True
    )
    fetch, live = _packed_fetch(cfg)
    ops = [(n, sp, eb, False) for n, sp in ins.items()] + \
          [(n, sp, eb, True) for n, sp in outs.items()]
    rep = replay_dma(grid, ops, extra=(fetch.reshape(-1),))
    # baseline: identity fetch == the pre-fetch-map kernel, whose kv maps
    # returned (b, ik, ...) unconditionally so dead tiles still copied in
    ident = np.broadcast_to(np.arange(nk, dtype=np.int32), (b, nq, nk))
    rep_id = replay_dma(grid, ops, extra=(ident.reshape(-1),))
    live_pairs = int(live.sum()) * h  # liveness is head-independent
    hbm, hbm_id = _total_bytes(rep), _total_bytes(rep_id)
    return {
        "grid": list(grid),
        "live_tile_pairs": live_pairs,
        "dead_tile_pairs": b * nq * nk * h - live_pairs,
        "visits": {n: r["visits"] for n, r in rep.items()},
        "hbm_bytes": hbm,
        "hbm_bytes_identity_fetch": hbm_id,
        "dead_tile_dma_savings": 1.0 - hbm / hbm_id,
        "mxu_flops": _matmul_flops(2 * live_pairs, bq, bk, d),
    }


def attention_bwd_cost(cfg: dict = ATTN_CONFIG) -> dict:
    from jax.experimental import pallas as pl

    from repro.kernels.flash_attention_bwd import bwd_geometry

    b, s, h, kvh, d = cfg["B"], cfg["S"], cfg["H"], cfg["KV"], cfg["D"]
    bq, bk, eb = cfg["block_q"], cfg["block_k"], cfg["elem_bytes"]
    grid, nq, nk, g, ins, outs, _ = bwd_geometry(b, s, h, d, s, kvh,
                                                 block_q=bq, block_k=bk)
    ops = [(n, sp, eb, False) for n, sp in ins.items()] + \
          [(n, sp, eb, True) for n, sp in outs.items()]
    rep = replay_dma(grid, ops)

    # --- superseded baseline: the split dq + dkv kernel pair this PR fused.
    # Replayed from the pre-PR geometries (dq on the forward-shaped
    # (b, h, nq, nk) grid with kv minor; dkv on today's grid minus dq).
    q_sp = pl.BlockSpec((1, bq, 1, d), lambda b_, h_, iq, ik: (b_, iq, h_, 0))
    kv_sp = pl.BlockSpec((1, bk, 1, d), lambda b_, h_, iq, ik: (b_, ik, h_ // g, 0))
    row_sp = pl.BlockSpec((1, 1, bq), lambda b_, h_, iq, ik: (b_, h_, iq))
    qr_sp = pl.BlockSpec((1, bq), lambda b_, h_, iq, ik: (b_, iq))
    kr_sp = pl.BlockSpec((1, bk), lambda b_, h_, iq, ik: (b_, ik))
    dq_ops = [("q", q_sp, eb, False), ("k", kv_sp, eb, False),
              ("v", kv_sp, eb, False), ("lse", row_sp, eb, False),
              ("delta", row_sp, eb, False), ("do", q_sp, eb, False),
              ("q_pos", qr_sp, eb, False), ("k_pos", kr_sp, eb, False),
              ("q_seg", qr_sp, eb, False), ("k_seg", kr_sp, eb, False),
              ("dq", q_sp, eb, True)]
    rep_dq = replay_dma((b, h, nq, nk), dq_ops)
    dkv_ops = [(n, sp, e, o) for n, sp, e, o in ops if n not in ("dq",)]
    rep_dkv = replay_dma(grid, dkv_ops)

    _, live = _packed_fetch(cfg)
    live_pairs = int(live.sum()) * h
    fused_mxu = _matmul_flops(5 * live_pairs, bq, bk, d)    # s,dp,dv,dk,dq
    split_mxu = _matmul_flops(7 * live_pairs, bq, bk, d)    # + dq kernel's s,dp
    fused_rc = _matmul_flops(2 * live_pairs, bq, bk, d)     # recompute: s,dp
    split_rc = _matmul_flops(4 * live_pairs, bq, bk, d)     # s,dp in BOTH kernels
    hbm = _total_bytes(rep)
    hbm_split = _total_bytes(rep_dq) + _total_bytes(rep_dkv)
    return {
        "grid": list(grid),
        "launches": 1,
        "launches_split_baseline": 2,
        "visits": {n: r["visits"] for n, r in rep.items()},
        "hbm_bytes": hbm,
        "hbm_bytes_split_baseline": hbm_split,
        "hbm_reduction": 1.0 - hbm / hbm_split,
        "mxu_flops": fused_mxu,
        "mxu_flops_split_baseline": split_mxu,
        "recompute_mxu_flops": fused_rc,
        "recompute_mxu_flops_split_baseline": split_rc,
        "recompute_mxu_reduction": split_rc / fused_rc,
        "total_mxu_reduction": split_mxu / fused_mxu,
    }


def _flat_layout():
    tests_dir = os.path.join(os.path.dirname(__file__), "..", "tests")
    if tests_dir not in sys.path:
        sys.path.insert(0, tests_dir)
    import oracle

    from repro.core.layout import ParamLayout

    return ParamLayout.for_tree(oracle.hostile_params())


def flat_update_cost(cfg: dict = FLAT_CONFIG) -> dict:
    from jax.experimental import pallas as pl

    from repro.core.layout import LANE
    from repro.kernels import flat_update as fu

    layout = _flat_layout()
    eb = cfg["elem_bytes"]
    inv, scal, acc = fu._specs(layout)
    lids = (layout.block_leaf_ids(),)
    fixed = [("inv", inv, 4, False), ("scal", scal, 4, False)]
    blind_blk = pl.BlockSpec((layout.block_rows, LANE), lambda ph, b, *_: (b, 0))
    buf = layout.n_rows * LANE * eb
    rec = {}
    for name in cfg["optimizers"]:
        pw = fu.PHASE_WINDOWS[name]
        grid = (fu.N_PHASES, layout.n_blocks)
        pin, pout = fu._phased_specs(layout, name)
        trust = name in ("flat_vr_lamb", "flat_vr_lars")
        accs = [("uacc", acc, 4, True), ("wacc", acc, 4, True)] if trust else []
        ops = fixed + [(n, sp, eb, False) for n, sp in pin.items()] + \
            [(n, sp, eb, True) for n, sp in pout.items()] + accs
        # baseline: phase-blind maps — every operand fetched in every phase
        blind = fixed + [(n, blind_blk, eb, False) for n in pw["ins"]] + \
            [(n, blind_blk, eb, True) for n in pw["outs"]] + accs
        rep, rep_b = replay_dma(grid, ops, lids), replay_dma(grid, blind, lids)
        # the jnp trust-ratio epilogue: read u, write upd (LARS: + m, m')
        epi = {"flat_vr_lamb": 2 * buf, "flat_vr_lars": 4 * buf}.get(name, 0)
        kern, kern_b = _total_bytes(rep), _total_bytes(rep_b)
        rec[name] = {
            "grid": list(grid),
            "block_visits": sum(r["visits"] for r in rep.values()),
            "block_visits_phase_blind": sum(r["visits"] for r in rep_b.values()),
            "epilogue_hbm_bytes": epi,
            "hbm_bytes": kern + epi,
            "hbm_bytes_phase_blind": kern_b + epi,
            # what parking saves in the kernel (the epilogue is unaffected)
            "dma_reduction": 1.0 - kern / kern_b,
        }
    return rec


def flat_stats_cost(cfg: dict = FLAT_CONFIG) -> dict:
    """The grad-stats launches of the fused step: the scan-body accumulate
    and the /k finalize (one-block-one-visit streams), plus the device-wise
    pack+square payload builder (distributed path)."""
    from jax.experimental import pallas as pl

    from repro.core.layout import LANE
    from repro.kernels import flat_stats as fs

    layout = _flat_layout()
    eb = cfg["elem_bytes"]
    blk = fs._blk(layout)
    grid = (layout.n_blocks,)
    accum = replay_dma(grid, [("gs", blk, eb, False), ("g2s", blk, eb, False),
                              ("g", blk, eb, False), ("gs_out", blk, eb, True),
                              ("g2s_out", blk, eb, True)])
    inv_sp = pl.BlockSpec((1, 1), lambda i: (0, 0))
    fin = replay_dma(grid, [("gs", blk, eb, False), ("g2s", blk, eb, False),
                            ("inv", inv_sp, 4, False), ("mean", blk, eb, True),
                            ("sq", blk, eb, True)])
    pack_out = pl.BlockSpec((2, layout.block_rows, LANE), lambda i: (0, i, 0))
    pack = replay_dma(grid, [("gf", blk, eb, False),
                             ("payload", pack_out, eb, True)])
    return {
        "accum_hbm_bytes": _total_bytes(accum),
        "finalize_hbm_bytes": _total_bytes(fin),
        "pack_square_hbm_bytes": _total_bytes(pack),
    }


def compute(fast: bool = False, attn_cfg: dict | None = None) -> dict:
    """The full machine-readable cost record merged into BENCH_flat_state.json.

    The step total composes the fused train step's six launches at the bench
    configs (attention fwd primal + LSE recompute + fused bwd on the packed
    shape; stats accum + finalize + vr_lamb update on the hostile layout) —
    a trajectory-tracking composite, not an absolute model of one real net.
    ``attn_cfg`` overrides the shape (check_regression replays the COMMITTED
    config so fast- and full-written BENCH files both compare cleanly).
    """
    cfg = attn_cfg or (ATTN_CONFIG_FAST if fast else ATTN_CONFIG)
    fwd = attention_fwd_cost(cfg)
    bwd = attention_bwd_cost(cfg)
    upd = flat_update_cost()
    stats = flat_stats_cost()
    hbm_step = (2 * fwd["hbm_bytes"] + bwd["hbm_bytes"]
                + stats["accum_hbm_bytes"] + stats["finalize_hbm_bytes"]
                + upd["flat_vr_lamb"]["hbm_bytes"])
    mxu_step = 2 * fwd["mxu_flops"] + bwd["mxu_flops"]
    rec = {
        "config": {"attn": {k: list(v) if isinstance(v, tuple) else v
                            for k, v in cfg.items()},
                   "flat": {k: list(v) if isinstance(v, tuple) else v
                            for k, v in FLAT_CONFIG.items()}},
        "attention_fwd": fwd,
        "attention_bwd": bwd,
        "flat_update": upd,
        "flat_stats": stats,
        "hbm_bytes_per_step": hbm_step,
        "mxu_flops_per_step": mxu_step,
        "note": ("counted by replaying the kernels' real index maps over "
                 "their grids (DMA = block index changed vs previous step); "
                 "baselines replay the superseded split-backward, "
                 "identity-fetch, and phase-blind geometries"),
    }
    check_claims(rec)
    return rec


def check_claims(rec: dict) -> None:
    """Gate the PR's claimed reductions on the counted numbers."""
    rc = rec["attention_bwd"]["recompute_mxu_reduction"]
    if rc < 1.9:
        raise AssertionError(
            f"counted backward recompute-MXU reduction {rc:.2f}x < 1.9x — "
            "the fused one-pass backward claim does not hold structurally"
        )
    dr = rec["flat_update"]["flat_vr_lamb"]["dma_reduction"]
    if dr < 0.40:
        raise AssertionError(
            f"counted vr_lamb flat-update DMA reduction {dr:.1%} < 40% — "
            "the phase-aware index-map claim does not hold structurally"
        )


def check_regression(committed: dict, tol: float = 0.05) -> list:
    """Fresh-vs-committed comparison for ``benchmarks.run
    --check-regression``: recompute the counted fields (host arithmetic
    only) and return a list of failure strings — empty means clean.  The
    configs must match exactly; counted bytes/FLOPs may not exceed the
    committed values by more than ``tol``."""
    old = committed.get("cost_model")
    if old is None:
        return ["BENCH_flat_state.json has no cost_model record — "
                "rerun benchmarks.bench_overhead to seed it"]
    try:  # replay at the committed shape so fast-written files compare too
        attn_cfg = {k: tuple(v) if isinstance(v, list) else v
                    for k, v in old["config"]["attn"].items()}
    except (KeyError, TypeError):
        return ["committed cost_model record has no config.attn — "
                "regenerate the BENCH file"]
    fresh = compute(attn_cfg=attn_cfg)
    failures = []
    if old.get("config") != fresh["config"]:
        return [f"cost-model config changed (committed {old.get('config')} "
                f"vs fresh {fresh['config']}) — regenerate the BENCH file"]
    for key in ("hbm_bytes_per_step", "mxu_flops_per_step"):
        if fresh[key] > old[key] * (1 + tol):
            failures.append(
                f"{key} regressed: counted {fresh[key]:,} vs committed "
                f"{old[key]:,} (>{tol:.0%} worse)"
            )
    try:
        check_claims(fresh)
    except AssertionError as e:
        failures.append(str(e))
    return failures
