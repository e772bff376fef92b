"""Pallas TPU kernel: causal / sliding-window flash attention (GQA-aware),
position- and segment-aware, with a custom VJP so the TRAINING forward runs
on the fused path too.

Forward grid (B, H, nq, nk) with the kv dim innermost: the output block for
(b, h, iq) is revisited across ik while running max / denominator /
accumulator live in VMEM scratch — the classic online-softmax pipeline,
MXU-fed by (BLOCK_Q x D) @ (D x BLOCK_K) tiles.  When the call is being
differentiated the forward additionally emits the LSE residual
``lse[b, h, i] = m_i + log l_i`` per query row — the only extra tensor the
recomputation-based FlashAttention-2 backward needs (Dao 2023, Alg. 2).
The backward kernels live in kernels/flash_attention_bwd.py.

GQA: the kv-head index is h // (H // KV) inside the BlockSpec index maps, so
grouped queries stream the same k/v tiles without materializing the repeat.

Positions and segments are EXPLICIT kernel operands (the packed-sequence
contract):

  * q_pos (B, Sq) / k_pos (B, Skv) int32 — absolute positions; a value < 0
    marks padding (the kv-cache convention).  When the caller passes no
    positions the implicit training layout arange(S) is materialized here,
    outside the kernel.
  * q_seg / k_seg int32 — segment (document) ids, derived from positions by
    ``segment_ids_from_positions``: a new segment starts wherever the
    position does not increase by exactly 1.  Packed batches (several
    documents per row, each restarting at position 0) therefore mask
    cross-document attention with ``q_seg == k_seg`` without any extra
    model-level input.

Masking rule per (q, k) pair: ``q_pos >= 0 & k_pos >= 0 & q_seg == k_seg``
plus causal ``k_pos <= q_pos`` and window ``k_pos > q_pos - window``.
Partial-block bounds are folded into the operands: out-of-range rows of edge
tiles are sanitized to position -1 / segment < 0 on load.

Masking convention: a query row with NO valid kv position (padding, or
sliding windows past the end of a shorter kv sequence) produces EXACTLY zero
output and ``lse = NEG_INF`` — not the `acc / max(l, eps)` garbage of a
clamped divide.  ref.attention_ref is the oracle and shares the convention.

Dead tiles skip their DMA, not just their compute: the kv-side operands
(k, v and the k_pos/k_seg rows) are indexed through a scalar-prefetched
FETCH MAP (``kv_fetch_blocks``) that replays the dead-tile predicate
OUTSIDE the kernel and forward-fills dead grid steps with the previous
live kv block index — Mosaic skips an operand's copy-in whenever its
index map returns the same block as the previous step, so fully-dead
packed-tail and cross-segment tiles never fetch their k/v blocks at all.
Implicit-arange callers get a STATIC numpy fetch map from
``tile_reachable_static`` (causal grids stop re-DMAing above-diagonal
blocks too) and keep the free grid-index compute predicate; explicit-
position callers derive the map from per-tile pos/seg bounds
(``tile_reachable`` vmapped over blocks) and the in-kernel live predicate
becomes ``fetch[step] == ik`` — the fetched block is the tile's own block
exactly on live steps, so compute can never run against a stale
forward-filled kv window.

Autodiff composes to arbitrary order: first-order grads run the fused Pallas
backward; the Pallas entry points carry jnp-replica VJPs so jax.grad twice
(and jvp-of-vjp) falls back to differentiable jnp math instead of hitting a
non-differentiable pallas_call.  Position/segment operands are integer inputs
and receive symbolic-zero (None) cotangents.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.analysis.layout_contracts import LANE
from repro.backend import resolve_interpret

DEFAULT_BLOCK_Q = 128
DEFAULT_BLOCK_K = 128
NEG_INF = -1e30
_BIG = 2**30  # position/segment sentinel for masked min/max bounds


def segment_ids_from_positions(pos: jnp.ndarray) -> jnp.ndarray:
    """(B, S) int32 positions -> (B, S) int32 segment ids.

    THE packed-layout contract: a new segment starts wherever the position
    does not increase by exactly 1 (documents are arange runs, possibly
    offset; packed rows restart at 0; pads carry -1 and land in throwaway
    segments that the ``pos >= 0`` validity mask kills anyway).  A plain
    arange — or any single offset run — yields one segment, so the implicit
    training layout is the trivial case of the same rule.
    """
    pos = pos.astype(jnp.int32)
    starts = jnp.concatenate(
        [jnp.ones_like(pos[:, :1], bool), pos[:, 1:] != pos[:, :-1] + 1], axis=1
    )
    return jnp.cumsum(starts.astype(jnp.int32), axis=1) - 1


def tile_mask(qp, kp, qs, ks, causal: bool, window: int):
    """(block_q, block_k) validity mask for one tile from SANITIZED per-tile
    position/segment vectors — THE masking rule, shared by the forward and
    backward kernels so the backward's softmax recompute p = exp(s - lse) can
    never drift from the mask the forward's lse was built under.

    qp/qs: (block_q, 1) or (block_q,) int32, kp/ks (1, block_k) or
    (block_k,) (rank-normalized here: q-side to columns, k-side to rows —
    a no-op on the kernels' own column/row tiles); out-of-range
    rows of partial edge tiles arrive as pos -1 / seg < 0 (see
    _load_pos_seg), so the ``pos >= 0`` terms subsume the old seq-bound
    checks.
    """
    qp2, qs2 = qp.reshape(-1, 1), qs.reshape(-1, 1)
    kp2, ks2 = kp.reshape(1, -1), ks.reshape(1, -1)
    mask = (qp2 >= 0) & (kp2 >= 0) & (qs2 == ks2)
    if causal:
        mask &= kp2 <= qp2
    if window > 0:
        mask &= kp2 > qp2 - window
    return mask


def tile_reachable_static(iq, ik, block_q: int, block_k: int, causal: bool, window: int):
    """Grid-index dead-tile predicate for the IMPLICIT arange layout: two
    scalar comparisons, no operand reads.  Returns None when the tile grid
    is statically dense (non-causal, no window), so callers can skip the
    pl.when entirely."""
    ok = None
    if causal:  # earliest k in tile vs latest q in tile
        ok = ik * block_k <= iq * block_q + (block_q - 1)
    if window > 0:  # latest k in tile vs the window's left edge for latest q
        c = ik * block_k + (block_k - 1) > iq * block_q - window
        ok = c if ok is None else ok & c
    return ok


def tile_reachable(qp, kp, qs, ks, causal: bool, window: int):
    """Scalar predicate: can ANY (q, k) pair in this tile be unmasked?

    Computed from per-tile pos/seg bounds of the sanitized operand vectors
    (invalid entries excluded from the min/max via +-_BIG sentinels): causal
    kills tiles whose earliest k sits after the latest q, a sliding window
    kills tiles wholly left of the window, disjoint segment ranges kill
    cross-document tiles, and all-padding tiles are dead outright.  For the
    implicit arange layout this reduces to the grid-index predicate
    tile_reachable_static, which the kernels use instead when the caller's
    positions were implicit (no bound reductions on a layout whose dead
    tiles are known from grid indices alone).
    """
    # rank-2 for the VPU; in-kernel operands already arrive rank-2 (q column,
    # k row) and are reduced as they are, with no relayout
    qp, qs, kp, ks = (x.reshape(1, -1) if x.ndim == 1 else x for x in (qp, qs, kp, ks))
    qv, kv = qp >= 0, kp >= 0
    qp_max = jnp.max(jnp.where(qv, qp, -_BIG))
    kp_min = jnp.min(jnp.where(kv, kp, _BIG))
    ok = jnp.any(qv) & jnp.any(kv)
    # segment ranges must overlap (segments are nondecreasing along the row)
    qs_min = jnp.min(jnp.where(qv, qs, _BIG))
    qs_max = jnp.max(jnp.where(qv, qs, -_BIG))
    ks_min = jnp.min(jnp.where(kv, ks, _BIG))
    ks_max = jnp.max(jnp.where(kv, ks, -_BIG))
    ok &= (qs_min <= ks_max) & (ks_min <= qs_max)
    if causal:  # earliest valid k vs latest valid q
        ok &= kp_min <= qp_max
    if window > 0:  # latest valid k vs the window's left edge for latest q
        qp_min = jnp.min(jnp.where(qv, qp, _BIG))
        kp_max = jnp.max(jnp.where(kv, kp, -_BIG))
        ok &= kp_max > qp_min - window
    return ok


def zero_oob_rows(x, i, block: int, seq: int):
    """Zero rows of a (block, d) tile beyond ``seq`` (interpret mode pads
    partial blocks with NaN; 0 * NaN would poison the MXU accumulations).
    Returns (x_zeroed, (block, 1) validity column)."""
    valid = i * block + jax.lax.broadcasted_iota(jnp.int32, (x.shape[0], 1), 0) < seq
    return jnp.where(valid, x, 0.0), valid


def _load_pos_seg(pos_ref, seg_ref, i, block: int, seq: int, seg_fill: int):
    """Sanitized pos/seg tiles: entries beyond ``seq`` (the NaN/garbage
    padding of partial edge blocks) become pos -1 and a negative seg
    sentinel.  seg_fill differs between the q (-1) and k (-2) sides so
    out-of-range q rows can never segment-match out-of-range k rows.

    The q side arrives as a (block, 1) column and the k side as a (1, block)
    row, so ``tile_mask`` broadcasts them into the (block_q, block_k) tile
    with no in-kernel transpose.  Everything stays rank-2 (Mosaic rejects
    iota of rank < 2)."""
    shape = pos_ref.shape
    axis = 0 if shape[0] == block else 1
    idx = i * block + jax.lax.broadcasted_iota(jnp.int32, shape, axis)
    valid = idx < seq
    pos = jnp.where(valid, pos_ref[...], -1)
    seg = jnp.where(valid, seg_ref[...], seg_fill)
    return pos, seg


def _ffill_fetch(live, nk, xp):
    """live (..., nk) bool -> (..., nk) int32 fetch map: each live step
    fetches its own block (fetch == ik); dead steps repeat the nearest live
    index (previous live block, or — for leading dead runs — the FIRST live
    block, pre-fetched early so arriving at it is free too).  Consecutive-
    equal indices are exactly the steps whose copy-in Mosaic elides, so the
    kv DMA count collapses to the number of LIVE tiles."""
    ids = xp.where(live, xp.arange(nk, dtype=xp.int32), -1)
    if xp is jnp:
        ff = jax.lax.cummax(ids, axis=ids.ndim - 1)
    else:
        ff = np.maximum.accumulate(ids, axis=-1)
    first = xp.argmax(live, axis=-1).astype(xp.int32)  # 0 when no tile is live
    return xp.where(ff < 0, first[..., None], ff).astype(xp.int32)


def kv_fetch_blocks(q_pos, k_pos, q_seg, k_seg, *, causal: bool, window: int,
                    block_q: int, block_k: int):
    """(B, nq, nk) int32 kv fetch map (+ the (B, nq, nk) live mask) from the
    EXPLICIT position/segment operands — ``tile_reachable`` vmapped over the
    block-padded pos/seg tiles, padded exactly like the in-kernel sanitize
    (_load_pos_seg: pos -1, q-seg -1 / k-seg -2), then forward-filled so
    dead grid steps repeat a live block index (see _ffill_fetch)."""
    b, sq = q_pos.shape
    skv = k_pos.shape[1]
    nq, nk = -(-sq // block_q), -(-skv // block_k)

    def blocks(x, n, block, fill):
        pad = n * block - x.shape[1]
        return jnp.pad(x, ((0, 0), (0, pad)), constant_values=fill).reshape(b, n, block)

    qp = blocks(q_pos, nq, block_q, -1)
    qs = blocks(q_seg, nq, block_q, -1)
    kp = blocks(k_pos, nk, block_k, -1)
    ks = blocks(k_seg, nk, block_k, -2)
    live = jax.vmap(  # batch rows
        lambda qpb, qsb, kpb, ksb: jax.vmap(  # q blocks
            lambda qp1, qs1: jax.vmap(  # k blocks
                lambda kp1, ks1: tile_reachable(qp1, kp1, qs1, ks1, causal, window)
            )(kpb, ksb)
        )(qpb, qsb)
    )(qp, qs, kp, ks)
    return _ffill_fetch(live, nk, jnp), live


def static_fetch_blocks(nq: int, nk: int, block_q: int, block_k: int,
                        causal: bool, window: int) -> np.ndarray:
    """(nq, nk) int32 fetch map for the IMPLICIT arange layout, computed in
    numpy at trace time from the grid-index predicate (identity for dense
    grids; causal/window grids stop fetching unreachable blocks)."""
    live = np.ones((nq, nk), bool)
    for iq in range(nq):
        for ik in range(nk):
            ok = tile_reachable_static(iq, ik, block_q, block_k, causal, window)
            if ok is not None:
                live[iq, ik] = bool(ok)
    return _ffill_fetch(live, nk, np)


def _maybe_skip_dead_tile(
    compute, qp, kp, qs, ks, causal: bool, window: int,
    *, implicit: bool, iq, ik, block_q: int, block_k: int,
):
    """Run ``compute`` only on reachable tiles (scratch accumulators are
    simply left untouched on dead ones).  ``implicit`` (static) selects the
    grid-index predicate — free for dense grids — over the pos/seg-bound
    reductions only packed layouts need."""
    if implicit:
        live = tile_reachable_static(iq, ik, block_q, block_k, causal, window)
        if live is None:
            compute()
        else:
            pl.when(live)(compute)
    else:
        pl.when(tile_reachable(qp, kp, qs, ks, causal, window))(compute)


def _kernel(
    fetch_ref, q_ref, k_ref, v_ref, qp_ref, kp_ref, qs_ref, ks_ref, *rest,
    causal: bool, window: int, block_q: int, block_k: int, scale: float,
    seq_q: int, seq_kv: int, with_lse: bool, implicit: bool,
):
    if with_lse:
        o_ref, lse_ref, m_scr, l_scr, acc_scr = rest
    else:
        (o_ref, m_scr, l_scr, acc_scr) = rest
    iq = pl.program_id(2)
    ik = pl.program_id(3)
    nk = pl.num_programs(3)

    @pl.when(ik == 0)
    def _init():
        m_scr[...] = jnp.full_like(m_scr, NEG_INF)
        l_scr[...] = jnp.zeros_like(l_scr)
        acc_scr[...] = jnp.zeros_like(acc_scr)

    qp, qs = _load_pos_seg(qp_ref, qs_ref, iq, block_q, seq_q, seg_fill=-1)
    kp, ks = _load_pos_seg(kp_ref, ks_ref, ik, block_k, seq_kv, seg_fill=-2)

    def _compute():
        q = q_ref[...].astype(jnp.float32)  # (BQ, D)
        k, _ = zero_oob_rows(k_ref[...].astype(jnp.float32), ik, block_k, seq_kv)
        v, _ = zero_oob_rows(v_ref[...].astype(jnp.float32), ik, block_k, seq_kv)
        s = jax.lax.dot_general(
            q * scale, k, (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32
        )  # (BQ, BK)

        mask = tile_mask(qp, kp, qs, ks, causal, window)
        s = jnp.where(mask, s, NEG_INF)

        m_prev = m_scr[...]  # (BQ, 1)
        l_prev = l_scr[...]
        m_new = jnp.maximum(m_prev, jnp.max(s, axis=1, keepdims=True))
        # exact zeros for masked entries: a fully-masked row has s == m ==
        # NEG_INF everywhere, where exp(s - m) would be 1 and the row would
        # silently turn into a uniform average over kv — the l stays 0 so
        # _finalize can emit 0.
        p = jnp.where(mask, jnp.exp(s - m_new), 0.0)
        corr = jnp.exp(m_prev - m_new)
        l_new = l_prev * corr + jnp.sum(p, axis=1, keepdims=True)
        acc_scr[...] = acc_scr[...] * corr + jax.lax.dot_general(
            p, v, (((1,), (0,)), ((), ())), preferred_element_type=jnp.float32
        )
        m_scr[...] = m_new
        l_scr[...] = l_new

    if implicit:
        # grid-index predicate: free, and the static fetch map is built from
        # the SAME tile_reachable_static, so live steps always hold their own
        # kv block.
        _maybe_skip_dead_tile(_compute, qp, kp, qs, ks, causal, window,
                              implicit=True, iq=iq, ik=ik,
                              block_q=block_q, block_k=block_k)
    else:
        # the kv windows hold the FETCH-MAPPED block, which is this tile's own
        # block exactly when the tile was live in the prefetched map (dead
        # steps repeat a neighbouring live index, so their stale windows are
        # never read).  Replaces the in-kernel tile_reachable bound reductions
        # — the map was computed from the same predicate outside.
        live = fetch_ref[(pl.program_id(0) * pl.num_programs(2) + iq) * nk + ik] == ik
        pl.when(live)(_compute)

    @pl.when(ik == nk - 1)
    def _finalize():
        l = l_scr[...]
        valid = l > 0.0  # rows with at least one unmasked kv position
        o_ref[...] = jnp.where(
            valid, acc_scr[...] / jnp.maximum(l, 1e-30), 0.0
        ).astype(o_ref.dtype)
        if with_lse:
            lse_ref[...] = jnp.where(
                valid, m_scr[...] + jnp.log(jnp.maximum(l, 1e-30)), NEG_INF
            )


def bhsd(x):
    """(B, S, H, D) <-> (B, H, S, D).  The kernels run on the head-major
    layout, so every block's last two dims are (rows, head_dim) — the only
    tiling Mosaic accepts for D < 128 (a (block, 1, D) block of a
    (B, S, H, D) array has a sublane dim of 1)."""
    return jnp.swapaxes(x, 1, 2)


def fwd_geometry(b, sq, h, d, skv, kvh, *, block_q: int, block_k: int, with_lse: bool):
    """Grid, named BlockSpecs and array shapes of the forward pallas_call.

    Single source of truth shared between _fwd_call, the contract checker
    and benchmarks.cost_model.  q/out are (B, H, Sq, D), k/v (B, KV, Skv, D);
    the q-side pos/seg operands are (B, Sq, 1) columns and the k-side ones
    (B, 1, Skv) rows; the LSE residual is (B, H, Sq, 1).  Every index map
    takes the flattened (B*nq*nk,) int32 fetch array as its trailing
    scalar-prefetch argument; the kv-side maps (k, v, k_pos, k_seg) read the
    fetch-mapped block so dead grid steps repeat the previous index and
    Mosaic elides their copy-in.
    """
    g = h // kvh
    nq = -(-sq // block_q)
    nk = -(-skv // block_k)
    grid = (b, h, nq, nk)

    def fetched(b_, iq, ik, f):
        return f[(b_ * nq + iq) * nk + ik]

    q_spec = pl.BlockSpec((None, None, block_q, d), lambda b_, h_, iq, ik, f: (b_, h_, iq, 0))
    kv_spec = pl.BlockSpec(
        (None, None, block_k, d),
        lambda b_, h_, iq, ik, f: (b_, h_ // g, fetched(b_, iq, ik, f), 0))
    qcol_spec = pl.BlockSpec((None, block_q, 1), lambda b_, h_, iq, ik, f: (b_, iq, 0))
    krow_spec = pl.BlockSpec(
        (None, 1, block_k), lambda b_, h_, iq, ik, f: (b_, 0, fetched(b_, iq, ik, f)))
    ins = {
        "q": q_spec, "k": kv_spec, "v": kv_spec, "q_pos": qcol_spec,
        "k_pos": krow_spec, "q_seg": qcol_spec, "k_seg": krow_spec,
    }
    outs = {"out": q_spec}
    shapes = {
        "q": (b, h, sq, d), "k": (b, kvh, skv, d), "v": (b, kvh, skv, d),
        "q_pos": (b, sq, 1), "k_pos": (b, 1, skv), "q_seg": (b, sq, 1),
        "k_seg": (b, 1, skv), "out": (b, h, sq, d),
    }
    if with_lse:
        outs["lse"] = pl.BlockSpec((None, None, block_q, 1),
                                   lambda b_, h_, iq, ik, f: (b_, h_, iq, 0))
        shapes["lse"] = (b, h, sq, 1)
    return grid, nq, nk, g, ins, outs, shapes


def pos_operands(q_pos, k_pos, q_seg, k_seg):
    """(B, S) int32 pos/seg -> the kernels' operands: q side as (B, Sq, 1)
    columns, k side as (B, 1, Skv) rows."""
    return q_pos[:, :, None], k_pos[:, None, :], q_seg[:, :, None], k_seg[:, None, :]


def _fwd_call(q, k, v, q_pos, k_pos, q_seg, k_seg,
              *, causal, window, block_q, block_k, interpret, with_lse, implicit):
    """One pallas_call on the head-major layout: q (B,H,Sq,D), k/v
    (B,KV,Skv,D), pos/seg (B,S) int32 -> out (B,H,Sq,D) [+ lse (B,H,Sq,1)
    f32 when with_lse]."""
    b, h, sq, d = q.shape
    kvh, skv = k.shape[1], k.shape[2]
    scale = d**-0.5
    grid, nq, nk, g, ins, out_spec_map, _ = fwd_geometry(
        b, sq, h, d, skv, kvh, block_q=block_q, block_k=block_k, with_lse=with_lse
    )
    if implicit:
        fetch = jnp.asarray(
            np.broadcast_to(
                static_fetch_blocks(nq, nk, block_q, block_k, causal, window),
                (b, nq, nk),
            ).reshape(-1)
        )
    else:
        fetch, _ = kv_fetch_blocks(
            q_pos, k_pos, q_seg, k_seg,
            causal=causal, window=window, block_q=block_q, block_k=block_k,
        )
        fetch = fetch.reshape(-1)
    out_shape = [jax.ShapeDtypeStruct((b, h, sq, d), q.dtype)]
    if with_lse:
        out_shape.append(jax.ShapeDtypeStruct((b, h, sq, 1), jnp.float32))
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,
        grid=grid,
        in_specs=list(ins.values()),
        out_specs=list(out_spec_map.values()),
        scratch_shapes=[
            pltpu.VMEM((block_q, 1), jnp.float32),
            pltpu.VMEM((block_q, 1), jnp.float32),
            pltpu.VMEM((block_q, d), jnp.float32),
        ],
    )
    outs = pl.pallas_call(
        functools.partial(
            _kernel, causal=causal, window=window,
            block_q=block_q, block_k=block_k, scale=scale, seq_q=sq, seq_kv=skv,
            with_lse=with_lse, implicit=implicit,
        ),
        grid_spec=grid_spec,
        out_shape=out_shape,
        interpret=interpret,
    )(fetch, q, k, v, *pos_operands(q_pos, k_pos, q_seg, k_seg))
    return tuple(outs) if with_lse else (outs[0],)


_NO_POS_GRADS = (None, None, None, None)  # int operands: symbolic-zero cotangents


@functools.lru_cache(maxsize=None)
def _flash_fn(causal: bool, window: int, block_q: int, block_k: int,
              interpret: bool, implicit: bool):
    """custom_vjp'd flash attention on the head-major (B,H,S,D) layout for
    one static config.

    Three nested custom_vjp layers keep every pallas_call out of autodiff's
    reach while staying differentiable to arbitrary order:

      flash     primal: fused fwd (no LSE).  vjp: fused bwd via _bwd_p.
      _fwd_p    primal: fused fwd emitting LSE (the residual producer).
                vjp (2nd order+): jnp replica attention_fwd_ref.
      _bwd_p    primal: fused dq + dk/dv kernel.
                vjp (2nd order+): jnp replica attention_bwd_ref.

    All three take the (q_pos, k_pos, q_seg, k_seg) int operands positionally
    and return None cotangents for them.
    """
    from repro.kernels import flash_attention_bwd as fab

    kw = dict(causal=causal, window=window, block_q=block_q, block_k=block_k,
              interpret=interpret, implicit=implicit)
    pos_kw = lambda qp, kp, qs, ks: dict(q_pos=qp, k_pos=kp, q_seg=qs, k_seg=ks)

    @jax.custom_vjp
    def _fwd_p(q, k, v, qp, kp, qs, ks):
        return _fwd_call(q, k, v, qp, kp, qs, ks, with_lse=True, **kw)

    def _fwd_p_fwd(q, k, v, qp, kp, qs, ks):
        return _fwd_p(q, k, v, qp, kp, qs, ks), (q, k, v, qp, kp, qs, ks)

    def _fwd_p_bwd(res, ct):
        q, k, v, qp, kp, qs, ks = res

        def ref(q_, k_, v_):  # the BSHD oracle, on the kernels' layout
            out, lse = fab.attention_fwd_ref(
                bhsd(q_), bhsd(k_), bhsd(v_), causal=causal, window=window,
                **pos_kw(qp, kp, qs, ks))
            return bhsd(out), lse[..., None]

        _, vjp = jax.vjp(ref, q, k, v)
        return vjp(ct) + _NO_POS_GRADS

    _fwd_p.defvjp(_fwd_p_fwd, _fwd_p_bwd)

    @jax.custom_vjp
    def _bwd_p(q, k, v, lse, delta, do, qp, kp, qs, ks):
        return fab.flash_attention_bwd(q, k, v, lse, delta, do, qp, kp, qs, ks, **kw)

    def _bwd_p_fwd(q, k, v, lse, delta, do, qp, kp, qs, ks):
        return _bwd_p(q, k, v, lse, delta, do, qp, kp, qs, ks), (
            q, k, v, lse, delta, do, qp, kp, qs, ks
        )

    def _bwd_p_bwd(res, ct):
        qp, kp, qs, ks = res[6:]
        _, vjp = jax.vjp(
            lambda *a: fab.attention_bwd_ref(
                *a, causal=causal, window=window, **pos_kw(qp, kp, qs, ks)
            ),
            *res[:6],
        )
        return vjp(ct) + _NO_POS_GRADS

    _bwd_p.defvjp(_bwd_p_fwd, _bwd_p_bwd)

    @jax.custom_vjp
    def flash(q, k, v, qp, kp, qs, ks):
        return _fwd_call(q, k, v, qp, kp, qs, ks, with_lse=False, **kw)[0]

    def flash_fwd(q, k, v, qp, kp, qs, ks):
        out, lse = _fwd_p(q, k, v, qp, kp, qs, ks)
        return out, (q, k, v, out, lse, qp, kp, qs, ks)

    def flash_bwd(res, do):
        q, k, v, out, lse, qp, kp, qs, ks = res
        # FlashAttention-2 preprocess: delta_i = <dO_i, O_i> — one cheap
        # element-wise jnp pass (XLA fuses it), not a kernel launch.
        delta = jnp.sum(
            do.astype(jnp.float32) * out.astype(jnp.float32), axis=-1, keepdims=True
        )
        return _bwd_p(q, k, v, lse, delta, do, qp, kp, qs, ks) + _NO_POS_GRADS

    flash.defvjp(flash_fwd, flash_bwd)
    return flash


def resolve_positions(q_pos, k_pos, sq: int, skv: int, q_seg=None, k_seg=None):
    """Normalize the position operands: (q_pos, k_pos, q_seg, k_seg) int32.

    Both positions explicit -> segments derived (unless also explicit);
    neither -> the implicit training layout arange(S), which is only
    well-defined for Sq == Skv (see flash_attention).  Exactly one explicit
    position operand is a contract violation.

    DERIVED-SEGMENT CONTRACT: segment_ids_from_positions numbers segments
    as per-STREAM ordinals (0, 1, ... along each row).  Ordinals from two
    DIFFERENT position streams (q_pos and k_pos distinct arrays, e.g. a
    query block continuing a multi-document kv cache) only align when each
    side is a single segment — a q continuing the cache's document 2 would
    derive q_seg=0 and match the cache's document 0.  Cross-stream
    multi-segment layouts must pass EXPLICIT q_seg/k_seg (certified by
    tests/test_oracle.py::test_cross_stream_segments_need_explicit_ids);
    self-attention (k_pos is q_pos) and single-segment-per-side layouts are
    safe to derive.  Not checkable here: segment counts are data-dependent
    and this runs under jit.
    """
    if (q_pos is None) != (k_pos is None):
        raise ValueError(
            "flash_attention: q_pos and k_pos must be passed together "
            f"(got q_pos={'set' if q_pos is not None else None}, "
            f"k_pos={'set' if k_pos is not None else None})"
        )
    if q_pos is None:
        if sq != skv:
            raise ValueError(
                "flash_attention: implicit arange positions are only defined "
                f"for Sq == Skv, got Sq={sq}, Skv={skv} — the q-vs-kv "
                "alignment would be ambiguous (start- vs end-aligned). "
                "Pass explicit q_pos/k_pos (B, S) int32 instead."
            )
        q_pos = k_pos = jnp.arange(sq, dtype=jnp.int32)[None, :]
        # an arange is one segment: skip the cumsum derivation
        if q_seg is None:
            q_seg = jnp.zeros((1, sq), jnp.int32)
        if k_seg is None:
            k_seg = q_seg
    q_pos = jnp.asarray(q_pos, jnp.int32)
    k_pos = jnp.asarray(k_pos, jnp.int32)
    if q_seg is None:
        q_seg = segment_ids_from_positions(q_pos)
    if k_seg is None:
        k_seg = (
            q_seg if k_pos is q_pos else segment_ids_from_positions(k_pos)
        )
    return q_pos, k_pos, jnp.asarray(q_seg, jnp.int32), jnp.asarray(k_seg, jnp.int32)


@functools.partial(
    jax.jit, static_argnames=("causal", "window", "block_q", "block_k", "interpret")
)
def flash_attention(
    q: jnp.ndarray,
    k: jnp.ndarray,
    v: jnp.ndarray,
    q_pos: jnp.ndarray | None = None,
    k_pos: jnp.ndarray | None = None,
    q_seg: jnp.ndarray | None = None,
    k_seg: jnp.ndarray | None = None,
    *,
    causal: bool = True,
    window: int = 0,
    block_q: int = DEFAULT_BLOCK_Q,
    block_k: int = DEFAULT_BLOCK_K,
    interpret: bool | None = None,
) -> jnp.ndarray:
    """q: (B,S,H,D); k,v: (B,Skv,KV,D) -> (B,S,H,D).  Differentiable.

    The kernels run on the head-major (B,H,S,D) layout (``bhsd``); the
    transposes in and out are XLA ops around the custom VJP.  ``interpret``
    None follows the platform (repro.backend.default_interpret).

    q_pos/k_pos: optional (B, S)/(B, Skv) int32 absolute positions (pos < 0
    = padding); omitted -> the implicit training arange, which REQUIRES
    Sq == Skv (a loud ValueError otherwise — the old kernel silently start-
    aligned the two aranges).  Segment ids are derived from positions
    (segment_ids_from_positions) unless passed explicitly, so packed
    multi-document rows mask cross-document attention with no extra operand.
    """
    b, sq, h, d = q.shape
    skv = k.shape[1]
    implicit = q_pos is None  # static: picks the grid-index dead-tile skip
    q_pos, k_pos, q_seg, k_seg = resolve_positions(
        q_pos, k_pos, sq, skv, q_seg=q_seg, k_seg=k_seg
    )
    q_pos = jnp.broadcast_to(q_pos, (b, sq))
    k_pos = jnp.broadcast_to(k_pos, (b, skv))
    q_seg = jnp.broadcast_to(q_seg, (b, sq))
    k_seg = jnp.broadcast_to(k_seg, (b, skv))
    block_q = min(block_q, sq)
    block_k = min(block_k, skv)
    fn = _flash_fn(causal, window, block_q, block_k, resolve_interpret(interpret), implicit)
    return bhsd(fn(bhsd(q), bhsd(k), bhsd(v), q_pos, k_pos, q_seg, k_seg))


# ---------------------------------------------------------------------------
# contract registration (repro.analysis): the forward geometry replayed with
# a REAL fetch map (kv_fetch_blocks on packed configs, static_fetch_blocks
# on the implicit layout) as the scalar-prefetch extra
# ---------------------------------------------------------------------------


def _analysis_positions(b: int, s: int, docs) -> np.ndarray:
    """(B, S) int32 packed positions: per-doc aranges, -1 tail padding."""
    row = np.full(s, -1, np.int32)
    i = 0
    for n in docs:
        row[i:i + n] = np.arange(n)
        i += n
    return np.tile(row, (b, 1))


def _analysis_geometry(B, S, H, KV, D, *, causal=True, window=0, docs=None,
                       dtype="float32", block_q=DEFAULT_BLOCK_Q,
                       block_k=DEFAULT_BLOCK_K):
    from repro.analysis.registry import FetchMap, Geometry, Operand

    bq, bk = min(block_q, S), min(block_k, S)
    grid, nq, nk, _, ins, outs, shapes = fwd_geometry(
        B, S, H, D, S, KV, block_q=bq, block_k=bk, with_lse=True)
    if docs is not None:
        qp, kp, qs, ks = resolve_positions(
            jnp.asarray(_analysis_positions(B, S, docs)),
            jnp.asarray(_analysis_positions(B, S, docs)), S, S)
        fetch, live = kv_fetch_blocks(qp, kp, qs, ks, causal=causal,
                                      window=window, block_q=bq, block_k=bk)
        fetch, live = np.asarray(fetch), np.asarray(live)
        fm = FetchMap(fetch, live=live, n_blocks=nk)
    else:
        fetch = np.broadcast_to(
            static_fetch_blocks(nq, nk, bq, bk, causal, window), (B, nq, nk))
        fm = FetchMap(fetch, n_blocks=nk,
                      dense_identity=not causal and window == 0)

    def op(name, spec):
        dt = {"lse": "float32"}.get(name, "int32" if name.endswith(("_pos", "_seg")) else dtype)
        return Operand(spec, shapes[name], dtype=dt)

    return Geometry(
        grid=grid,
        ins={n: op(n, s) for n, s in ins.items()},
        outs={n: op(n, s) for n, s in outs.items()},
        # m/l are (bq, 1) columns, lane-padded in VMEM
        scratch_bytes=4 * (2 * bq * LANE + bq * D),
        extra=(fetch.reshape(-1),),
        fetch_maps={"kv": fm},
    )


def _register():
    from repro.analysis.registry import register_kernel

    register_kernel(
        "flash_attention_fwd",
        module=__name__,
        oracle="attention_fwd_ref",
        build=_analysis_geometry,
        configs={
            "representative": dict(B=2, S=512, H=8, KV=2, D=64,
                                   causal=True, docs=(256, 170, 54)),
            "hostile_packed_bf16": dict(B=1, S=130, H=4, KV=2, D=32,
                                        causal=True, docs=(70, 41, 19),
                                        dtype="bfloat16"),
            "hostile_dense_identity": dict(B=1, S=256, H=2, KV=2, D=64,
                                           causal=False, docs=None),
        },
    )


_register()
