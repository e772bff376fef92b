"""Attention layer: GQA/MQA, causal, sliding-window, cross-attention.

Three execution paths:

  * naive      — materialize (Sq, Skv) scores; used when the score matrix is
                 small (training at moderate seq, decode, cross-attn to short
                 memory).
  * chunked    — online-softmax over kv-chunks inside a scan over q-chunks
                 ("flash attention in jnp"); the default for long prefill.
                 This is also the reference semantics for the Pallas kernel
                 in kernels/flash_attention.py.
  * kernel     — pl.pallas_call flash attention (TPU target); selected by a
                 Backend plan with a fused ``attention`` subsystem
                 (repro.backend) for self-attention TRAIN and
                 prefill.  The kernel carries a custom VJP with fused Pallas
                 backward kernels (kernels/flash_attention_bwd.py) and takes
                 EXPLICIT position/segment operands, so packed and offset
                 position layouts run fused too.  CROSS-attention train and
                 prefill route through the same Sq != Skv kernel with
                 explicit all-zero segments (cross has no segment gating).
                 Self-attention DECODE runs a forward-only flash kernel over
                 the paged cache (kernels/flash_decode.py) — only cross
                 DECODE (ragged memory-explicit kv cache) falls back to the
                 jnp paths.

All three paths share one masking contract: positions < 0 are padding,
causal/window compare absolute positions, and segment ids — derived from
positions by segment_ids_from_positions (a new segment wherever the position
does not increase by exactly 1) — gate cross-document attention in packed
rows.  Decode additionally runs a dedicated fused path
(kernels/flash_decode.py) when the plan's ``attention`` subsystem is fused.

KV caches are PAGED and segment-aware: a slot is assigned by SEQUENCE INDEX
(a per-row ``fill`` cursor counting tokens ever written, mod cache_len — NOT
by position, which collides across the documents of a packed row), and every
slot stores its absolute position (``kpos``, -1 = empty) AND its row-global
segment id (``kseg``).  Attention over the cache is therefore order-
independent: the mask reads only (kpos, kseg), so documents may interleave
arbitrarily in slot order — several in-flight requests can share one cache
row, each gated to its own segment.  Full caches and sliding-window ring
buffers share the same rule (the fill cursor wraps, evicting in arrival
order).  ``seg_base`` offsets the segment ids stored by a prefill so a chunk
appended to a partially-used row continues the row's segment numbering.
"""
from __future__ import annotations

from typing import Dict, Optional, Tuple

import jax
import jax.numpy as jnp

from repro.backend import Backend, resolve_backend
from repro.kernels.flash_attention import segment_ids_from_positions
from repro.models.common import apply_rope, normal_init

NEG_INF = -1e30


def attn_init(key, d_model: int, n_heads: int, n_kv_heads: int, head_dim: int) -> Dict:
    kq, kk, kv, ko = jax.random.split(key, 4)
    return {
        "wq": normal_init(kq, (d_model, n_heads * head_dim)),
        "wk": normal_init(kk, (d_model, n_kv_heads * head_dim)),
        "wv": normal_init(kv, (d_model, n_kv_heads * head_dim)),
        "wo": normal_init(ko, (n_heads * head_dim, d_model), fan_in=n_heads * head_dim),
    }


def _split_heads(x: jnp.ndarray, n_heads: int) -> jnp.ndarray:
    b, s, _ = x.shape
    return x.reshape(b, s, n_heads, -1)


def _merge_heads(x: jnp.ndarray) -> jnp.ndarray:
    b, s, h, d = x.shape
    return x.reshape(b, s, h * d)


def _mask(q_pos, k_pos, causal: bool, window: int, q_seg=None, k_seg=None):
    """q_pos: (B, Sq); k_pos: (B, Skv); optional segment ids of the same
    shapes (None = no segment gating, e.g. decode over a cache or
    cross-attention — deliberately unlike ref.attention_mask, which derives
    segments from explicit positions).  Returns bool (B, Sq, Skv).

    The packed-position rule itself lives in ref.attention_mask / kernel
    tile_mask; with segments supplied this must match them term for term —
    pinned by tests/test_models.py::test_mask_matches_ref_contract."""
    qp = q_pos[:, :, None]
    kp = k_pos[:, None, :]
    m = (kp >= 0) & (qp >= 0)
    if q_seg is not None:
        m &= q_seg[:, :, None] == k_seg[:, None, :]
    if causal:
        m &= kp <= qp
    if window > 0:
        m &= kp > qp - window
    return m


def _sdpa(q, k, v, mask) -> jnp.ndarray:
    """q: (B,Sq,K,G,D); k,v: (B,Skv,K,D); mask: (B,Sq,Skv) -> (B,Sq,K,G,D)."""
    scale = q.shape[-1] ** -0.5
    scores = jnp.einsum("bqkgd,bskd->bkgqs", q, k).astype(jnp.float32) * scale
    scores = jnp.where(mask[:, None, None, :, :], scores, NEG_INF)
    w = jax.nn.softmax(scores, axis=-1).astype(v.dtype)
    # rows with no valid kv (e.g. empty cache slots) emit exactly 0, matching
    # the flash-kernel convention, instead of a uniform average over kv
    w = jnp.where(mask.any(-1)[:, None, None, :, None], w, 0)
    return jnp.einsum("bkgqs,bskd->bqkgd", w, v)


def _chunked_sdpa(q, k, v, q_pos, k_pos, causal, window, q_chunk, kv_chunk,
                  q_seg=None, k_seg=None):
    """Online-softmax attention; same signature/result as _sdpa but O(chunk^2) memory.

    Outer scan over q chunks, inner scan over kv chunks carrying the running
    (max, denominator, accumulator) triple.  Segment ids (None = no segment
    gating) ride the same chunking as the positions.
    """
    b, sq, kh, g, d = q.shape
    skv = k.shape[1]
    # all-zero segments == no segment gating; keeps the scans uniform
    if q_seg is None:
        q_seg = jnp.zeros_like(q_pos)
        k_seg = jnp.zeros_like(k_pos)
    q_chunk = min(q_chunk, sq)
    kv_chunk = min(kv_chunk, skv)
    # pad to multiples
    pq = (-sq) % q_chunk
    pk = (-skv) % kv_chunk
    if pq:
        q = jnp.pad(q, ((0, 0), (0, pq), (0, 0), (0, 0), (0, 0)))
        q_pos = jnp.pad(q_pos, ((0, 0), (0, pq)), constant_values=-1)
        q_seg = jnp.pad(q_seg, ((0, 0), (0, pq)), constant_values=-1)
    if pk:
        k = jnp.pad(k, ((0, 0), (0, pk), (0, 0), (0, 0)))
        v = jnp.pad(v, ((0, 0), (0, pk), (0, 0), (0, 0)))
        k_pos = jnp.pad(k_pos, ((0, 0), (0, pk)), constant_values=-1)
        k_seg = jnp.pad(k_seg, ((0, 0), (0, pk)), constant_values=-2)
    nq, nk = q.shape[1] // q_chunk, k.shape[1] // kv_chunk
    scale = d**-0.5

    qs = q.reshape(b, nq, q_chunk, kh, g, d).transpose(1, 0, 2, 3, 4, 5)
    qps = q_pos.reshape(b, nq, q_chunk).transpose(1, 0, 2)
    qss = q_seg.reshape(b, nq, q_chunk).transpose(1, 0, 2)
    ks = k.reshape(b, nk, kv_chunk, kh, d).transpose(1, 0, 2, 3, 4)
    vs = v.reshape(b, nk, kv_chunk, kh, d).transpose(1, 0, 2, 3, 4)
    kps = k_pos.reshape(b, nk, kv_chunk).transpose(1, 0, 2)
    kss = k_seg.reshape(b, nk, kv_chunk).transpose(1, 0, 2)

    def q_step(_, qc):
        qb, qp, qg = qc  # (B,Cq,K,G,D), (B,Cq), (B,Cq)

        def kv_step(carry, kc):
            m_run, l_run, acc = carry
            kb, vb, kp, kg = kc
            s = jnp.einsum("bqkgd,bskd->bkgqs", qb, kb).astype(jnp.float32) * scale
            msk = _mask(qp, kp, causal, window, qg, kg)[:, None, None, :, :]
            s = jnp.where(msk, s, NEG_INF)
            m_new = jnp.maximum(m_run, jnp.max(s, axis=-1))
            # exact zeros off-mask (a fully-masked chunk has s == m == NEG_INF
            # everywhere, where exp(s - m) would be 1 and inflate l)
            p = jnp.where(msk, jnp.exp(s - m_new[..., None]), 0.0)
            corr = jnp.exp(m_run - m_new)
            l_new = l_run * corr + jnp.sum(p, axis=-1)
            acc = acc * corr[..., None] + jnp.einsum(
                "bkgqs,bskd->bkgqd", p.astype(vb.dtype), vb
            ).astype(jnp.float32)
            return (m_new, l_new, acc), None

        m0 = jnp.full((b, kh, g, q_chunk), NEG_INF, jnp.float32)
        l0 = jnp.zeros((b, kh, g, q_chunk), jnp.float32)
        a0 = jnp.zeros((b, kh, g, q_chunk, d), jnp.float32)
        (m_f, l_f, acc), _ = jax.lax.scan(kv_step, (m0, l0, a0), (ks, vs, kps, kss))
        # l == 0 means the whole row was masked: emit exact 0, not acc/eps
        out = jnp.where(l_f[..., None] > 0, acc / jnp.maximum(l_f, 1e-30)[..., None], 0.0)
        return None, out.transpose(0, 3, 1, 2, 4)  # (B,Cq,K,G,D)

    _, outs = jax.lax.scan(q_step, None, (qs, qps, qss))  # (nq,B,Cq,K,G,D)
    out = outs.transpose(1, 0, 2, 3, 4, 5).reshape(b, nq * q_chunk, kh, g, d)
    return out[:, :sq].astype(v.dtype)


def attention(
    p: Dict,
    x: jnp.ndarray,
    *,
    n_heads: int,
    n_kv_heads: int,
    head_dim: int,
    q_pos: jnp.ndarray,
    rope_theta: float = 0.0,
    rope_yarn=None,
    causal: bool = True,
    window: int = 0,
    memory: Optional[jnp.ndarray] = None,
    mem_pos: Optional[jnp.ndarray] = None,
    cache: Optional[Dict] = None,
    mode: str = "train",
    attn_chunk: int = 1024,
    cache_len: int = 0,
    backend: Optional[Backend] = None,
    implicit_layout: bool = False,
    q_seg: Optional[jnp.ndarray] = None,
    seg_base: Optional[jnp.ndarray] = None,
    use_pallas=None,
) -> Tuple[jnp.ndarray, Optional[Dict]]:
    """Self- or cross-attention.

    mode: "train" (no cache), "prefill" (builds a fresh cache, or APPENDS
    into an existing one when ``cache`` is passed), "decode" (consumes/
    returns cache; x is (B, L, d) — L lanes decode in lock-step per row).
    memory: (B, M, d) for cross-attention (causal/window ignored).
    rope_yarn: a YarnConfig scaling the RoPE frequencies (None: plain).
    q_pos: (B, S) int32 absolute positions; pos < 0 marks padding.  Packed
    and offset layouts are first-class everywhere: segment ids gate
    cross-document attention on the jnp paths AND the fused kernels, and
    the cache is paged by sequence index so packed documents never collide
    slots (module docstring).
    q_seg: (B, S) explicit segment ids; None derives them from q_pos
    (segment_ids_from_positions).  Decode MUST receive explicit segments
    when a row holds more than one document: derived ordinals from a (B, L)
    decode query stream cannot align with the cache's numbering.
    seg_base: (B,) int32 added to the (explicit or derived) segment ids —
    lets a prefill chunk continue a partially-used cache row's numbering.
    implicit_layout: static hint that q_pos is the plain broadcast
    arange(S).  Purely a fast path, NOT a correctness gate (explicit
    positions run fused regardless): it keeps the kernel on the free
    grid-index dead-tile predicate and skips the segment-id cumsum — the
    derived segments of an arange are identically zero.
    backend: the execution plan (repro.backend.Backend); its ``attention``
    subsystem selects the fused kernel vs the jnp paths.  The deprecated
    boolean keyword maps through the shim (warns once).
    Returns (out (B,S,d), cache or None).
    """
    bk = resolve_backend(backend, use_pallas=use_pallas, where="models.attention")
    b, s, _ = x.shape
    g = n_heads // n_kv_heads
    dtype = x.dtype
    cross = memory is not None

    # Segment ids for the query stream: explicit > derived-from-positions >
    # None (implicit arange / cross-attention — identically zero segments).
    # seg_base shifts them into the cache row's global numbering.
    if cross:
        seg_q = None  # cross-attention memory carries no packing structure
    elif q_seg is not None:
        seg_q = jnp.asarray(q_seg, jnp.int32)
    elif implicit_layout:
        seg_q = None
    else:
        seg_q = segment_ids_from_positions(q_pos)
    if seg_q is not None and seg_base is not None:
        seg_q = seg_q + jnp.asarray(seg_base, jnp.int32)[:, None]

    q = _split_heads(x @ p["wq"].astype(dtype), n_heads)  # (B,S,H,D)
    if cross:
        if mode == "decode" and cache is not None:
            k, v = cache["k"], cache["v"]
            k_pos = cache["kpos"]
            new_cache = cache
        else:
            src = memory.astype(dtype)
            k = _split_heads(src @ p["wk"].astype(dtype), n_kv_heads)
            v = _split_heads(src @ p["wv"].astype(dtype), n_kv_heads)
            k_pos = (
                mem_pos
                if mem_pos is not None
                else jnp.broadcast_to(jnp.arange(k.shape[1]), (b, k.shape[1]))
            )
            new_cache = {"k": k, "v": v, "kpos": k_pos} if mode == "prefill" else None
        causal, window = False, 0
    else:
        k = _split_heads(x @ p["wk"].astype(dtype), n_kv_heads)
        v = _split_heads(x @ p["wv"].astype(dtype), n_kv_heads)
        if rope_theta:
            q = apply_rope(q, q_pos, rope_theta, rope_yarn)
            k = apply_rope(k, q_pos, rope_theta, rope_yarn)
        if mode == "train":
            k_pos = q_pos
            new_cache = None
        else:
            fresh_cache = mode == "prefill" and cache is None
            c = cache_len if fresh_cache else cache["k"].shape[1]
            if fresh_cache:
                ck = jnp.zeros((b, c, n_kv_heads, head_dim), dtype)
                cv = jnp.zeros((b, c, n_kv_heads, head_dim), dtype)
                ckpos = jnp.full((b, c), -1, jnp.int32)
                ckseg = jnp.full((b, c), -1, jnp.int32)
                cfill = jnp.zeros((b,), jnp.int32)
            else:
                ck, cv, ckpos = cache["k"], cache["v"], cache["kpos"]
                ckseg, cfill = cache["kseg"], cache["fill"]
            # segment ids stored alongside the keys: pads keep -1 (they are
            # dropped below anyway)
            seg_in = seg_q if seg_q is not None else jnp.zeros_like(q_pos)
            # PAGED SLOTTING: a token's slot is its ARRIVAL index (the row's
            # fill cursor + its rank among this call's valid tokens), mod c —
            # NOT its position, which repeats across the documents of a
            # packed row and would collide slots.  Only the last <=c tokens
            # of an over-long prefill can survive the ring; slice them
            # statically so the scatter has no duplicate indices.
            if mode == "prefill" and s > c:
                k_in, v_in = k[:, -c:], v[:, -c:]
                pos_in, seg_w = q_pos[:, -c:], seg_in[:, -c:]
            else:
                k_in, v_in, pos_in, seg_w = k, v, q_pos, seg_in
            # pads (pos < 0) must NOT scatter or advance the cursor: route
            # them out of bounds and drop the write.
            valid = pos_in >= 0
            arrival = jnp.cumsum(valid.astype(jnp.int32), axis=1) - 1
            slot = jnp.where(valid, (cfill[:, None] + arrival) % c, c)
            bidx = jnp.arange(b)[:, None]
            ck = ck.at[bidx, slot].set(k_in, mode="drop")
            cv = cv.at[bidx, slot].set(v_in, mode="drop")
            ckpos = ckpos.at[bidx, slot].set(pos_in, mode="drop")
            ckseg = ckseg.at[bidx, slot].set(seg_w, mode="drop")
            cfill = cfill + jnp.sum(valid, axis=1, dtype=jnp.int32)
            new_cache = {"k": ck, "v": cv, "kpos": ckpos, "kseg": ckseg, "fill": cfill}
            if mode == "decode":
                k, v, k_pos = ck, cv, ckpos
            else:
                k_pos = q_pos  # prefill attends within the fresh sequence

    qh = q.reshape(b, s, n_kv_heads, g, head_dim)
    naive_elems = s * k.shape[1]
    # k-side segments: self train/prefill attend the fresh sequence against
    # itself (k side shares seg_q); decode gates against the cache's stored
    # kseg; cross-attention memory has no segments.  seg_q/seg_k are
    # both-None or both-arrays, matching the _mask contract.
    if cross:
        seg_k = None
    elif mode == "decode":
        if seg_q is None:  # implicit-layout decode: single segment 0
            seg_q = jnp.zeros_like(q_pos)
        seg_k = new_cache["kseg"]
    else:
        seg_k = seg_q
    self_fresh = not cross and mode in ("train", "prefill")
    if bk.fused("attention") and self_fresh and k.shape[1] == s:
        # Fused path for train AND prefill: the kernel carries a custom VJP
        # (fused dq and dk/dv Pallas kernels), so the training forward and
        # backward both stay on Pallas.  The kernel takes the positions and
        # segment ids as operands — packed/offset layouts run fused too.
        # The implicit layout passes NO positions: the kernel materializes
        # the arange itself and keeps the static grid-index dead-tile skip.
        from repro.kernels import ops as kops

        if implicit_layout:
            out = kops.flash_attention(qh, k, v, causal=causal, window=window,
                                       backend=bk)
        else:
            out = kops.flash_attention(
                qh, k, v, q_pos, k_pos, q_seg=seg_q, k_seg=seg_k,
                causal=causal, window=window, backend=bk,
            )
    elif bk.fused("attention") and cross and mode in ("train", "prefill"):
        # Fused cross-attention (train/prefill): the same Sq != Skv kernel
        # with fully explicit operands (M pads up to the kv block size).
        # Segments are EXPLICIT ZEROS on both sides — cross-attention has no
        # segment gating (_mask passes seg None), so letting the kernel
        # derive them (q from a packed q_pos, k from a mem_pos) would
        # mis-gate valid q->memory pairs; only pos >= 0 validity masking
        # applies.  Grads flow to q AND the memory projections through the
        # kernel's fused one-pass backward.  Cross DECODE stays on the jnp
        # paths: its kv comes from the ragged prefill cache.
        from repro.kernels import ops as kops

        out = kops.flash_attention(
            qh, k, v, q_pos, k_pos,
            q_seg=jnp.zeros_like(q_pos), k_seg=jnp.zeros_like(k_pos),
            causal=False, window=0, backend=bk,
        )
    elif bk.fused("attention") and not cross and mode == "decode":
        # Fused decode: forward-only flash kernel over the paged cache with
        # fully explicit positions/segments on both sides (Sq = lanes,
        # Skv = cache_len).  Closes the "decode stays on jnp" gap.
        from repro.kernels import ops as kops

        out = kops.flash_decode(qh, k, v, q_pos, k_pos, seg_q, seg_k,
                                causal=causal, window=window, backend=bk)
    elif attn_chunk and naive_elems > attn_chunk * attn_chunk * 4:
        out = _chunked_sdpa(qh, k, v, q_pos, k_pos, causal, window, attn_chunk,
                            attn_chunk, q_seg=seg_q, k_seg=seg_k)
    else:
        mask = _mask(q_pos, k_pos, causal, window, seg_q, seg_k)
        out = _sdpa(qh, k, v, mask)  # (B,Sq,K,G,D)
    out = _merge_heads(out.reshape(b, s, n_heads, head_dim))
    return out @ p["wo"].astype(dtype), new_cache


def self_cache_shape(batch: int, cache_len: int, n_kv_heads: int, head_dim: int, dtype):
    """ShapeDtypeStruct pytree for a self-attention cache (dry-run friendly)."""
    return {
        "k": jax.ShapeDtypeStruct((batch, cache_len, n_kv_heads, head_dim), dtype),
        "v": jax.ShapeDtypeStruct((batch, cache_len, n_kv_heads, head_dim), dtype),
        "kpos": jax.ShapeDtypeStruct((batch, cache_len), jnp.int32),
        "kseg": jax.ShapeDtypeStruct((batch, cache_len), jnp.int32),
        "fill": jax.ShapeDtypeStruct((batch,), jnp.int32),
    }
