"""Pure-jnp oracles for every Pallas kernel (the allclose targets)."""
from __future__ import annotations

import jax.numpy as jnp


def vr_scale_ref(g: jnp.ndarray, g2: jnp.ndarray, gamma: float, eps: float, g_apply=None):
    """GSNR pipeline on one tensor: returns (scaled_grad, r_clipped).

    var -> r -> normalize by mean(r) -> clip [gamma, 1] -> r * g_apply.
    g_apply defaults to g; it differs when global grad-clip rescaled the
    gradient entering the update (r always derives from the raw moments).
    """
    ga = (g if g_apply is None else g_apply).astype(jnp.float32)
    g = g.astype(jnp.float32)
    var = jnp.maximum(g2.astype(jnp.float32) - jnp.square(g), 0.0)
    r = jnp.square(g) / (var + eps)
    r = r / jnp.maximum(jnp.mean(r), 1e-30)
    r = jnp.clip(r, gamma, 1.0)
    return r * ga, r


def vr_adam_inner_ref(
    g, g2, m, v, p, *, b1, b2, b3, eps, gamma, gsnr_eps, bc1, bc2, bc3, g_apply=None
):
    """Fused VR-Adam inner step on one tensor (paper Alg. 3 lines 8-17).

    Returns (direction, m', v', p').  bcN = 1 - betaN**t.
    """
    ga = (g if g_apply is None else g_apply).astype(jnp.float32)
    m, v, p = (x.astype(jnp.float32) for x in (m, v, p))
    _, r = vr_scale_ref(g, g2, gamma, gsnr_eps)
    p_new = b3 * p + (1 - b3) * r
    ghat = (p_new / bc3) * ga
    m_new = b1 * m + (1 - b1) * ghat
    v_new = b2 * v + (1 - b2) * jnp.square(ghat)
    direction = (m_new / bc1) / (jnp.sqrt(v_new / bc2) + eps)
    return direction, m_new, v_new, p_new


def vr_lamb_inner_ref(
    g, ga, g2, m, v, p, w, *, b1, b2, b3, eps, wd, gamma, gsnr_eps, bc1, bc2, bc3
):
    """Fused VR-LAMB step on one tensor (paper Alg. 5): the VR-Adam direction
    plus the pre-trust-ratio update u = dir + wd*w and the exact norm sums.

    Returns (u, m', v', p', sum(u²), sum(w²)).
    """
    direction, m_new, v_new, p_new = vr_adam_inner_ref(
        g, g2, m, v, p, b1=b1, b2=b2, b3=b3, eps=eps, gamma=gamma,
        gsnr_eps=gsnr_eps, bc1=bc1, bc2=bc2, bc3=bc3, g_apply=ga,
    )
    w = w.astype(jnp.float32)
    u = direction + wd * w
    return u, m_new, v_new, p_new, jnp.sum(u * u), jnp.sum(w * w)


def vr_lars_inner_ref(g, ga, g2, w, *, wd, gamma, eps):
    """Fused VR-LARS scale on one tensor (§4.2): u = r*ga + wd*w plus the
    exact norm sums.  Returns (u, sum(u²), sum(w²))."""
    sg, _ = vr_scale_ref(g, g2, gamma, eps, g_apply=ga)
    w = w.astype(jnp.float32)
    u = sg + wd * w
    return u, jnp.sum(u * u), jnp.sum(w * w)


def moments_accum_ref(g_sum, g2_sum, g):
    """Scan-body moment update on one leaf: (g_sum + g, g2_sum + g²) in f32."""
    g = g.astype(jnp.float32)
    return g_sum + g, g2_sum + jnp.square(g)


def moments_finalize_ref(g_sum, g2_sum, k):
    """Terminal /k normalize of both accumulated moments."""
    inv = 1.0 / jnp.asarray(k, jnp.float32)
    return g_sum * inv, g2_sum * inv


def pack_square_ref(gf):
    """(rows, LANE) flat gradient -> (2, rows, LANE) stacked [g; g²] f32
    payload — the collective-shaped carry of the data-parallel stats pmean."""
    g = gf.astype(jnp.float32)
    return jnp.stack([g, jnp.square(g)])


def gsnr_r_raw_ref(g, g2, eps):
    """Raw (un-normalized) GSNR ratio r on one tensor — the quantity the
    per-leaf partial sums accumulate before the cross-shard mean."""
    g = g.astype(jnp.float32)
    var = jnp.maximum(g2.astype(jnp.float32) - jnp.square(g), 0.0)
    return jnp.square(g) / (var + eps)


def attention_mask_2d(sq: int, skv: int, causal: bool, window: int, q_offset: int = 0):
    """(Sq, Skv) implicit-position validity mask shared by the jnp attention
    references (q_pos = q_offset + arange(Sq), k_pos = arange(Skv))."""
    qpos = q_offset + jnp.arange(sq)[:, None]
    kpos = jnp.arange(skv)[None, :]
    mask = jnp.ones((sq, skv), bool)
    if causal:
        mask &= kpos <= qpos
    if window > 0:
        mask &= kpos > qpos - window
    return mask


def attention_mask(
    sq: int, skv: int, causal: bool, window: int, q_offset: int = 0,
    q_pos=None, k_pos=None, q_seg=None, k_seg=None,
):
    """(B | 1, Sq, Skv) validity mask — THE jnp home of the packed-position
    masking contract (kernels/flash_attention.py implements the same rule
    tile-wise).

    Implicit layout (q_pos None): q_offset + arange(Sq) vs arange(Skv), one
    segment.  Explicit layout: per-batch (B, S) int32 positions where pos < 0
    marks padding, and segment ids (derived from positions when not given)
    gate cross-document pairs with ``q_seg == k_seg``.
    """
    if q_pos is None:
        return attention_mask_2d(sq, skv, causal, window, q_offset)[None]
    if q_offset:
        raise ValueError(
            "attention_mask: q_offset is the IMPLICIT-layout parameter and is "
            "ignored under explicit q_pos — fold the offset into q_pos instead"
        )
    from repro.kernels.flash_attention import segment_ids_from_positions

    q_pos = jnp.asarray(q_pos, jnp.int32).reshape(-1, sq)
    k_pos = jnp.asarray(k_pos, jnp.int32).reshape(-1, skv)
    if q_seg is None:
        q_seg = segment_ids_from_positions(q_pos)
    if k_seg is None:
        k_seg = segment_ids_from_positions(k_pos)
    qp, kp = q_pos[:, :, None], k_pos[:, None, :]
    mask = (qp >= 0) & (kp >= 0)
    mask &= jnp.asarray(q_seg)[:, :, None] == jnp.asarray(k_seg)[:, None, :]
    if causal:
        mask &= kp <= qp
    if window > 0:
        mask &= kp > qp - window
    return mask


def attention_fwd_ref(
    q, k, v, *, causal: bool, window: int = 0, q_offset: int = 0,
    q_pos=None, k_pos=None, q_seg=None, k_seg=None,
):
    """Naive attention oracle with the flash-kernel residual contract:
    returns (out (B,Sq,H,D), lse (B,H,Sq) f32).  GQA by h//g.

    Positions default to the implicit layout (q_offset + arange); explicit
    q_pos/k_pos (+ optional segment ids) follow the packed-position contract
    of attention_mask.  A query row with no valid kv position yields exactly
    0 output and lse = -1e30 (the flash-kernel convention), not the uniform
    average a clamped softmax would produce.  This is THE jnp attention
    reference — the second-order VJP fallback in kernels/flash_attention.py
    uses it too, so the masking convention has a single jnp home.

    BACKWARD ORACLE CONTRACT: the fused one-pass dq/dk/dv kernel
    (kernels/flash_attention_bwd.py) is certified against ``jax.grad`` of
    THIS function (and its explicit replica attention_bwd_ref lives next to
    the kernel).  Because the kernel recomputes p from the forward's lse
    residual, any change to the masking/lse conventions here silently
    changes the gradients the kernel must reproduce — keep the two in
    lockstep (tests/test_oracle.py pins the full hostile grid).
    """
    b, sq, h, d = q.shape
    kvh = k.shape[2]
    g = h // kvh
    qh = q.reshape(b, sq, kvh, g, d)
    scale = d**-0.5
    s = jnp.einsum("bqkgd,bskd->bkgqs", qh.astype(jnp.float32), k.astype(jnp.float32)) * scale
    mask = attention_mask(
        sq, k.shape[1], causal, window, q_offset,
        q_pos=q_pos, k_pos=k_pos, q_seg=q_seg, k_seg=k_seg,
    )[:, None, None]  # (B | 1, 1, 1, Sq, Skv)
    s = jnp.where(mask, s, -1e30)
    m = jnp.max(s, axis=-1)
    p = jnp.where(mask, jnp.exp(s - m[..., None]), 0.0)
    l = jnp.sum(p, axis=-1)
    acc = jnp.einsum("bkgqs,bskd->bkgqd", p, v.astype(jnp.float32))
    valid = l > 0.0
    out = jnp.where(valid[..., None], acc / jnp.maximum(l, 1e-30)[..., None], 0.0)
    lse = jnp.where(valid, m + jnp.log(jnp.maximum(l, 1e-30)), -1e30)
    out = out.transpose(0, 3, 1, 2, 4).reshape(b, sq, h, d).astype(q.dtype)
    return out, lse.reshape(b, h, sq)


def attention_ref(
    q, k, v, *, causal: bool, window: int = 0, q_offset: int = 0,
    q_pos=None, k_pos=None, q_seg=None, k_seg=None,
):
    """attention_fwd_ref's output without the LSE residual."""
    return attention_fwd_ref(
        q, k, v, causal=causal, window=window, q_offset=q_offset,
        q_pos=q_pos, k_pos=k_pos, q_seg=q_seg, k_seg=k_seg,
    )[0]


def decode_attention_ref(
    q, k, v, q_pos, k_pos, q_seg, k_seg, *, causal: bool = True, window: int = 0,
):
    """Paged-decode oracle: L query lanes against a C-slot paged cache.

    Slot order is arbitrary (arrival order, not position order) — the mask
    reads only the explicit per-slot (k_pos, k_seg) and per-lane
    (q_pos, q_seg), which is why this is just attention_ref with every
    operand explicit.  Idle lanes (q_pos < 0) and empty slots (k_pos < 0)
    are masked; a lane with no reachable slot emits exactly 0.  The allclose
    target for kernels/flash_decode.py.
    """
    return attention_ref(
        q, k, v, causal=causal, window=window,
        q_pos=q_pos, k_pos=k_pos, q_seg=q_seg, k_seg=k_seg,
    )
