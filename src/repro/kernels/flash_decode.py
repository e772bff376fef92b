"""Fused decode attention over a paged KV cache (forward-only flash).

Decode is the last attention site that stayed on jnp: queries are a handful
of lanes per row (Sq = L, typically 1-4) attending a cache of C slots whose
order is ARRIVAL order, not position order.  The training flash kernel
already takes fully explicit (q_pos, k_pos, q_seg, k_seg) operands and its
``_load_pos_seg`` / ``tile_reachable`` machinery masks purely from those
values — slot order never enters the math — so decode reuses the same
``_fwd_call`` launcher with Sq != Skv and no LSE output (inference only, no
VJP; differentiating through this path raises).

EXPLICIT-SEGMENT CONTRACT: both q_seg and k_seg are REQUIRED here.  The
cache's kseg carries row-global segment numbering (models/attention.py) and
a decode query stream is a different position stream than the cache —
derived per-stream ordinals cannot align (resolve_positions docstring), so
there is no safe default to fall back to.

Mosaic checklist (compile-tested for v5e in tests/test_tpu_compile.py):
  * the kernels run head-major: q and the paged cache are transposed to
    (B, H, L, D) / (B, KV, C, D) here, so every tile's last two dims are
    (rows, D).  The cache transpose is a full copy per call — the price
    until the cache itself is stored head-major.
  * the min tile is DTYPE-DEPENDENT — (8, 128) for f32 but (16, 128) for
    bf16: the lane axis is the kernel's sublane axis, so L is padded up to
    the query dtype's sublane multiple (_sublane) with pos = -1 / seg = -1
    pad lanes (masked rows emit exact 0 and are sliced off).  A hard-coded
    8 would hand Mosaic a half-height bf16 q tile.
  * block_q covers the whole padded lane axis (one q tile per row); block_k
    tiles the cache, so dead cache tiles (kpos still -1 past the fill
    cursor) are skipped through the prefetched fetch map.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from repro.analysis.layout_contracts import LANE
from repro.analysis.layout_contracts import sublane as _sublane
from repro.backend import resolve_interpret
from repro.kernels.flash_attention import DEFAULT_BLOCK_K, _fwd_call, bhsd


@functools.partial(
    jax.jit, static_argnames=("causal", "window", "block_k", "interpret")
)
def flash_decode(
    q: jnp.ndarray,
    k: jnp.ndarray,
    v: jnp.ndarray,
    q_pos: jnp.ndarray,
    k_pos: jnp.ndarray,
    q_seg: jnp.ndarray,
    k_seg: jnp.ndarray,
    *,
    causal: bool = True,
    window: int = 0,
    block_k: int = DEFAULT_BLOCK_K,
    interpret: bool | None = None,
) -> jnp.ndarray:
    """q: (B,L,H,D) decode lanes; k,v: (B,C,KV,D) paged cache -> (B,L,H,D).

    q_pos/q_seg: (B, L) int32 per-lane absolute position / row-global
    segment id (-1 = idle lane, emits exact 0); k_pos/k_seg: (B, C) int32
    per-slot position / segment (-1 = empty slot).  All four are required —
    see the module docstring.  NOT differentiable (inference only).
    """
    if q_pos is None or k_pos is None or q_seg is None or k_seg is None:
        raise ValueError(
            "flash_decode: q_pos, k_pos, q_seg and k_seg are all required — "
            "cache slot order is arbitrary and cross-stream segment ordinals "
            "cannot be derived (see kernels/flash_decode.py docstring)"
        )
    b, l, h, d = q.shape
    skv = k.shape[1]
    sub = _sublane(q.dtype)
    lp = -(-l // sub) * sub
    pad = lp - l
    q_pos = jnp.broadcast_to(jnp.asarray(q_pos, jnp.int32), (b, l))
    q_seg = jnp.broadcast_to(jnp.asarray(q_seg, jnp.int32), (b, l))
    if pad:
        q = jnp.pad(q, ((0, 0), (0, pad), (0, 0), (0, 0)))
        q_pos = jnp.pad(q_pos, ((0, 0), (0, pad)), constant_values=-1)
        q_seg = jnp.pad(q_seg, ((0, 0), (0, pad)), constant_values=-1)
    k_pos = jnp.broadcast_to(jnp.asarray(k_pos, jnp.int32), (b, skv))
    k_seg = jnp.broadcast_to(jnp.asarray(k_seg, jnp.int32), (b, skv))
    out = _fwd_call(
        bhsd(q), bhsd(k), bhsd(v), q_pos, k_pos, q_seg, k_seg,
        causal=causal, window=window,
        block_q=lp, block_k=min(block_k, skv),
        interpret=resolve_interpret(interpret), with_lse=False, implicit=False,
    )[0]
    return bhsd(out)[:, :l]

# ---------------------------------------------------------------------------
# contract registration (repro.analysis): decode replayed over a synthetic
# paged cache — 2 interleaved segments in arrival order, a fill cursor with
# empty slots behind it, idle query lanes — at the dtype-derived lane
# padding (this geometry is exactly the PR-7 bf16 half-tile fix, now gated)
# ---------------------------------------------------------------------------


def _analysis_geometry(B, L, C, H, KV, D, *, dtype="float32",
                       block_k=DEFAULT_BLOCK_K):
    import numpy as np

    from repro.analysis.registry import FetchMap, Geometry, Operand
    from repro.kernels.flash_attention import fwd_geometry, kv_fetch_blocks

    lp = -(-L // _sublane(dtype)) * _sublane(dtype)
    bk = min(block_k, C)
    grid, _, nk, _, ins, outs, shapes = fwd_geometry(
        B, lp, H, D, C, KV, block_q=lp, block_k=bk, with_lse=False)

    # arrival-ordered cache: slots alternate between two segments up to the
    # fill cursor, then sit empty (pos/seg -1); queries are the next token
    # of each segment on the first lanes, idle (-1) lanes after
    fill = (2 * C) // 3
    kp = np.full((B, C), -1, np.int32)
    ks = np.full((B, C), -1, np.int32)
    kp[:, :fill] = np.arange(fill) // 2
    ks[:, :fill] = np.arange(fill) % 2
    qp = np.full((B, lp), -1, np.int32)
    qs = np.full((B, lp), -1, np.int32)
    n_live = min(L, 2)
    qp[:, :n_live] = fill // 2
    qs[:, :n_live] = np.arange(n_live)
    fetch, live = kv_fetch_blocks(
        jnp.asarray(qp), jnp.asarray(kp), jnp.asarray(qs), jnp.asarray(ks),
        causal=True, window=0, block_q=lp, block_k=bk)
    fetch, live = np.asarray(fetch), np.asarray(live)

    def op(name, spec):
        dt = "int32" if name.endswith(("_pos", "_seg")) else dtype
        return Operand(spec, shapes[name], dtype=dt)

    return Geometry(
        grid=grid,
        ins={n: op(n, s) for n, s in ins.items()},
        outs={n: op(n, s) for n, s in outs.items()},
        scratch_bytes=4 * (2 * lp * LANE + lp * D),
        extra=(fetch.reshape(-1),),
        fetch_maps={"kv": FetchMap(fetch, live=live, n_blocks=nk)},
    )


def _register():
    from repro.analysis.registry import register_kernel

    register_kernel(
        "flash_decode",
        module=__name__,
        oracle="decode_attention_ref",
        build=_analysis_geometry,
        configs={
            "representative": dict(B=4, L=4, C=256, H=8, KV=2, D=64),
            "hostile_bf16_lanes": dict(B=2, L=3, C=130, H=4, KV=2, D=32,
                                       dtype="bfloat16"),
        },
    )


_register()
