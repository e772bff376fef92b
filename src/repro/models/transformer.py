"""Model assembly: decoder LMs, encoder-decoder (whisper), VLM cross-attn,
hybrid recurrent and xLSTM stacks — all driven by ModelConfig.block_pattern.

Layers are organized as `n_groups` repetitions of the pattern (scanned with
stacked params to keep HLO small and CPU compiles tractable) plus an unrolled
tail for remainders (e.g. recurrentgemma's 38 = 12*(rec,rec,local) + (rec,rec)).

Public API:
  init_params(cfg, key)                         -> params pytree
  forward(cfg, pcfg, params, tokens, ...)       -> (logits, aux, cache|None)
  decode_step(cfg, pcfg, params, cache, token, positions) -> (logits, cache)
  prefill(...)                                  -> (logits, cache)
  encode(cfg, pcfg, params, frames)             -> encoder memory (whisper)
  cache_shapes(cfg, pcfg, batch, cache_len)     -> ShapeDtypeStruct pytree
"""
from __future__ import annotations

import functools
from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp

from repro.backend import resolve_backend
from repro.configs.base import ModelConfig, ParallelismConfig
from repro.models import attention as attn_mod
from repro.models import moe as moe_mod
from repro.models import recurrent as rec_mod
from repro.models import xlstm as xl_mod
from repro.models.common import (
    apply_head,
    apply_norm,
    embed_tokens,
    embedding_init,
    head_init,
    norm_init,
    normal_init,
)
from repro.models.mlp import apply_mlp, mlp_init
from repro.sharding.rules import constrain

AUX_ZERO = {"moe_lb_loss": 0.0, "moe_z_loss": 0.0, "moe_util": 0.0}


def _aux_zero(cfg: ModelConfig):
    keys = list(AUX_ZERO)
    if cfg.moe is not None and cfg.moe.dropless:
        keys.append(moe_mod.MOE_ROWS)
    return {k: jnp.zeros((), jnp.float32) for k in keys}


# ---------------------------------------------------------------------------
# per-block init / apply
# ---------------------------------------------------------------------------


def _ffn_init(key, cfg: ModelConfig) -> Tuple[str, Dict]:
    if cfg.moe is not None:
        return "moe", moe_mod.moe_init(key, cfg.d_model, cfg.d_ff, cfg.act, cfg.moe)
    return "mlp", mlp_init(key, cfg.d_model, cfg.d_ff, cfg.act)


def _block_init(key, cfg: ModelConfig, kind: str) -> Dict:
    d, hd = cfg.d_model, cfg.resolved_head_dim
    ks = jax.random.split(key, 6)
    p: Dict[str, Any] = {"ln1": norm_init(ks[0], d, cfg.norm)}
    if kind in ("attn", "swa", "local", "xattn"):
        p["attn"] = attn_mod.attn_init(ks[1], d, cfg.n_heads, cfg.n_kv_heads, hd)
        if kind == "xattn":
            p["lnx"] = norm_init(ks[2], d, cfg.norm)
            p["xattn"] = attn_mod.attn_init(ks[3], d, cfg.n_heads, cfg.n_kv_heads, hd)
        p["ln2"] = norm_init(ks[4], d, cfg.norm)
        name, ffn = _ffn_init(ks[5], cfg)
        p[name] = ffn
    elif kind == "rec":
        p["rec"] = rec_mod.rglru_init(ks[1], d)
        p["ln2"] = norm_init(ks[2], d, cfg.norm)
        name, ffn = _ffn_init(ks[3], cfg)
        p[name] = ffn
    elif kind == "mlstm":
        p["mlstm"] = xl_mod.mlstm_init(ks[1], d, cfg.n_heads, cfg.qk_dim_factor)
    elif kind == "slstm":
        p["slstm"] = xl_mod.slstm_init(ks[1], d, cfg.n_heads)
    else:
        raise ValueError(f"unknown block kind {kind!r}")
    return p


def _block_apply(
    cfg: ModelConfig,
    pcfg: ParallelismConfig,
    kind: str,
    p: Dict,
    x: jnp.ndarray,
    *,
    q_pos: jnp.ndarray,
    memory: Optional[jnp.ndarray],
    cache: Optional[Dict],
    mode: str,
    cache_len: int,
    causal: bool,
    implicit_layout: bool,
    q_seg: Optional[jnp.ndarray] = None,
    seg_base: Optional[jnp.ndarray] = None,
) -> Tuple[jnp.ndarray, Optional[Dict], Dict]:
    aux = _aux_zero(cfg)
    new_cache: Optional[Dict] = None
    h = apply_norm(p["ln1"], x, cfg.norm)
    common = dict(
        n_heads=cfg.n_heads,
        n_kv_heads=cfg.n_kv_heads,
        head_dim=cfg.resolved_head_dim,
        q_pos=q_pos,
        mode=mode,
        attn_chunk=pcfg.attn_chunk,
        backend=resolve_backend(pcfg),
        implicit_layout=implicit_layout,
        q_seg=q_seg,
        seg_base=seg_base,
    )
    if kind in ("attn", "swa", "local", "xattn"):
        window = cfg.sliding_window if kind in ("swa", "local") else 0
        eff_cache_len = min(cache_len, window) if (window and cache_len) else cache_len
        out, c_self = attn_mod.attention(
            p["attn"],
            h,
            rope_theta=cfg.rope_theta,
            rope_yarn=cfg.rope_yarn if kind == "attn" else None,
            causal=causal,
            window=window,
            cache=None if cache is None else cache.get("self"),
            cache_len=eff_cache_len,
            **common,
        )
        x = x + out
        c_cross = None
        if kind == "xattn":
            hx = apply_norm(p["lnx"], x, cfg.norm)
            out, c_cross = attn_mod.attention(
                p["xattn"],
                hx,
                rope_theta=0.0,
                memory=memory,
                cache=None if cache is None else cache.get("cross"),
                **common,
            )
            x = x + out
        h2 = apply_norm(p["ln2"], x, cfg.norm)
        if "moe" in p:
            out, moe_aux = moe_mod.apply_moe(p["moe"], h2, cfg.act, cfg.moe,
                                             interpret=common["backend"].interpret)
            aux.update(moe_aux)
        else:
            out = apply_mlp(p["mlp"], h2, cfg.act)
        x = x + out
        if mode != "train":
            new_cache = {"self": c_self}
            if kind == "xattn":
                new_cache["cross"] = c_cross
    elif kind == "rec":
        out, c_rec = rec_mod.apply_rglru(p["rec"], h, cache=cache, mode=mode)
        x = x + out
        h2 = apply_norm(p["ln2"], x, cfg.norm)
        if "moe" in p:
            out, moe_aux = moe_mod.apply_moe(p["moe"], h2, cfg.act, cfg.moe)
            aux.update(moe_aux)
        else:
            out = apply_mlp(p["mlp"], h2, cfg.act)
        x = x + out
        new_cache = c_rec
    elif kind == "mlstm":
        out, new_cache = xl_mod.apply_mlstm(p["mlstm"], h, cfg.n_heads, cache=cache, mode=mode)
        x = x + out
    elif kind == "slstm":
        out, new_cache = xl_mod.apply_slstm(p["slstm"], h, cfg.n_heads, cache=cache, mode=mode)
        x = x + out
    x = constrain(x, ("batch", None, None))
    return x, new_cache, aux


# ---------------------------------------------------------------------------
# whole-model init
# ---------------------------------------------------------------------------


def init_params(cfg: ModelConfig, key, scan_layers: bool = True) -> Dict:
    pattern = cfg.block_pattern
    n_groups, tail = cfg.n_groups(), cfg.tail_kinds()
    k_embed, k_groups, k_tail, k_norm, k_head, k_enc, k_img = jax.random.split(key, 7)

    def group_init(gkey):
        gks = jax.random.split(gkey, len(pattern))
        return {f"pos{i}": _block_init(gks[i], cfg, kind) for i, kind in enumerate(pattern)}

    params: Dict[str, Any] = {"embed": embedding_init(k_embed, cfg.vocab_size, cfg.d_model)}
    if n_groups > 0:
        gkeys = jax.random.split(k_groups, n_groups)
        if scan_layers and n_groups > 1:
            params["groups"] = jax.vmap(group_init)(gkeys)
        else:
            params["groups"] = [group_init(k) for k in gkeys]
    tkeys = jax.random.split(k_tail, max(1, len(tail)))
    params["tail"] = [_block_init(tkeys[i], cfg, kind) for i, kind in enumerate(tail)]
    params["final_norm"] = norm_init(k_norm, cfg.d_model, cfg.norm)
    if not cfg.tie_embeddings:
        params.update(head_init(k_head, cfg.d_model, cfg.vocab_size))
    if cfg.encoder is not None:
        ekeys = jax.random.split(k_enc, cfg.encoder.n_layers + 1)
        params["encoder"] = {
            "layers": [_block_init(ekeys[i], cfg, "attn") for i in range(cfg.encoder.n_layers)],
            "final_norm": norm_init(ekeys[-1], cfg.d_model, cfg.norm),
        }
    if cfg.n_image_tokens:
        params["img_proj"] = normal_init(k_img, (cfg.d_model, cfg.d_model))
    return params


# ---------------------------------------------------------------------------
# forward
# ---------------------------------------------------------------------------


def encode(cfg: ModelConfig, pcfg: ParallelismConfig, params: Dict, frames: jnp.ndarray):
    """Whisper encoder over stubbed conv-frontend frame embeddings (B,F,d)."""
    x = frames.astype(jnp.dtype(pcfg.compute_dtype))
    b, f, _ = x.shape
    pos = jnp.broadcast_to(jnp.arange(f), (b, f))
    for lp in params["encoder"]["layers"]:
        x, _, _ = _block_apply(
            cfg, pcfg, "attn", lp, x, q_pos=pos, memory=None, cache=None, mode="train",
            cache_len=0, causal=False, implicit_layout=True,
        )
    return apply_norm(params["encoder"]["final_norm"], x, cfg.norm)


def _resolve_memory(cfg, pcfg, params, extra):
    if cfg.encoder is not None:
        if extra is None or "frames" not in extra:
            raise ValueError("enc-dec model needs extra={'frames': (B,F,d)}")
        return encode(cfg, pcfg, params, extra["frames"])
    if cfg.n_image_tokens:
        if extra is None or "image" not in extra:
            raise ValueError("vlm needs extra={'image': (B,N,d)}")
        img = extra["image"].astype(jnp.dtype(pcfg.compute_dtype))
        return img @ params["img_proj"].astype(img.dtype)
    return None


def forward(
    cfg: ModelConfig,
    pcfg: ParallelismConfig,
    params: Dict,
    tokens: jnp.ndarray,
    *,
    extra: Optional[Dict] = None,
    mode: str = "train",
    cache: Optional[Dict] = None,
    positions: Optional[jnp.ndarray] = None,
    segments: Optional[jnp.ndarray] = None,
    seg_base: Optional[jnp.ndarray] = None,
    cache_len: int = 0,
    last_only: bool = False,
    gather_idx: Optional[jnp.ndarray] = None,
) -> Tuple[jnp.ndarray, Dict, Optional[Dict]]:
    """segments: (B, S) explicit segment ids (None = derive from positions);
    seg_base: (B,) offset into a cache row's segment numbering; gather_idx:
    (B, L) per-row token indices to unembed (serving: each packed document's
    last token) — overrides last_only.  A cache passed with mode="prefill"
    is APPENDED to (paged scatter) instead of rebuilt."""
    pattern = cfg.block_pattern
    n_groups, tail = cfg.n_groups(), cfg.tail_kinds()
    dtype = jnp.dtype(pcfg.compute_dtype)
    b, s = tokens.shape
    # positions are first-class in train/prefill (the fused kernel takes
    # pos/segment operands), so explicit packed/offset layouts train fused.
    # implicit_layout is a static FAST-PATH hint (free grid-index dead-tile
    # predicate, no segment cumsum), not a dispatch gate like the retired
    # implicit_pos fallback.
    implicit_layout = positions is None
    if positions is None:
        q_pos = jnp.broadcast_to(jnp.arange(s, dtype=jnp.int32), (b, s))
    elif positions.ndim == 1:
        q_pos = positions[:, None] + jnp.arange(s, dtype=jnp.int32)[None, :]
    else:
        q_pos = positions

    memory = None
    if mode == "decode" and cache is not None and "memory" in cache:
        memory = cache["memory"]
    else:
        memory = _resolve_memory(cfg, pcfg, params, extra)

    x = embed_tokens(params["embed"], tokens, dtype)
    x = constrain(x, ("batch", None, None))
    aux_total = _aux_zero(cfg)

    def apply_one(kind, p, xx, blk_cache):
        return _block_apply(
            cfg, pcfg, kind, p, xx,
            q_pos=q_pos, memory=memory, cache=blk_cache, mode=mode,
            cache_len=cache_len, causal=cfg.causal,
            implicit_layout=implicit_layout,
            q_seg=segments, seg_base=seg_base,
        )

    remat = pcfg.remat and mode == "train"
    # layers outside the scan (an unscanned stack, the tail) are
    # rematerialized one block at a time, as the scan does one group
    apply_unscanned = jax.checkpoint(apply_one, static_argnums=(0,)) if remat else apply_one

    use_cache_in = cache is not None and mode in ("decode", "prefill")
    group_caches = None
    if n_groups > 0:
        gparams = params["groups"]
        scanned = not isinstance(gparams, (list, tuple))
        if scanned:

            def group_fn(carry, xs):
                xx, aux = carry
                gp, gc = xs
                new_gc = {}
                for i, kind in enumerate(pattern):
                    blk_c = None if gc is None else gc.get(f"pos{i}")
                    xx, nc, a = apply_one(kind, gp[f"pos{i}"], xx, blk_c)
                    aux = {k_: aux[k_] + a[k_] for k_ in aux}
                    new_gc[f"pos{i}"] = nc
                return (xx, aux), new_gc

            if remat:
                group_fn = jax.checkpoint(group_fn)
            gcache_in = cache["groups"] if use_cache_in else None
            if gcache_in is None:
                (x, aux_total), group_caches = jax.lax.scan(
                    lambda c, gp: group_fn(c, (gp, None)), (x, aux_total), gparams
                )
            else:
                (x, aux_total), group_caches = jax.lax.scan(
                    group_fn, (x, aux_total), (gparams, gcache_in)
                )
        else:
            group_caches = []
            for gi, gp in enumerate(gparams):
                new_gc = {}
                for i, kind in enumerate(pattern):
                    blk_c = cache["groups"][gi].get(f"pos{i}") if use_cache_in else None
                    x, nc, a = apply_unscanned(kind, gp[f"pos{i}"], x, blk_c)
                    aux_total = {k_: aux_total[k_] + a[k_] for k_ in aux_total}
                    new_gc[f"pos{i}"] = nc
                group_caches.append(new_gc)

    tail_caches = []
    for ti, kind in enumerate(tail):
        blk_c = cache["tail"][ti] if use_cache_in else None
        x, nc, a = apply_unscanned(kind, params["tail"][ti], x, blk_c)
        aux_total = {k_: aux_total[k_] + a[k_] for k_ in aux_total}
        tail_caches.append(nc)

    if gather_idx is not None:
        # serving prefill over a packed chunk: unembed each document's own
        # last token (one index per lane), not the row's last position
        x = jnp.take_along_axis(x, gather_idx.astype(jnp.int32)[:, :, None], axis=1)
    elif last_only:
        x = x[:, -1:]  # serving prefill: unembed only the last position
    x = apply_norm(params["final_norm"], x, cfg.norm)
    if cfg.tie_embeddings:
        logits = jnp.einsum(
            "...d,vd->...v", x.astype(jnp.float32), params["embed"]["embed"].astype(jnp.float32)
        )
    else:
        logits = apply_head(params, x, cfg.logit_softcap)

    n_layers = max(1, cfg.n_layers)
    # the routed-row count is summed over the layers, the losses averaged
    aux_total = {k_: v if k_ == moe_mod.MOE_ROWS else v / n_layers
                 for k_, v in aux_total.items()}
    out_cache = None
    if mode in ("prefill", "decode"):
        out_cache = {"groups": group_caches, "tail": tail_caches}
        if memory is not None:
            out_cache["memory"] = memory
    return logits, aux_total, out_cache


def prefill(cfg, pcfg, params, tokens, *, extra=None, cache_len: int, cache=None,
            positions=None, segments=None, seg_base=None, gather_idx=None):
    """Returns (logits, cache): logits are (B,1,V) last-position by default, or
    (B,L,V) at gather_idx (B,L) when given.  Passing an existing ``cache``
    appends this chunk into it (continuous batching) instead of building a
    fresh one."""
    logits, _aux, cache = forward(
        cfg, pcfg, params, tokens, extra=extra, mode="prefill", cache_len=cache_len,
        cache=cache, positions=positions, segments=segments, seg_base=seg_base,
        last_only=True, gather_idx=gather_idx,
    )
    return logits, cache


def decode_step(cfg, pcfg, params, cache, token, positions, segments=None):
    """token: (B, L) int32 (L lock-step lanes; classic decode is L=1);
    positions: (B,) or (B, L) int32 absolute position of each token, -1 for
    idle lanes; segments: optional (B,)/(B, L) row-global segment ids gating
    each lane to its own document in the shared cache row (None = segment 0,
    correct only for single-document rows)."""
    if token.ndim == 1:
        token = token[:, None]
    pos = positions if positions.ndim == 2 else positions[:, None]
    seg = None
    if segments is not None:
        seg = segments if segments.ndim == 2 else segments[:, None]
    logits, _aux, cache = forward(
        cfg, pcfg, params, token, mode="decode", cache=cache, positions=pos,
        segments=seg,
    )
    return logits, cache


def cache_shapes(cfg, pcfg, batch: int, prompt_len: int, cache_len: int, extra_shapes=None):
    """ShapeDtypeStruct pytree of the decode-input cache via abstract prefill."""
    tok = jax.ShapeDtypeStruct((batch, prompt_len), jnp.int32)

    def fn(p, tokens, ex):
        return prefill(cfg, pcfg, p, tokens, extra=ex, cache_len=cache_len)[1]

    return jax.eval_shape(fn, params_shapes(cfg, pcfg), tok, extra_shapes)


@functools.lru_cache(maxsize=32)
def _abstract_params(cfg: ModelConfig, scan_layers: bool):
    return jax.eval_shape(lambda k: init_params(cfg, k, scan_layers), jax.random.PRNGKey(0))


def params_shapes(cfg: ModelConfig, pcfg: ParallelismConfig):
    """Abstract params (ShapeDtypeStruct) — dry-run / analysis, no allocation."""
    return _abstract_params(cfg, pcfg.scan_layers)
