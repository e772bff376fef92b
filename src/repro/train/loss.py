"""Losses: next-token / MLM cross-entropy with MoE auxiliaries.

Packed batches (multiple documents per row, pads at position -1) support two
normalizations, selected by ``Config.loss_norm``:

  "token"     mean NLL over live tokens (the classic LM convention);
  "document"  every packed document contributes its OWN token-mean NLL with
              equal weight (BERT-pretraining per-sequence normalization) —
              a row packing one long and five short documents no longer lets
              the long one dominate the gradient.

Packed batches also report a ``pack_efficiency`` metric (live tokens / total
slots) so trainer logs surface how much compute the packer is actually
saving.
"""
from __future__ import annotations

from typing import Dict, Optional, Tuple

import jax
import jax.numpy as jnp

from repro import obs
from repro.configs.base import Config
from repro.models import forward


def _nll(logits: jnp.ndarray, targets: jnp.ndarray) -> jnp.ndarray:
    logits = logits.astype(jnp.float32)
    logz = jax.nn.logsumexp(logits, axis=-1)
    gold = jnp.take_along_axis(logits, targets[..., None], axis=-1)[..., 0]
    return logz - gold


def cross_entropy(logits: jnp.ndarray, targets: jnp.ndarray, mask: Optional[jnp.ndarray] = None):
    """logits (B,S,V) f32, targets (B,S) int32 -> scalar mean CE over mask."""
    nll = _nll(logits, targets)
    if mask is None:
        return jnp.mean(nll)
    m = mask.astype(jnp.float32)
    return jnp.sum(nll * m) / jnp.maximum(jnp.sum(m), 1.0)


def document_cross_entropy(
    logits: jnp.ndarray,
    targets: jnp.ndarray,
    segments: jnp.ndarray,
    mask: Optional[jnp.ndarray] = None,
):
    """Segment-weighted CE for packed rows: mean over documents of each
    document's token-mean NLL.

    segments: (B, S) int32 per-row document ids (repro.data.pack_sequences /
    segment_ids_from_positions); mask kills pads (and any segment whose
    tokens are all masked contributes nothing).  Documents are keyed by
    (row, segment): packing never merges documents across rows.
    """
    nll = _nll(logits, targets)
    b, s = targets.shape
    m = jnp.ones((b, s), jnp.float32) if mask is None else mask.astype(jnp.float32)
    # negative segment ids mark pads (pack_sequences emits -1 there): force
    # their weight to 0 — a pad id of -1 in row r would otherwise flatten to
    # key s*r - 1 and alias row r-1's last document
    m = m * (segments >= 0)
    # flatten (row, segment) -> one id space; segment ids are < S by
    # construction (each starts at a distinct token)
    key = (segments.astype(jnp.int32) + s * jnp.arange(b, dtype=jnp.int32)[:, None]).reshape(-1)
    doc_tok = jax.ops.segment_sum(m.reshape(-1), key, num_segments=b * s)
    doc_nll = jax.ops.segment_sum((nll * m).reshape(-1), key, num_segments=b * s)
    live = doc_tok > 0
    per_doc = jnp.where(live, doc_nll / jnp.maximum(doc_tok, 1.0), 0.0)
    return jnp.sum(per_doc) / jnp.maximum(jnp.sum(live.astype(jnp.float32)), 1.0)


def make_loss_fn(cfg: Config, with_aux: bool = True):
    """loss_fn(params, batch) -> (loss, metrics) for the trainer / grad_stats.

    batch: {"tokens": (B,S) int32, "targets": (B,S) int32, optional "mask",
            optional "positions" (B,S) int32 (packed/offset layouts — pads
            carry position -1 and should be masked out of the loss),
            optional "segments" (B,S) int32 (derived from positions when
            absent), optional "image" (B,N,d) / "frames" (B,F,d)}.
    """
    m, p = cfg.model, cfg.parallel
    loss_norm = getattr(cfg, "loss_norm", "token")
    if loss_norm not in ("token", "document"):
        raise ValueError(f"Config.loss_norm={loss_norm!r}: must be 'token' or 'document'")

    def loss(params, batch) -> Tuple[jnp.ndarray, Dict]:
        extra = {}
        if "image" in batch:
            extra["image"] = batch["image"]
        if "frames" in batch:
            extra["frames"] = batch["frames"]
        positions = batch.get("positions")
        logits, aux, _ = forward(
            m, p, params, batch["tokens"], extra=extra or None, mode="train",
            positions=positions,
        )
        mask = batch.get("mask")
        packed = positions is not None and positions.ndim == 2
        if mask is None and packed:
            # packed layouts mark pads with position -1; without an explicit
            # mask those slots must still not train against the pad-fill
            # targets (their logits are the zero-output attention rows)
            mask = positions >= 0
        if loss_norm == "document" and packed:
            segments = batch.get("segments")
            if segments is None:
                from repro.kernels.flash_attention import segment_ids_from_positions

                segments = segment_ids_from_positions(positions)
            ce = document_cross_entropy(logits, batch["targets"], segments, mask)
        else:
            ce = cross_entropy(logits, batch["targets"], mask)
        total = ce + aux["moe_lb_loss"] + aux["moe_z_loss"]
        metrics = {"ce": ce, **aux}
        if packed:
            # live tokens / total slots: how much of the batch the packer
            # actually fills (trainer logs surface it as pack_efficiency)
            metrics["pack_efficiency"] = jnp.mean((positions >= 0).astype(jnp.float32))
        if not with_aux:
            return total
        return total, metrics

    def loss_fn(params, batch):
        with obs.scope(obs.MODEL):
            return loss(params, batch)

    return loss_fn
