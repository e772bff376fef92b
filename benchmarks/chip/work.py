"""The least work a training step needs, counted from shapes and from the
batch's documents, never from how a kernel tiles it.  So no implementation
can read over 100% of a roofline, and a kernel that skips dead tiles or
fuses a pass is credited.

Notation: L layers, D model width, H query and KV key/value heads of width
hd, F MLP width, V vocabulary, P parameters.  A live token is a non-pad
slot (position >= 0); a live pair is a (query, key) pair of live tokens in
the same document, with key <= query where the model is causal.
"""
from __future__ import annotations

from typing import Dict

import numpy as np

from benchmarks.chip.weights import dims, leaf_shapes


def param_count(conf: Dict) -> int:
    return int(sum(np.prod(s) for s in leaf_shapes(conf).values()))


def matmul_params(conf: Dict) -> int:
    """Weights a token multiplies by: per layer the q, k, v, o projections
    and the MLP (two or three matrices); the vocabulary projection once,
    whether tied to the embedding or not.  The embedding gather is no
    matmul."""
    n = dims(conf)
    q, kv = n["H"] * n["hd"], n["KV"] * n["hd"]
    mlp = (3 if conf["mlp"] == "gated" else 2) * n["D"] * n["F"]
    return n["L"] * (2 * n["D"] * q + 2 * n["D"] * kv + mlp) + n["D"] * n["V"]


def live_pairs(piece_lens: np.ndarray, causal: bool) -> int:
    """Live (query, key) pairs of documents (pieces) of these lengths."""
    n = piece_lens.astype(np.int64)
    return int(np.sum(n * (n + 1) // 2 if causal else n * n))


def model_flops(conf: Dict, live_tokens: int, pairs: int) -> float:
    """Forward and backward, no recompute: 6 FLOPs per matmul weight per
    live token, and 12 * L * H * hd per live pair for the attention scores
    and their weighted sum (4 * hd per head and layer forward, twice that
    backward)."""
    n = dims(conf)
    return 6.0 * matmul_params(conf) * live_tokens + 12.0 * n["L"] * n["H"] * n["hd"] * pairs


def attention_fwd(conf: Dict, live_tokens: int, pairs: int, itemsize: int):
    """(FLOPs, bytes) of the attention forward over all layers: 4 * hd per
    head and live pair; q, k, v read and o written once per live token."""
    n = dims(conf)
    flops = 4.0 * n["hd"] * n["H"] * pairs * n["L"]
    nbytes = float(live_tokens) * n["L"] * (2 * n["H"] + 2 * n["KV"]) * n["hd"] * itemsize
    return flops, nbytes


def attention_bwd(conf: Dict, live_tokens: int, pairs: int, itemsize: int):
    """(FLOPs, bytes) of the attention backward over all layers: 8 * hd per
    head and live pair (the scores' and the weighted sum's gradients, no
    recompute); q, k, v, o, do read and dq, dk, dv written once."""
    n = dims(conf)
    flops = 8.0 * n["hd"] * n["H"] * pairs * n["L"]
    nbytes = float(live_tokens) * n["L"] * (4 * n["H"] + 4 * n["KV"]) * n["hd"] * itemsize
    return flops, nbytes


def stats_bytes(conf: Dict, k: int) -> float:
    """Gradient statistics of one step: each of the k microbatch gradients
    read once, mean and mean of squares written once, all float32."""
    return (k + 2) * 4.0 * param_count(conf)


def update_bytes(conf: Dict) -> float:
    """One VR-LAMB step, float32: read parameters, gradient mean, mean of
    squares, m, v and the GSNR momentum; write parameters, m, v and the
    GSNR momentum."""
    return 10 * 4.0 * param_count(conf)


def roofline_pct(flops: float, nbytes: float, seconds: float, peak: Dict[str, float]):
    """Least time at the chip's peaks over the measured time, in %."""
    if seconds <= 0:
        return None
    least = max(flops / peak["flops"], nbytes / peak["hbm_bytes_per_s"])
    return 100.0 * least / seconds
