"""The least work a training step needs, counted from shapes and from the
batch's documents, never from how a kernel tiles it.  So no implementation
can read over 100% of a roofline, and a kernel that skips dead tiles or
fuses a pass is credited.

What depends on the model's shape is counted by its family
(``families/<family>.py``); what a step of any family needs, the statistics
and the update over P parameters, is counted here.  A live token is a
non-pad slot (position >= 0); a live pair is a (query, key) pair of live
tokens in the same document, with key <= query where the model is causal.
"""
from __future__ import annotations

from typing import Dict

import numpy as np

from benchmarks.chip.spec import family
from benchmarks.chip.weights import leaf_shapes


def param_count(conf: Dict) -> int:
    return int(sum(np.prod(s) for s in leaf_shapes(conf).values()))


def live_pairs(piece_lens: np.ndarray, causal: bool) -> int:
    """Live (query, key) pairs of documents (pieces) of these lengths."""
    n = piece_lens.astype(np.int64)
    return int(np.sum(n * (n + 1) // 2 if causal else n * n))


def pairs(conf: Dict, pieces: np.ndarray):
    """What the family's attention counts take for a step's document pieces
    of these lengths: the dense family's is one count of live pairs, a
    family with layers of several kinds may count them per kind."""
    return family(conf).pairs(conf, pieces)


def matmul_params(conf: Dict) -> int:
    """Weights a live token multiplies by (the family's count)."""
    return family(conf).matmul_params(conf)


def model_flops(conf: Dict, live_tokens: int, pairs) -> float:
    """FLOPs of a step's forward and backward, no recompute (the family's
    count)."""
    return family(conf).model_flops(conf, live_tokens, pairs)


def attention_fwd(conf: Dict, live_tokens: int, pairs, itemsize: int):
    """(FLOPs, bytes) of the attention forward over all layers (the
    family's count)."""
    return family(conf).attention_fwd(conf, live_tokens, pairs, itemsize)


def attention_bwd(conf: Dict, live_tokens: int, pairs, itemsize: int):
    """(FLOPs, bytes) of the attention backward over all layers (the
    family's count)."""
    return family(conf).attention_bwd(conf, live_tokens, pairs, itemsize)


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
