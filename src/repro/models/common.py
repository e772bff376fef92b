"""Shared model primitives: initializers, norms, RoPE, embeddings, tree utils.

Parameter convention: params are nested dicts of jnp arrays.  Sharding is
derived from *leaf names* (see sharding/rules.py); the names used across the
model zoo are a closed vocabulary:

  wq wk wv wo            attention projections
  wi wg wd               MLP in / gate / down
  embed head             token embedding / unembedding
  scale bias             norm affine / biases
  router expert_wi expert_wg expert_wd   MoE
  img_proj               VLM projector
  conv_w a_log w_rg_a w_rg_x w_in w_gate  RG-LRU block
  (xLSTM names in models/xlstm.py docstring)

Stacked-scan leaves carry one extra leading "layers" dim; rules detect this
by ndim.
"""
from __future__ import annotations

import math
from typing import Any, Dict, Tuple

import jax
import jax.numpy as jnp
import numpy as np

PyTree = Any


# ---------------------------------------------------------------------------
# init helpers
# ---------------------------------------------------------------------------


def normal_init(key, shape, fan_in=None, dtype=jnp.float32):
    fan_in = fan_in if fan_in is not None else shape[-2] if len(shape) >= 2 else shape[-1]
    std = 1.0 / math.sqrt(max(1, fan_in))
    return (jax.random.normal(key, shape) * std).astype(dtype)


def zeros_init(_key, shape, dtype=jnp.float32):
    return jnp.zeros(shape, dtype)


def ones_init(_key, shape, dtype=jnp.float32):
    return jnp.ones(shape, dtype)


def count_params(tree: PyTree) -> int:
    return sum(int(x.size) for x in jax.tree_util.tree_leaves(tree))


def tree_cast(tree: PyTree, dtype) -> PyTree:
    return jax.tree_util.tree_map(lambda x: x.astype(dtype), tree)


def global_norm(tree: PyTree) -> jnp.ndarray:
    return jnp.sqrt(
        sum(jnp.sum(jnp.square(x.astype(jnp.float32))) for x in jax.tree_util.tree_leaves(tree))
    )


# ---------------------------------------------------------------------------
# norms
# ---------------------------------------------------------------------------


def norm_init(key, d: int, kind: str) -> Dict:
    del key
    if kind == "rmsnorm":
        return {"scale": jnp.ones((d,), jnp.float32)}
    return {"scale": jnp.ones((d,), jnp.float32), "bias": jnp.zeros((d,), jnp.float32)}


def apply_norm(p: Dict, x: jnp.ndarray, kind: str, eps: float = 1e-6) -> jnp.ndarray:
    xf = x.astype(jnp.float32)
    if kind == "rmsnorm":
        ms = jnp.mean(jnp.square(xf), axis=-1, keepdims=True)
        out = xf * jax.lax.rsqrt(ms + eps) * p["scale"]
    else:
        mu = jnp.mean(xf, axis=-1, keepdims=True)
        var = jnp.var(xf, axis=-1, keepdims=True)
        out = (xf - mu) * jax.lax.rsqrt(var + eps) * p["scale"] + p["bias"]
    return out.astype(x.dtype)


def group_norm(x: jnp.ndarray, n_groups: int, eps: float = 1e-6) -> jnp.ndarray:
    """Head-wise group norm used by xLSTM cells: x (..., H, D) normalized over D."""
    del n_groups
    xf = x.astype(jnp.float32)
    mu = jnp.mean(xf, axis=-1, keepdims=True)
    var = jnp.var(xf, axis=-1, keepdims=True)
    return ((xf - mu) * jax.lax.rsqrt(var + eps)).astype(x.dtype)


# ---------------------------------------------------------------------------
# rotary position embeddings
# ---------------------------------------------------------------------------


def rope_freqs(head_dim: int, theta: float) -> jnp.ndarray:
    return 1.0 / (theta ** (jnp.arange(0, head_dim, 2, dtype=jnp.float32) / head_dim))


def yarn_freqs(head_dim: int, theta: float, yarn) -> Tuple[np.ndarray, float]:
    """YaRN's frequencies and the factor on cos and sin (HF
    ``_compute_yarn_parameters``): the dims below the correction range keep
    their frequencies (extrapolation), those above it take them divided by
    ``factor`` (interpolation), a linear ramp between.  ``yarn``: a
    YarnConfig."""

    def correction_dim(rotations: float) -> float:
        return (head_dim * math.log(yarn.original_max_positions / (rotations * 2 * math.pi))
                / (2 * math.log(theta)))

    low = max(math.floor(correction_dim(yarn.beta_fast)), 0)
    high = min(math.ceil(correction_dim(yarn.beta_slow)), head_dim - 1)
    pos_freqs = theta ** (np.arange(0, head_dim, 2, dtype=np.float32) / head_dim)
    ramp = np.clip((np.arange(head_dim // 2, dtype=np.float32) - low)
                   / max(high - low, 1e-3), 0.0, 1.0)
    inv = (1.0 / (yarn.factor * pos_freqs)) * ramp + (1.0 / pos_freqs) * (1.0 - ramp)
    return inv.astype(np.float32), float(yarn.attention_factor)


def apply_rope(x: jnp.ndarray, positions: jnp.ndarray, theta: float, yarn=None) -> jnp.ndarray:
    """x: (B, S, H, D); positions: (B, S) int32; ``yarn`` (a YarnConfig)
    scales the frequencies and cos and sin."""
    d = x.shape[-1]
    if yarn is None:
        freqs = rope_freqs(d, theta)  # (D/2,)
    else:
        freqs, attention_factor = yarn_freqs(d, theta, yarn)
    angles = positions[..., None].astype(jnp.float32) * freqs  # (B, S, D/2)
    cos = jnp.cos(angles)[:, :, None, :]
    sin = jnp.sin(angles)[:, :, None, :]
    if yarn is not None:
        cos, sin = cos * attention_factor, sin * attention_factor
    x1, x2 = jnp.split(x.astype(jnp.float32), 2, axis=-1)
    out = jnp.concatenate([x1 * cos - x2 * sin, x1 * sin + x2 * cos], axis=-1)
    return out.astype(x.dtype)


# ---------------------------------------------------------------------------
# embeddings
# ---------------------------------------------------------------------------


def embedding_init(key, vocab: int, d: int) -> Dict:
    return {"embed": normal_init(key, (vocab, d), fan_in=d)}


def embed_tokens(p: Dict, tokens: jnp.ndarray, dtype) -> jnp.ndarray:
    return p["embed"].astype(dtype)[tokens]


def head_init(key, d: int, vocab: int) -> Dict:
    return {"head": normal_init(key, (d, vocab), fan_in=d)}


def apply_head(p: Dict, x: jnp.ndarray, softcap: float = 0.0) -> jnp.ndarray:
    logits = jnp.einsum("...d,dv->...v", x.astype(jnp.float32), p["head"].astype(jnp.float32))
    if softcap > 0:
        logits = jnp.tanh(logits / softcap) * softcap
    return logits
