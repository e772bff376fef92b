"""Weights from ``--seed``, in the benchmark's own layout, made on the device
in one jitted call.

The layout is a flat dict of named arrays, the family's
(``families/<family>.py``: ``leaf_shapes``).  Layer weights are stacked over
the layers under ``layers.<name>`` (leading axis L), which is how the
program groups its parameter tensors too; the rest are ``embed``,
``final.scale``/``final.bias`` and, for an untied head, ``head``.  Matrices
are N(0, 1/fan_in), norm scales 1 and norm biases 0.  The program is handed
these arrays and the reference makes them again from the same seed with the
same function, so both start from the same bits.
"""
from __future__ import annotations

import functools
import json
from typing import Dict, Tuple

import jax
import jax.numpy as jnp

from benchmarks.chip.spec import family


def leaf_shapes(conf: Dict) -> Dict[str, Tuple[int, ...]]:
    """Name -> shape of every parameter tensor, as the configuration's
    family lays them out."""
    return family(conf).leaf_shapes(conf)


def seed_key(seed: int):
    """A PRNG key that keeps all the bits of a seed wider than 32."""
    return jax.random.fold_in(jax.random.PRNGKey(seed & 0xFFFFFFFF), seed >> 32)


def _init(name: str, shape, key):
    if name.endswith("_scale") or name == "final.scale":
        return jnp.ones(shape, jnp.float32)
    if name.endswith("_bias") or name == "final.bias":
        return jnp.zeros(shape, jnp.float32)
    fan_in = shape[-1] if name == "embed" else shape[-2]
    return jax.random.normal(key, shape, jnp.float32) / jnp.sqrt(jnp.float32(fan_in))


@functools.lru_cache(maxsize=None)
def _builder(conf_json: str):
    shapes = leaf_shapes(json.loads(conf_json))

    def build(key):
        return {name: _init(name, shapes[name], jax.random.fold_in(key, i))
                for i, name in enumerate(sorted(shapes))}

    return jax.jit(build)


def make_params(conf: Dict, seed: int) -> Dict[str, jax.Array]:
    """Every parameter, float32, from one jitted call."""
    return _builder(json.dumps(conf, sort_keys=True))(seed_key(seed))


def leaf_norms(params: Dict[str, jax.Array]) -> Dict[str, jax.Array]:
    """L2 norm of every tensor; stacked layer tensors give one per layer."""
    out = {}
    for name, x in params.items():
        x = x.astype(jnp.float32)
        if name.startswith("layers."):
            out[name] = jnp.sqrt(jnp.sum(jnp.square(x), axis=tuple(range(1, x.ndim))))
        else:
            out[name] = jnp.sqrt(jnp.sum(jnp.square(x)))
    return out


def per_layer(norms: Dict) -> Dict[str, float]:
    """Host dict ``name`` / ``name[i]`` -> float from ``leaf_norms`` output."""
    out = {}
    for name, v in norms.items():
        v = jax.device_get(v)
        if getattr(v, "ndim", 0):
            out.update({f"{name}[{i}]": float(x) for i, x in enumerate(v)})
        else:
            out[name] = float(v)
    return out
