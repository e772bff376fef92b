"""Divisibility-adaptive sharding rules.

Parameters get FSDP+TP by default: for every weight leaf the last dim maps to
the tensor-parallel axis ("model") and the second-to-last to the FSDP axis
("data"), *only when the dimension divides the axis size* — so whisper's 12
heads simply stay replicated on a 16-way model axis instead of erroring.
Expert leaves ("expert_*") prefer expert-parallelism (expert dim over
"model"); when the expert count does not divide (mixtral: 8 experts, 16-way
axis) they adaptively fall back to tensor-parallel inside each expert.

Activations are constrained at a few seams via ``constrain(x, logical_axes)``
with logical names resolved against the active mesh:

  batch      -> ("pod", "data") (whichever exist & divide)
  cache_seq  -> "data"  (sequence-parallel KV caches for tiny-batch decode)
  experts    -> "model" when divisible
  expert_cap -> "data"
  ff / heads -> "model"

``activate(mesh)`` installs rules process-wide (context manager); without an
active mesh every constraint is the identity, so single-device tests and CPU
benchmarks never touch device state.
"""
from __future__ import annotations

import contextlib
import dataclasses
from typing import Optional, Tuple

import jax
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from repro.core.layout import FlatBuffer, is_flat

_ACTIVE: Optional["Rules"] = None


@dataclasses.dataclass
class Rules:
    mesh: Mesh
    fsdp: bool = True
    tp_axis: str = "model"
    dp_axis: str = "data"
    pod_axis: str = "pod"
    # hillclimb knobs (see EXPERIMENTS.md §Perf)
    shard_cache_seq: bool = True
    cache_seq_tp: bool = True  # decode caches: seq dim over leftover axes (§Perf: 5.3x mem, 8x coll win)
    fsdp_over_pod: bool = False  # FSDP over ("pod","data") on multi-pod meshes

    # -- helpers ------------------------------------------------------------
    def axis_size(self, name: str) -> int:
        return dict(self.mesh.shape).get(name, 0)  # works for Mesh & AbstractMesh

    def has_axis(self, name: str) -> bool:
        return name in self.mesh.axis_names

    def fsdp_axes(self):
        if self.fsdp_over_pod and self.has_axis(self.pod_axis):
            return (self.pod_axis, self.dp_axis)
        return self.dp_axis

    def fits(self, dim: int, axis) -> bool:
        if axis is None:
            return False
        axes = (axis,) if isinstance(axis, str) else tuple(axis)
        total = 1
        for a in axes:
            if not self.has_axis(a):
                return False
            total *= self.axis_size(a)
        return dim % total == 0 and dim >= total

    def batch_axes(self, batch: int):
        """Best mesh axes for the batch dim: ("pod","data"), "data", "pod", None."""
        cands = []
        if self.has_axis(self.pod_axis):
            cands.append((self.pod_axis, self.dp_axis))
            cands.append((self.pod_axis,))
        cands.append((self.dp_axis,))
        for c in cands:
            cc = tuple(a for a in c if self.has_axis(a))
            if cc and self.fits(batch, cc):
                return cc if len(cc) > 1 else cc[0]
        return None

    def resolve(self, logical: str, dim: int):
        if logical is None:
            return None
        if logical == "batch":
            return self.batch_axes(dim)
        table = {
            "tp": self.tp_axis,
            "ff": self.tp_axis,
            "heads": self.tp_axis,
            "vocab": self.tp_axis,
            "experts": self.tp_axis,
            "fsdp": self.dp_axis if self.fsdp else None,
            "expert_cap": self.dp_axis,
            "cache_seq": self.dp_axis if self.shard_cache_seq else None,
        }
        axis = table.get(logical)
        return axis if self.fits(dim, axis) else None

    # -- parameter specs ----------------------------------------------------
    def leaf_pspec(self, path: str, shape: Tuple[int, ...]) -> P:
        nd = len(shape)
        spec = [None] * nd
        if nd >= 2:
            used = set()
            if path.endswith("embed") and nd == 2:
                # token-embedding table: Megatron-style vocab sharding (the
                # gather lowers to masked-lookup + all-reduce); sharding the
                # feature dim over "model" trips XLA SPMD gather partitioning.
                if self.fits(shape[0], self.tp_axis):
                    spec[0] = self.tp_axis
                if self.fsdp and self.fits(shape[1], self.fsdp_axes()):
                    spec[1] = self.fsdp_axes()
            elif "expert_" in path and nd >= 3:
                # (..., E, d_in, d_out): expert-parallel preferred
                e_dim = nd - 3
                if self.fits(shape[e_dim], self.tp_axis):
                    spec[e_dim] = self.tp_axis
                    used.add(self.tp_axis)
                if self.fsdp and self.fits(shape[nd - 2], self.fsdp_axes()):
                    spec[nd - 2] = self.fsdp_axes()
                    used.add(self.dp_axis)
                if self.tp_axis not in used and self.fits(shape[nd - 1], self.tp_axis):
                    spec[nd - 1] = self.tp_axis
            else:
                if self.fits(shape[nd - 1], self.tp_axis):
                    spec[nd - 1] = self.tp_axis
                if self.fsdp and self.fits(shape[nd - 2], self.fsdp_axes()):
                    spec[nd - 2] = self.fsdp_axes()
        elif nd == 1 and self.fsdp and self.fits(shape[0], (self.dp_axis, self.tp_axis)):
            # big 1D leaves (e.g. RG-LRU gate params at full width) still shard
            spec[0] = None  # keep small vectors replicated; cheap & robust
        return P(*spec)

    def flat_buffer_pspec(self, shape: Tuple[int, ...]) -> P:
        """FSDP rule for a packed (n_rows, 128) FlatBuffer: shard the ROWS
        dimension over the FSDP axes (like the per-leaf m/v/p state it
        replaced) and keep the 128-lane dim whole — TP-sharding lanes would
        split the (block_rows, 128) kernel tiles, and the generic 2-D weight
        rule would happily do exactly that (128 divides most model axes).
        """
        axes = self.fsdp_axes() if self.fsdp else None
        return P(axes if (axes is not None and self.fits(shape[0], axes)) else None, None)


def param_pspecs(params, rules: Optional[Rules] = None):
    r = rules or _ACTIVE
    if r is None:
        raise RuntimeError("no active sharding rules; call sharding.activate(mesh)")

    def one(path, leaf):
        if is_flat(leaf):
            # flat optimizer state: rows-dimension FSDP (the FlatBuffer node
            # structure is preserved so the spec tree matches the state tree)
            return FlatBuffer(r.flat_buffer_pspec(leaf.shape), leaf.layout)
        name = "/".join(str(getattr(k, "key", getattr(k, "idx", k))) for k in path)
        return r.leaf_pspec(name, leaf.shape)

    return jax.tree_util.tree_map_with_path(one, params, is_leaf=is_flat)


def param_shardings(params, rules: Optional[Rules] = None):
    r = rules or _ACTIVE
    specs = param_pspecs(params, r)
    return jax.tree_util.tree_map(lambda s: NamedSharding(r.mesh, s), specs)


def batch_axes(batch: int):
    return _ACTIVE.batch_axes(batch) if _ACTIVE else None


def pspec_for_leaf(path: str, shape) -> P:
    return _ACTIVE.leaf_pspec(path, shape) if _ACTIVE else P()


def constrain_like_param(x, path: str):
    """Constrain an activation/weight view with the PARAM rule for `path`.

    Used on weights at their point of use so backward cotangents inherit the
    same sharding (GSPMD otherwise may materialize replicated gradients)."""
    if _ACTIVE is None:
        return x
    spec = _ACTIVE.leaf_pspec(path, x.shape)
    return jax.lax.with_sharding_constraint(x, NamedSharding(_ACTIVE.mesh, spec))


def constrain(x, logical_axes: Tuple[Optional[str], ...]):
    """with_sharding_constraint against the active rules; identity when none."""
    if _ACTIVE is None:
        return x
    spec = P(*(_ACTIVE.resolve(a, d) for a, d in zip(logical_axes, x.shape)))
    return jax.lax.with_sharding_constraint(x, NamedSharding(_ACTIVE.mesh, spec))


def active_rules() -> Optional[Rules]:
    return _ACTIVE


def rules_for(mesh: Mesh) -> Rules:
    """The active rules where they are ``mesh``'s, else fresh defaults for it."""
    if _ACTIVE is not None and _ACTIVE.mesh is mesh:
        return _ACTIVE
    return Rules(mesh=mesh)


@contextlib.contextmanager
def activate(mesh: Mesh, **kw):
    global _ACTIVE
    prev = _ACTIVE
    _ACTIVE = Rules(mesh=mesh, **kw)
    try:
        yield _ACTIVE
    finally:
        _ACTIVE = prev
