"""Kernel registry: every ``pallas_call`` site declares its contract here.

Each kernel module (kernels/flash_attention.py, flash_attention_bwd.py,
flash_decode.py, flat_update.py, flat_stats.py, flat_spmd.py) calls
``register_kernel`` at import time with a geometry BUILDER — a zero-cost
closure over the kernel's own single-source-of-truth spec constructors
(fwd_geometry, _phased_specs, _blk, ...) — plus the
configs (representative and hostile) the analyzer replays it at.  Nothing
heavy runs at registration; geometries materialize only inside
``repro.analysis.check``.

Declared contracts ride on the operands:

  * ``shape``       the full array's shape: the layout rules compare the
                    block's last two dims against it (a block dim equal to
                    the array dim is always legal)
  * ``window``      inclusive (lo, hi) window on ``Geometry.phase_axis`` of
                    the grid steps in which the kernel reads / writes the
                    operand — outside it the index map must PARK (inputs:
                    constant index; outputs: the index of an adjacent live
                    step, so the parked steps join a written run)

``Geometry.fetch_maps`` carries concrete scalar-prefetch fetch arrays for
the FETCH-* soundness rules.  ``oracle`` names the pure-jnp reference the
differential harness certifies the kernel against — a bare name resolves in
repro.kernels.ref, a dotted path anywhere (ORACLE-REF).
"""
from __future__ import annotations

import dataclasses
import importlib
from typing import Any, Callable, Dict, Optional, Tuple

Config = Dict[str, Any]


@dataclasses.dataclass(frozen=True)
class Operand:
    """One kernel operand: its BlockSpec plus the declared contracts."""

    spec: Any  # pl.BlockSpec
    shape: Tuple[int, ...]  # the full array's shape
    dtype: str = "float32"
    window: Optional[Tuple[int, int]] = None  # inclusive live phase window


@dataclasses.dataclass(frozen=True)
class FetchMap:
    """A concrete scalar-prefetch fetch array to verify (FETCH-* rules)."""

    fetch: Any  # np.ndarray (..., nk) int32
    live: Any = None  # np.ndarray (..., nk) bool, or None (static map)
    n_blocks: int = 0  # valid index range [0, n_blocks)
    dense_identity: bool = False  # dense grid: fetch must equal arange


@dataclasses.dataclass(frozen=True)
class Geometry:
    """One launch configuration, fully concrete: ready to replay."""

    grid: Tuple[int, ...]
    ins: Dict[str, Operand]
    outs: Dict[str, Operand]
    scratch_bytes: int = 0
    extra: Tuple = ()  # appended to every index-map call (fetch array)
    phase_axis: Optional[int] = None  # grid axis carrying the phase counter
    fetch_maps: Dict[str, FetchMap] = dataclasses.field(default_factory=dict)

    def operands(self):
        """(name, Operand, is_output) over ins then outs."""
        for name, op in self.ins.items():
            yield name, op, False
        for name, op in self.outs.items():
            yield name, op, True


@dataclasses.dataclass(frozen=True)
class KernelSpec:
    name: str
    module: str
    oracle: Optional[str]  # attr in repro.kernels.ref, or dotted path
    build: Callable[..., Geometry]  # build(**config) -> Geometry
    configs: Dict[str, Config]  # names starting "hostile" skipped by --fast


_REGISTRY: Dict[str, KernelSpec] = {}

# Importing these runs every register_kernel call in the repo.  The
# REGISTRY-COVERAGE rule (analysis/rules.py) enforces the closure property:
# every module under src/repro/kernels/ with a pl.pallas_call( site must be
# listed here AND register at least one kernel.
KERNEL_MODULES = (
    "repro.kernels.flash_attention",
    "repro.kernels.flash_attention_bwd",
    "repro.kernels.flash_decode",
    "repro.kernels.flat_update",
    "repro.kernels.flat_stats",
    "repro.kernels.flat_spmd",
)


def register_kernel(name: str, *, module: str, oracle: Optional[str],
                    build: Callable[..., Geometry], configs: Dict[str, Config]) -> None:
    """Idempotent per (name, module): re-imports overwrite their own entry."""
    prev = _REGISTRY.get(name)
    if prev is not None and prev.module != module:
        raise ValueError(
            f"kernel {name!r} already registered by {prev.module} "
            f"(now also by {module}) — kernel names must be unique"
        )
    _REGISTRY[name] = KernelSpec(name, module, oracle, build, dict(configs))


def all_kernels() -> Dict[str, KernelSpec]:
    """Import every kernel module, then return the full registry."""
    for mod in KERNEL_MODULES:
        importlib.import_module(mod)
    return dict(_REGISTRY)


# ---------------------------------------------------------------------------
# shared demo layouts for the flat-buffer kernels' configs
# ---------------------------------------------------------------------------


def demo_tree(kind: str = "hostile"):
    """Parameter trees the flat kernels register their configs over.

    "aligned": every leaf exactly one (block_rows, LANE) block.  "hostile":
    ragged sizes — a sub-row leaf, a scalar-ish leaf, a leaf straddling two
    blocks, a 3-d leaf — exercising tail padding and multi-block leaves.
    """
    import numpy as np

    if kind == "aligned":
        return {f"w{i}": np.zeros((64, 128), np.float32) for i in range(4)}
    return {
        "w": np.zeros(517, np.float32),
        "b": np.zeros(3, np.float32),
        "e": np.zeros((64, 129), np.float32),
        "t": np.zeros((3, 5, 7), np.float32),
    }


def demo_layout(kind: str = "hostile"):
    from repro.core.layout import ParamLayout

    return ParamLayout.for_tree(demo_tree(kind))
