"""The contract checks.  Each has a stable rule ID (asserted by the
mutation tests in tests/test_analysis.py and referenced from the Mosaic
checklists in docs/) and produces ``Finding`` records, never exceptions —
the checker reports everything it can see in one pass.

Revisit rules (replaying the real index maps via analysis.replay).  The TPU
pipeline copies an output block back to HBM whenever its block index
changes (and after the last step), and never reads an output block from
HBM — so an output block must be written in exactly one run of consecutive
steps:

  REVISIT-RACE   an OUTPUT block index recurs in two separate runs (the
                 revisit starts from a buffer that does not hold the first
                 run's writes: they are lost or overwritten)
  REVISIT-PARK   an INPUT with a declared phase window must hold a CONSTANT
                 block index through every out-of-window segment (parked =
                 zero DMA; a drifting index means the kernel re-fetches
                 blocks in phases it never reads them)
  REVISIT-WRITE  every run of an OUTPUT's block index contains a live step
                 of its window (a run with no live step is copied back from
                 a buffer the kernel never wrote — parked outputs must park
                 on the index of an adjacent live step)

Layout rules (static, from BlockSpec shapes, array shapes and dtypes) — the
TPU compiler's block rule: the last two block dims (None counts as 1) are
each divisible by the tile, or equal to the array's own dims:

  LAYOUT-RANK     every operand block keeps >= MIN_TILE_RANK dims
  LAYOUT-SUBLANE  second-minor block dim % sublane(dtype) == 0, or == the
                  array's second-minor dim (dtype-derived, not 8)
  LAYOUT-LANE     minor block dim % LANE == 0, or == the array's minor dim

Fetch-map rules (concrete scalar-prefetch arrays):

  FETCH-BOUNDS    every fetch index in [0, n_blocks)
  FETCH-FILL      monotone nondecreasing forward-fill along the kv axis;
                  fetch[ik] == ik exactly on live tiles (rows with at least
                  one live tile); all-dead rows fetch one constant block
  FETCH-IDENTITY  a dense non-causal grid's static map is the identity

Resource / metadata rules:

  VMEM-BUDGET   sum of double-buffered operand windows + scratch within the
                per-platform VMEM budget
  ORACLE-REF    the registered jnp oracle resolves to a callable
  LAUNCH-COUNT  traced pallas_call counts match analysis.launch_manifest
"""
from __future__ import annotations

import dataclasses
import importlib
from typing import List

import numpy as np

from repro.analysis.layout_contracts import (
    DOUBLE_BUFFER,
    LANE,
    MIN_TILE_RANK,
    VMEM_BUDGET_BYTES,
    itemsize,
    sublane,
)
from repro.analysis.registry import Geometry, KernelSpec, Operand
from repro.analysis.replay import _blk_bytes, grid_steps, replay_indices

RULES = {
    "REVISIT-RACE": "an output block is written in one run of consecutive steps, never revisited",
    "REVISIT-PARK": "input parked outside its phase window must hold a constant block index",
    "REVISIT-WRITE": "every run of an output block index contains a live (written) step",
    "LAYOUT-RANK": f"operand blocks keep >= {MIN_TILE_RANK} dims",
    "LAYOUT-SUBLANE": "second-minor block dim divisible by sublane(dtype) or equal to the array dim",
    "LAYOUT-LANE": "minor block dim divisible by LANE or equal to the array dim",
    "FETCH-BOUNDS": "scalar-prefetch fetch indices in [0, n_blocks)",
    "FETCH-FILL": "fetch map is a monotone forward-fill; self-fetch exactly on live tiles",
    "FETCH-IDENTITY": "dense non-causal static fetch map is the identity",
    "VMEM-BUDGET": "double-buffered operand windows + scratch fit the per-platform VMEM budget",
    "ORACLE-REF": "every registered kernel names a resolvable jnp oracle",
    "LAUNCH-COUNT": "traced pallas_call counts match analysis.launch_manifest",
    "REGISTRY-COVERAGE": "every kernels/ module with a pl.pallas_call site is registered in analysis.registry",
}


@dataclasses.dataclass(frozen=True)
class Finding:
    rule: str
    kernel: str
    config: str
    detail: str

    def __str__(self) -> str:
        return f"[{self.rule}] {self.kernel}/{self.config}: {self.detail}"


# ---------------------------------------------------------------------------
# layout contracts (static)
# ---------------------------------------------------------------------------


def _layout_findings(kernel: str, config: str, name: str, op: Operand) -> List[Finding]:
    bs = tuple(1 if d is None else int(d) for d in op.spec.block_shape)
    mk = lambda rule, detail: Finding(rule, kernel, config, f"operand {name!r}: {detail}")
    if len(bs) < MIN_TILE_RANK:
        return [mk("LAYOUT-RANK", f"block shape {bs} has rank {len(bs)} < {MIN_TILE_RANK} "
                   "— Mosaic tiling needs >= 2 dims")]
    shape = tuple(op.shape)
    findings = []
    sub = sublane(op.dtype)
    if bs[-2] % sub and bs[-2] != shape[-2]:
        findings.append(mk("LAYOUT-SUBLANE",
                           f"second-minor dim {bs[-2]} of block {bs} is neither a multiple of "
                           f"{sub} (= sublane({op.dtype})) nor the array's {shape[-2]} "
                           f"(array {shape})"))
    if bs[-1] % LANE and bs[-1] != shape[-1]:
        findings.append(mk("LAYOUT-LANE",
                           f"minor dim {bs[-1]} of block {bs} is neither a multiple of "
                           f"{LANE} nor the array's {shape[-1]} (array {shape})"))
    return findings


# ---------------------------------------------------------------------------
# revisit races & phase-window parking (replayed)
# ---------------------------------------------------------------------------


def _runs(seq: List[tuple]) -> List[tuple]:
    """Maximal runs of equal consecutive indices: (index, first, last)."""
    runs, start = [], 0
    for i in range(1, len(seq) + 1):
        if i == len(seq) or seq[i] != seq[start]:
            runs.append((seq[start], start, i - 1))
            start = i
    return runs


def _revisit_findings(kernel: str, config: str, name: str, op: Operand,
                      is_out: bool, seq: List[tuple], live: List[bool]) -> List[Finding]:
    findings: List[Finding] = []
    mk = lambda rule, detail: Finding(rule, kernel, config, f"operand {name!r}: {detail}")
    runs = _runs(seq)

    if not is_out:
        # parked segments hold a constant index (no DMA outside the window)
        i, n = 0, len(seq)
        while i < n:
            if live[i]:
                i += 1
                continue
            j = i
            while j < n and not live[j]:
                j += 1
            if len(set(seq[i:j])) > 1:
                findings.append(mk("REVISIT-PARK",
                                   f"block index changes inside the parked segment (steps "
                                   f"{i}..{j - 1}: {sorted(set(seq[i:j]))[:4]}...) — outside its "
                                   "phase window the index map must park (constant index, zero DMA)"))
                break
            i = j
        return findings

    unwritten = [(b, lo, hi) for b, lo, hi in runs if not any(live[lo:hi + 1])]
    if unwritten:
        b, lo, hi = unwritten[0]
        findings.append(mk("REVISIT-WRITE",
                           f"block {b} is held over steps {lo}..{hi} with no live step — its "
                           "copy-back writes a buffer the kernel never wrote; park on the "
                           "index of an adjacent live step"))
    seen: dict = {}
    for b, _, _ in runs:
        seen[b] = seen.get(b, 0) + 1
    revisited = sorted(b for b, c in seen.items() if c > 1)
    if revisited:
        findings.append(mk("REVISIT-RACE",
                           f"block(s) {revisited[:4]} recur in separate runs — an output "
                           "block is never re-read from HBM, so the earlier run's writes "
                           "are lost or overwritten"))
    return findings


# ---------------------------------------------------------------------------
# fetch-map soundness (concrete scalar-prefetch arrays)
# ---------------------------------------------------------------------------


def _fetch_findings(kernel: str, config: str, name: str, fm) -> List[Finding]:
    findings: List[Finding] = []
    mk = lambda rule, detail: Finding(rule, kernel, config, f"fetch map {name!r}: {detail}")
    fetch = np.asarray(fm.fetch)
    if fetch.size == 0:
        return [mk("FETCH-BOUNDS", "empty fetch array")]
    if fetch.min() < 0 or fetch.max() >= fm.n_blocks:
        return findings + [mk("FETCH-BOUNDS",
                              f"indices span [{fetch.min()}, {fetch.max()}] outside "
                              f"[0, {fm.n_blocks}) — a kv map would fetch out of bounds")]
    if np.any(np.diff(fetch, axis=-1) < 0):
        findings.append(mk("FETCH-FILL", "not monotone nondecreasing along the kv axis — "
                           "a backward jump re-fetches an already-departed block mid-row"))
    if fm.live is not None:
        live = np.asarray(fm.live, bool)
        ik = np.arange(fetch.shape[-1])
        self_fetch = fetch == ik
        has_live = live.any(axis=-1, keepdims=True)
        if np.any((self_fetch != live) & has_live):
            findings.append(mk("FETCH-FILL",
                               "fetch[ik] == ik must hold exactly on live tiles — the kernel's "
                               "liveness predicate IS the self-fetch test, so a mismatch runs "
                               "compute on a stale window or skips a live tile"))
        dead_const = np.all(fetch == fetch[..., :1], axis=-1, keepdims=True)
        if np.any(~has_live & ~dead_const):
            findings.append(mk("FETCH-FILL", "an all-dead row must fetch one constant block"))
    if fm.dense_identity:
        ident = np.broadcast_to(np.arange(fetch.shape[-1], dtype=fetch.dtype), fetch.shape)
        if not np.array_equal(fetch, ident):
            findings.append(mk("FETCH-IDENTITY",
                               "dense non-causal grid: the static fetch map must be the "
                               "identity (every tile live, every step self-fetching)"))
    return findings


# ---------------------------------------------------------------------------
# VMEM footprint
# ---------------------------------------------------------------------------


def _vmem_findings(kernel: str, config: str, geom: Geometry, budget: int) -> List[Finding]:
    window_bytes = sum(_blk_bytes(op.spec, itemsize(op.dtype))
                       for _, op, _ in geom.operands())
    total = DOUBLE_BUFFER * window_bytes + geom.scratch_bytes
    if total <= budget:
        return []
    return [Finding("VMEM-BUDGET", kernel, config,
                    f"estimated working set {total:,} B ({DOUBLE_BUFFER}x {window_bytes:,} B "
                    f"operand windows + {geom.scratch_bytes:,} B scratch) exceeds the "
                    f"{budget:,} B VMEM budget")]


# ---------------------------------------------------------------------------
# entry points
# ---------------------------------------------------------------------------


def check_geometry(kernel: str, config: str, geom: Geometry,
                   budget: int = VMEM_BUDGET_BYTES["tpu"]) -> List[Finding]:
    """All geometry-level rules for one (kernel, config) launch."""
    findings: List[Finding] = []
    steps = list(grid_steps(geom.grid))
    for name, op, is_out in geom.operands():
        findings += _layout_findings(kernel, config, name, op)
        seq = replay_indices(geom.grid, op.spec, geom.extra)
        if op.window is None or geom.phase_axis is None:
            live = [True] * len(steps)
        else:
            lo, hi = op.window
            ax = geom.phase_axis
            live = [lo <= s[ax] <= hi for s in steps]
        findings += _revisit_findings(kernel, config, name, op, is_out, seq, live)
    for name, fm in geom.fetch_maps.items():
        findings += _fetch_findings(kernel, config, name, fm)
    findings += _vmem_findings(kernel, config, geom, budget)
    return findings


def check_registry_coverage(
    kernel_dir=None,
    package: str = "repro.kernels",
    known_modules=None,
    registered=None,
) -> List[Finding]:
    """REGISTRY-COVERAGE: no pallas_call can dodge the contract checker.

    Scans every ``*.py`` under the kernels package for ``pl.pallas_call(``
    CALL SITES (the bare word appears in docstrings and in the jaxpr counter,
    so the regex matches the call form only) and fails when a containing
    module either isn't imported by the registry (registry.KERNEL_MODULES)
    or is imported but registers no kernel.  All arguments default to the
    real package/registry; the mutation test points them at a synthetic
    tree instead.
    """
    import pathlib
    import re

    findings: List[Finding] = []
    if kernel_dir is None:
        import repro.kernels as _kpkg

        kernel_dir = pathlib.Path(_kpkg.__file__).parent
    if known_modules is None or registered is None:
        from repro.analysis import registry as _registry

        kernels = _registry.all_kernels()
        if known_modules is None:
            known_modules = _registry.KERNEL_MODULES
        if registered is None:
            registered = {k.module for k in kernels.values()}
    pat = re.compile(r"\bpl\s*\.\s*pallas_call\s*\(")
    for path in sorted(pathlib.Path(kernel_dir).glob("*.py")):
        n_sites = len(pat.findall(path.read_text()))
        if not n_sites:
            continue
        mod = f"{package}.{path.stem}"
        if mod not in known_modules:
            findings.append(Finding(
                "REGISTRY-COVERAGE", mod, "-",
                f"{path.name} has {n_sites} pl.pallas_call site(s) but the module "
                "is not in registry.KERNEL_MODULES — its kernels dodge the "
                "contract checker"))
        elif mod not in registered:
            findings.append(Finding(
                "REGISTRY-COVERAGE", mod, "-",
                f"{path.name} is imported by the registry but registers no kernel "
                f"for its {n_sites} pl.pallas_call site(s)"))
    return findings


def check_oracle(kspec: KernelSpec) -> List[Finding]:
    """ORACLE-REF: the registered jnp oracle exists and is callable."""
    if not kspec.oracle:
        return [Finding("ORACLE-REF", kspec.name, "-",
                        "kernel registered without a jnp oracle — every fused kernel "
                        "needs an allclose target in repro.kernels.ref")]
    mod_name, _, attr = kspec.oracle.rpartition(".")
    mod_name = mod_name or "repro.kernels.ref"
    try:
        fn = getattr(importlib.import_module(mod_name), attr, None)
    except ImportError:
        fn = None
    if not callable(fn):
        return [Finding("ORACLE-REF", kspec.name, "-",
                        f"oracle {kspec.oracle!r} does not resolve to a callable "
                        f"in {mod_name}")]
    return []
