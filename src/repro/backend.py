"""First-class execution plan: which implementation serves each subsystem.

This replaces the single ``use_pallas`` boolean that used to thread through
18 files (configs -> trainer -> accumulate -> gsnr -> vrgd -> ops ->
attention -> transformer -> distributed) as the repo's only dispatch
mechanism.  A :class:`Backend` selects, per subsystem,

  ============  ===================================  =========================
  subsystem     fused                                reference
  ============  ===================================  =========================
  ``optimizer``  flat-buffer Pallas VRGD update      jnp tree math (the oracle)
  ``stats``      flat GradStats carries + kernels    jnp moment trees
  ``attention``  flash kernels (fwd + custom VJP)    jnp SDPA / chunked softmax
  ============  ===================================  =========================

each mode one of ``"fused" | "reference" | "auto"`` — ``"auto"`` resolves to
fused on real TPU (Mosaic lowering) and reference elsewhere, so the default
plan is correct on any platform without a flag.  The module also centralizes
interpret-mode/platform detection: :func:`default_interpret` is the single
source of truth that ``kernels/ops.py::_interpret`` and the benchmark
``interpret``/plan markers delegate to.

Construct the plan ONCE from the parallelism config at the top of the
program (:func:`resolve_backend`) and pass it explicitly, instead of
re-deriving a config boolean at every call site.  The deprecated
``use_pallas=`` keyword still accepted at the public seams (ParallelismConfig,
make_optimizer, grad_stats, attention, the vr_* factories) maps onto the
equivalent plan here — it warns once per process and will be removed after
one release.

SPMD
----
``Backend.shard(mesh, rules)`` returns a :class:`FlatSpmd` plan that wraps
the flat-update / flat-stats ``pallas_call``s in ``shard_map`` so the
optimizer step runs PER SHARD on FSDP-sharded flat-buffer rows
(``Rules.flat_buffer_pspec``) instead of XLA gathering the whole buffer to
every device (the old ROADMAP gap).  Element-wise kernels (moment
accumulation / finalize, the update streams) shard trivially; the per-leaf
scalar reductions (GSNR 1/mean(r), LAMB/LARS trust-ratio norms) split into a
per-shard partials kernel, ONE ``jax.lax.psum`` of the small
``(leaf_slots, LANE)`` accumulator, and a per-shard apply kernel
(kernels/flat_spmd.py).  When no leaf straddles a shard boundary the psum
adds exact zeros from the other shards, so the sharded step bit-matches the
single-launch path; straddling leaves reassociate the reduction (~1 ulp).
See docs/backend.md for the full contract.
"""
from __future__ import annotations

import dataclasses
import warnings
from typing import Any, Optional, Tuple

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

FUSED = "fused"
REFERENCE = "reference"
AUTO = "auto"
_MODES = (FUSED, REFERENCE, AUTO)
SUBSYSTEMS = ("optimizer", "stats", "attention")


def platform() -> str:
    """The active jax platform ("cpu" | "gpu" | "tpu")."""
    return jax.default_backend()


def default_interpret() -> bool:
    """Pallas kernels lower through Mosaic only on TPU; everywhere else they
    run in interpret mode (same kernel bodies, evaluated by jax).  The single
    platform probe every consumer delegates to."""
    return platform() != "tpu"


def resolve_interpret(interpret: Optional[bool]) -> bool:
    """A kernel wrapper's ``interpret`` argument: None follows the platform
    (default_interpret), so a caller that omits it runs Mosaic on a TPU and
    the interpreter elsewhere — never the interpreter on the chip."""
    return default_interpret() if interpret is None else interpret


@dataclasses.dataclass(frozen=True)
class Backend:
    """Per-subsystem execution plan (frozen, hashable: safe as a jit static
    argument and as a config field)."""

    optimizer: str = AUTO
    stats: str = AUTO
    attention: str = AUTO
    # None = detect by platform (default_interpret); True/False forces the
    # Pallas interpreter on/off regardless of platform (CI overrides).
    interpret: Optional[bool] = None

    def __post_init__(self):
        for sub in SUBSYSTEMS:
            mode = getattr(self, sub)
            if mode not in _MODES:
                raise ValueError(
                    f"Backend.{sub}={mode!r}: must be one of {_MODES}"
                )

    # -- resolution ---------------------------------------------------------

    def resolve(self, subsystem: str) -> str:
        """The concrete mode ("fused" | "reference") serving ``subsystem``."""
        if subsystem not in SUBSYSTEMS:
            raise KeyError(f"unknown subsystem {subsystem!r}; one of {SUBSYSTEMS}")
        mode = getattr(self, subsystem)
        if mode == AUTO:
            return FUSED if platform() == "tpu" else REFERENCE
        return mode

    def fused(self, subsystem: str) -> bool:
        return self.resolve(subsystem) == FUSED

    def interpret_mode(self) -> bool:
        return default_interpret() if self.interpret is None else self.interpret

    def describe(self) -> dict:
        """The fully-resolved plan as a plain dict — the benchmark record
        marker (benchmarks refuse to merge records whose plans disagree)."""
        plan = {sub: self.resolve(sub) for sub in SUBSYSTEMS}
        plan["interpret"] = self.interpret_mode()
        plan["platform"] = platform()
        return plan

    # -- constructors -------------------------------------------------------

    @classmethod
    def all_fused(cls, interpret: Optional[bool] = None) -> "Backend":
        return cls(FUSED, FUSED, FUSED, interpret)

    @classmethod
    def all_reference(cls) -> "Backend":
        return cls(REFERENCE, REFERENCE, REFERENCE)

    @classmethod
    def from_flag(cls, use_pallas: bool) -> "Backend":
        """The legacy boolean's exact semantics: all-or-nothing."""
        return cls.all_fused() if use_pallas else cls.all_reference()

    # -- SPMD ---------------------------------------------------------------

    def shard(self, mesh, rules=None) -> "FlatSpmd":
        """A shard_map execution plan for the flat-buffer pallas_calls on
        ``mesh``: the optimizer step / stats sweeps run per-shard on the
        FSDP-sharded rows dimension (rules.flat_buffer_pspec)."""
        if rules is None:
            from repro.sharding.rules import Rules

            rules = Rules(mesh=mesh)
        return FlatSpmd(mesh, rules, self)


# ---------------------------------------------------------------------------
# deprecation shim: the one place a use_pallas boolean is still understood
# ---------------------------------------------------------------------------

_WARNED_USE_PALLAS = False


def _warn_use_pallas(where: str) -> None:
    global _WARNED_USE_PALLAS
    if _WARNED_USE_PALLAS:
        return
    _WARNED_USE_PALLAS = True
    warnings.warn(
        f"{where}: the use_pallas boolean is deprecated (one release); pass a "
        "repro.backend.Backend execution plan instead "
        "(Backend.from_flag(flag) is the exact legacy mapping).",
        DeprecationWarning,
        stacklevel=3,
    )


def reset_deprecation_warnings() -> None:
    """Re-arm the warn-once latch (tests)."""
    global _WARNED_USE_PALLAS
    _WARNED_USE_PALLAS = False


def resolve_backend(spec: Any = None, use_pallas: Optional[bool] = None,
                    where: str = "repro") -> Backend:
    """Normalize anything the public seams accept into a :class:`Backend`.

    spec may be a Backend, a ParallelismConfig / Config (duck-typed: the
    ``backend`` field, with a set legacy boolean field taking precedence), a
    bare bool (legacy positional callers), or None (default plan).  The
    deprecated keyword maps through :meth:`Backend.from_flag` and warns once
    per process; passing both an explicit Backend and the keyword is an
    error, not a silent preference.
    """
    if use_pallas is not None:
        if isinstance(spec, Backend):
            raise ValueError(
                f"{where}: both backend= and the deprecated boolean keyword "
                "were given; pass only the Backend plan"
            )
        _warn_use_pallas(where)
        return Backend.from_flag(use_pallas)
    if spec is None:
        return Backend()
    if isinstance(spec, Backend):
        return spec
    if isinstance(spec, bool):  # legacy positional use_pallas
        _warn_use_pallas(where)
        return Backend.from_flag(spec)
    parallel = getattr(spec, "parallel", None)
    if parallel is not None:  # a full Config
        return resolve_backend(parallel, where=where)
    _missing = object()
    flag = getattr(spec, "use_pallas", _missing)
    plan = getattr(spec, "backend", _missing)
    if flag is not _missing or plan is not _missing:  # a ParallelismConfig
        if flag is not _missing and flag is not None:
            _warn_use_pallas(where)
            return Backend.from_flag(flag)
        if plan is _missing or plan is None:  # both unset: the default plan
            return Backend()
        return resolve_backend(plan, where=where)
    raise TypeError(f"{where}: cannot resolve a Backend from {type(spec).__name__}")


# ---------------------------------------------------------------------------
# SPMD plan: shard_map wrappers for the flat-buffer pallas_calls
# ---------------------------------------------------------------------------

class FlatSpmd:
    """Per-shard execution of the flat-buffer kernels under shard_map.

    Wraps the kernels/flat_spmd.py building blocks: flat buffers arrive
    sharded over their rows dimension (Rules.flat_buffer_pspec), every
    kernel runs on the local row block with the per-block leaf-id map riding
    as a sharded operand, and cross-shard per-leaf scalars combine through a
    single psum of the (leaf_slots, LANE) partial accumulator.  Falls back
    (``supports() == False``) only when the rules leave the buffer
    replicated: a block count that does not divide across the shards is
    handled by padding the rows dimension with zero blocks (leaf id 0) up to
    the next multiple — zero rows contribute exact-zero partials to every
    per-leaf psum (r, trust numerator/denominator), so the padded math is
    bit-identical to the divisible case, and the pad rows are sliced off the
    outputs.
    """

    def __init__(self, mesh, rules, backend: Backend):
        self.mesh = mesh
        self.rules = rules
        self.backend = backend

    # -- geometry -----------------------------------------------------------

    def _axes(self, layout) -> Optional[Tuple[str, ...]]:
        from repro.core.layout import LANE

        spec = self.rules.flat_buffer_pspec((layout.n_rows, LANE))
        ax = spec[0]
        if ax is None:
            return None
        return (ax,) if isinstance(ax, str) else tuple(ax)

    def n_shards(self, layout) -> int:
        axes = self._axes(layout)
        if not axes:
            return 1
        shape = dict(self.mesh.shape)
        n = 1
        for a in axes:
            n *= shape[a]
        return n

    def supports(self, layout) -> bool:
        """True when the flat buffer for ``layout`` actually shards here.
        Block counts that don't divide the shard count are padded internally
        (class docstring), so divisibility is no longer a gate."""
        return self.n_shards(layout) > 1

    def _pad_rows(self, layout) -> int:
        """Zero rows appended so every shard holds a whole number of grid
        blocks (0 when the block count already divides)."""
        n = self.n_shards(layout)
        return 0 if n <= 1 else ((-layout.n_blocks) % n) * layout.block_rows

    @staticmethod
    def _padded(x, rows: int):
        if rows == 0:
            return x
        return jnp.pad(x, ((0, rows),) + ((0, 0),) * (x.ndim - 1))

    # -- plumbing -----------------------------------------------------------

    def _interp(self) -> bool:
        return self.backend.interpret_mode()

    def _row_spec(self, layout) -> P:
        return P(self._axes(layout), None)

    def _smap(self, fn, in_specs, out_specs):
        return jax.shard_map(
            fn, mesh=self.mesh, in_specs=in_specs, out_specs=out_specs, check_vma=False
        )

    def _meta(self, layout, pad: int = 0):
        lids = jnp.asarray(layout.block_leaf_ids())
        invsz = jnp.asarray(layout.leaf_inv_sizes())
        if pad:
            # pad blocks carry leaf id 0: their zero rows contribute exact
            # zeros to leaf 0's partial sums (additive no-ops)
            lids = jnp.pad(lids, (0, pad // layout.block_rows))
        return lids, invsz

    # -- flat-stats sweeps (element-wise: shard with no collective) ---------

    def moments_accum(self, gs, g2s, g, layout):
        from repro.kernels import flat_stats as fs

        interp = self._interp()
        pad = self._pad_rows(layout)
        row = self._row_spec(layout)
        body = lambda a, b, c: fs.flat_moments_accum(a, b, c, interpret=interp)
        out = self._smap(body, (row, row, row), (row, row))(
            self._padded(gs, pad), self._padded(g2s, pad), self._padded(g, pad)
        )
        return tuple(o[: layout.n_rows] for o in out)

    def moments_finalize(self, gs, g2s, k, layout):
        from repro.kernels import flat_stats as fs

        interp = self._interp()
        pad = self._pad_rows(layout)
        row = self._row_spec(layout)
        body = lambda a, b, kk: fs.flat_moments_finalize(a, b, kk, interpret=interp)
        k = jnp.asarray(k, jnp.float32)
        out = self._smap(body, (row, row, P()), (row, row))(
            self._padded(gs, pad), self._padded(g2s, pad), k
        )
        return tuple(o[: layout.n_rows] for o in out)

    # -- optimizer updates (partials kernel -> psum -> apply kernel) --------

    def vr_scale(self, g, ga, g2, layout, *, gamma, eps):
        from repro.kernels import flat_spmd as fsp

        interp = self._interp()
        axes = self._axes(layout)
        pad = self._pad_rows(layout)
        lids, invsz = self._meta(layout, pad)
        row = self._row_spec(layout)

        def body(lids, invsz, g, ga, g2):
            racc = fsp.leaf_r_partials(g, g2, lids, layout, gsnr_eps=eps, interpret=interp)
            racc = jax.lax.psum(racc, axes)
            return fsp.vr_scale_apply(
                g, ga, g2, racc, lids, invsz, layout, gamma=gamma, eps=eps,
                interpret=interp,
            )

        out = self._smap(
            body, (P(axes), P(None, None), row, row, row), (row, row)
        )(lids, invsz, self._padded(g, pad), self._padded(ga, pad), self._padded(g2, pad))
        return tuple(o[: layout.n_rows] for o in out)

    def vr_adam(self, g, ga, g2, m, v, p, w, scal, layout, *,
                b1, b2, b3, eps, wd, gamma, gsnr_eps, state_dtype):
        from repro.kernels import flat_spmd as fsp

        interp = self._interp()
        axes = self._axes(layout)
        pad = self._pad_rows(layout)
        lids, invsz = self._meta(layout, pad)
        row = self._row_spec(layout)
        rep = P(None, None)

        def body(lids, invsz, scal, g, ga, g2, m, v, p, w):
            racc = fsp.leaf_r_partials(g, g2, lids, layout, gsnr_eps=gsnr_eps, interpret=interp)
            racc = jax.lax.psum(racc, axes)
            return fsp.vr_adam_apply(
                g, ga, g2, m, v, p, w, scal, racc, lids, invsz, layout,
                b1=b1, b2=b2, b3=b3, eps=eps, wd=wd, gamma=gamma,
                gsnr_eps=gsnr_eps, state_dtype=state_dtype, interpret=interp,
            )

        out = self._smap(
            body, (P(axes), rep, rep) + (row,) * 7, (row,) * 4
        )(lids, invsz, scal, *(self._padded(x, pad) for x in (g, ga, g2, m, v, p, w)))
        return tuple(o[: layout.n_rows] for o in out)

    def vr_lamb(self, g, ga, g2, m, v, p, w, scal, layout, *,
                b1, b2, b3, eps, wd, gamma, gsnr_eps, state_dtype):
        from repro.kernels import flat_spmd as fsp
        from repro.kernels import flat_update as fu

        interp = self._interp()
        axes = self._axes(layout)
        pad = self._pad_rows(layout)
        lids, invsz = self._meta(layout, pad)
        row = self._row_spec(layout)
        rep = P(None, None)

        def body(lids, invsz, scal, g, ga, g2, m, v, p, w):
            racc = fsp.leaf_r_partials(g, g2, lids, layout, gsnr_eps=gsnr_eps, interpret=interp)
            racc = jax.lax.psum(racc, axes)
            u, m2, v2, p2, uacc, wacc = fsp.vr_lamb_compute(
                g, ga, g2, m, v, p, w, scal, racc, lids, invsz, layout,
                b1=b1, b2=b2, b3=b3, eps=eps, wd=wd, gamma=gamma,
                gsnr_eps=gsnr_eps, state_dtype=state_dtype, interpret=interp,
            )
            uacc = jax.lax.psum(uacc, axes)
            wacc = jax.lax.psum(wacc, axes)
            # per-leaf trust-ratio apply: a tiny element-wise epilogue XLA
            # fuses into the surrounding step — the same one the gathered
            # kernel's wrapper runs
            return fu.lamb_epilogue(u, uacc, wacc, scal[0, 0], lids), m2, v2, p2

        out = self._smap(
            body, (P(axes), rep, rep) + (row,) * 7, (row,) * 4
        )(lids, invsz, scal, *(self._padded(x, pad) for x in (g, ga, g2, m, v, p, w)))
        return tuple(o[: layout.n_rows] for o in out)

    def vr_lars(self, g, ga, g2, m, w, scal, layout, *, mu, wd, trust, eps):
        from repro.kernels import flat_spmd as fsp
        from repro.kernels import flat_update as fu

        interp = self._interp()
        axes = self._axes(layout)
        pad = self._pad_rows(layout)
        lids, invsz = self._meta(layout, pad)
        row = self._row_spec(layout)
        rep = P(None, None)

        def body(lids, invsz, scal, g, ga, g2, m, w):
            racc = fsp.leaf_r_partials(g, g2, lids, layout, gsnr_eps=eps, interpret=interp)
            racc = jax.lax.psum(racc, axes)
            u, uacc, wacc = fsp.vr_lars_compute(
                g, ga, g2, w, scal, racc, lids, invsz, layout,
                wd=wd, eps=eps, interpret=interp,
            )
            uacc = jax.lax.psum(uacc, axes)
            wacc = jax.lax.psum(wacc, axes)
            return fu.lars_epilogue(u, m, uacc, wacc, scal[0, 0], lids, mu=mu, trust=trust)

        out = self._smap(
            body, (P(axes), rep, rep) + (row,) * 5, (row, row)
        )(lids, invsz, scal, *(self._padded(x, pad) for x in (g, ga, g2, m, w)))
        return tuple(o[: layout.n_rows] for o in out)
