"""The train step's phases, from the program's own named scopes.

A profile's device ops carry their HLO instruction's name and text, but not
its metadata.  The compiled step's text carries both: every instruction's
``op_name`` holds the path of ``jax.named_scope`` components it was traced
under (``repro.obs.SCOPES``) and the autodiff transforms around them.  So a
traced op's phase is read from the step's text, keyed by instruction name.

The text is that of the traced step itself, mesh and all: ``run.py`` keeps
its ``op_scopes`` as ``RunInfo.op_scopes``.  A program without
``repro.obs`` has no scopes to read, and every reading here is then
``None``.
"""
from __future__ import annotations

import re
from typing import Dict, Optional

PHASES = ("grad_pack", "stats", "optimizer", "recompute", "backward", "forward", "other")

# HLO text, printed operands before users: a computation's header, an
# instruction, its op_name, its operands (references after "(" or " ";
# attributes refer after "=" or "{") and the computations it runs
_COMP = re.compile(r"^(?:ENTRY\s+)?%?([\w.\-]+) \(.*\{\s*$")
_INSTR = re.compile(r"^\s*(?:ROOT\s+)?%?([\w.\-]+) = ")
_OP_NAME = re.compile(r'op_name="((?:[^"\\]|\\.)*)"')
_OPERAND = re.compile(r"(?<=[( ])%([\w.\-]+)")
_CALLS = re.compile(r"\bcalls=%([\w.\-]+)")
_RUNS = re.compile(r"\b(?:calls|body|condition)=%([\w.\-]+)|branch_computations=\{([^}]*)\}")


def program_obs():
    """The program's instrumentation module, or None where the program
    predates it."""
    try:
        from repro import obs
    except ImportError:
        return None
    return obs


def op_scopes(hlo_text: str) -> Dict[str, str]:
    """{instruction name: op_name} of every instruction in ``hlo_text``.

    An instruction the compiler made without an op_name (the dynamic-update-
    slices it rewrites a concatenate into, a layout copy, a loop it expands
    an op into) takes the op_name of what it was made from: the instructions
    it fuses, from the root up; else the first of its operands that has one;
    else that of the loop or call that runs it.  "" where none has one."""
    own: Dict[str, str] = {}
    operands: Dict[str, list] = {}
    fuses: Dict[str, str] = {}
    home: Dict[str, str] = {}
    body: Dict[str, list] = {}
    runner: Dict[str, str] = {}
    comp = ""
    for line in hlo_text.splitlines():
        m = _INSTR.match(line)
        if not m:
            c = _COMP.match(line)
            comp = c.group(1) if c else comp
            continue
        name, rest = m.group(1), line[m.end():]
        n = _OP_NAME.search(rest)
        own[name] = n.group(1) if n else ""
        operands[name] = _OPERAND.findall(rest.split("metadata=", 1)[0])
        home[name] = comp
        body.setdefault(comp, []).append(name)
        f = _CALLS.search(rest)
        if f:
            fuses[name] = f.group(1)
        for r in _RUNS.finditer(rest):
            for callee in [r.group(1)] if r.group(1) else re.findall(r"%([\w.\-]+)", r.group(2)):
                runner.setdefault(callee, name)

    made: Dict[str, str] = {}
    for name in own:  # in text order, so an operand comes first
        got = own[name] or next(
            (own[i] for i in reversed(body.get(fuses.get(name, ""), [])) if own[i]), "")
        made[name] = got or next((made[o] for o in operands[name] if made.get(o)), "")

    def run_by(comp: str) -> str:
        seen = set()
        while comp in runner and comp not in seen:
            seen.add(comp)
            if made[runner[comp]]:
                return made[runner[comp]]
            comp = home[runner[comp]]
        return ""

    return {name: got or run_by(home[name]) for name, got in made.items()}


def _inside(op_name: str, scope: str) -> bool:
    """A path component is ``scope`` itself or ``scope`` wrapped in
    transforms, as ``jvp(model)`` or ``transpose(jvp(model))``."""
    pattern = re.compile(r"(?:\w+\()*" + re.escape(scope) + r"\)*")
    return any(pattern.fullmatch(c) for c in op_name.split("/"))


def phase(op_name: str) -> str:
    """The phase of the train step an instruction belongs to; the first
    rule that holds wins."""
    obs = program_obs()
    if obs is None:
        return "other"
    if _inside(op_name, obs.STATS_PACK):
        return "grad_pack"
    if _inside(op_name, obs.STATS_ACCUM) or _inside(op_name, obs.STATS_FINALIZE):
        return "stats"
    if _inside(op_name, obs.OPTIMIZER):
        return "optimizer"
    if _inside(op_name, obs.MODEL):
        parts = op_name.split("/")
        if "rematted_computation" in parts:
            return "recompute"
        if any(c.startswith("transpose(") for c in parts):
            return "backward"
        return "forward"
    return "other"


def phase_ns(trace, scopes: Dict[str, str]) -> Optional[Dict[str, int]]:
    """Summed device time of the trace's ops in each phase; None where an
    op of the trace is not an instruction of ``scopes``, whose text is then
    not the traced program's."""
    out = dict.fromkeys(PHASES, 0)
    phases: Dict[str, str] = {}
    for ops in trace.ops.values():
        for o in ops:
            if o.name not in scopes:
                return None
            if o.name not in phases:
                phases[o.name] = phase(scopes[o.name])
            out[phases[o.name]] += o.dur
    return out


def phase_ms(run, name: str) -> Optional[float]:
    """Device time per traced step of the ops in phase ``name``, in ms;
    None without a trace and the traced step's text, without the program's
    scopes, or with none of the phase's ops."""
    if (run.trace is None or not run.traced_steps or run.op_scopes is None
            or program_obs() is None):
        return None
    ns = phase_ns(run.trace, run.op_scopes)
    return ns[name] * 1e-6 / run.traced_steps if ns and ns[name] else None
