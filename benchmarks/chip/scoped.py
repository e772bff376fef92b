"""Device time of the traced ops under one of the program's named scopes,
anywhere in the step (forward, recompute and backward alike), read from the
traced step's own text by the rule ``scopes.py`` reads the phases by."""
from __future__ import annotations

from typing import Optional

from benchmarks.chip.scopes import _inside


def scope_ns(run, scope: str) -> Optional[int]:
    """Summed device ns of the traced ops under ``scope``; None without a
    trace and the traced step's text, or where an op of the trace is not an
    instruction of that text."""
    if run.trace is None or not run.traced_steps or run.op_scopes is None:
        return None
    ns = 0
    for ops in run.trace.ops.values():
        for o in ops:
            if o.name not in run.op_scopes:
                return None
            if _inside(run.op_scopes[o.name], scope):
                ns += o.dur
    return ns
