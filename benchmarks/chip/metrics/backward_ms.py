"""Train step, backward: summed device time per traced step of the ops of
the transposed ``model`` scope, the recompute left out (``scopes.phase``),
in ms."""
from benchmarks.chip import scopes


def read(run):
    return scopes.phase_ms(run, "backward")
