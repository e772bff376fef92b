"""Train step, forward: summed device time per traced step of the ops traced
under the program's ``model`` scope (forward and loss) that are neither
backward nor recompute (``scopes.phase``), in ms."""
from benchmarks.chip import scopes


def read(run):
    return scopes.phase_ms(run, "forward")
