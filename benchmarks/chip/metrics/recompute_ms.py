"""Train step, recompute: summed device time per traced step of the ops the
backward pass reruns under ``jax.checkpoint`` (``rematted_computation``
inside the program's ``model`` scope; ``scopes.phase``), in ms."""
from benchmarks.chip import scopes


def read(run):
    return scopes.phase_ms(run, "recompute")
