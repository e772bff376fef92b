"""Gradient statistics: summed device time per traced step of packing each
microbatch's gradient tree into the flat buffer the moment kernels read
(the program's ``stats_pack`` scope; ``scopes.phase``), in ms."""
from benchmarks.chip import scopes


def read(run):
    return scopes.phase_ms(run, "grad_pack")
