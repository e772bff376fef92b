"""Gradient statistics: summed device time per step of the moment
accumulation and finalize kernels (kernels/flat_stats.py), in ms."""
from benchmarks.chip import xplane

KERNELS = xplane.named("flat_moments_accum", "flat_moments_finalize")


def read(run):
    if run.trace is None or not run.traced_steps:
        return None
    ns = xplane.op_ns(run.trace, KERNELS)
    return ns * 1e-6 / run.traced_steps if ns else None
