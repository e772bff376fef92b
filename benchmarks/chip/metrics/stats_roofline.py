"""Gradient statistics: the least bytes of the k-group moments (work.
stats_bytes: each microbatch gradient read once, mean and mean of squares
written once, float32) at peak HBM bandwidth, over the statistics kernels'
device time, in %."""
from benchmarks.chip import work, xplane

KERNELS = xplane.named("flat_moments_accum", "flat_moments_finalize")


def read(run):
    if run.trace is None or run.peak is None or not run.traced_steps:
        return None
    ns = xplane.op_ns(run.trace, KERNELS)
    if not ns:
        return None
    nbytes = work.stats_bytes(run.conf, int(run.traffic["k"])) * run.traced_steps
    return work.roofline_pct(0.0, nbytes, ns * 1e-9, run.peak)
