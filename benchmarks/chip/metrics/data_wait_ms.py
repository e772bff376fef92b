"""Data path: mean host time per step in the benchmark's ``data_wait`` span
around ``next()`` on the prefetched batch iterator, in the traced window."""
from benchmarks.chip import xplane


def read(run):
    if run.trace is None or not run.traced_steps:
        return None
    ns = xplane.spans_ns(run.trace, "data_wait")
    return None if ns is None else ns * 1e-6 / run.traced_steps
