"""Collectives: device time per traced step in which a collective runs on a
chip and no other op does, averaged over the chips, in ms.  The collectives
are the synchronous all-reduce, all-gather, reduce-scatter, all-to-all and
collective-permute ops and the waits (``-done``) of asynchronous ones
(``xplane.is_collective``); a collective that compute covers costs the step
nothing and reads 0.  A cell on one chip has none, and reads nothing."""
from benchmarks.chip import xplane


def read(run):
    if run.trace is None or not run.traced_steps:
        return None
    planes = list(run.trace.ops)
    if not any(xplane.is_collective(o) for p in planes for o in run.trace.ops[p]):
        return None
    ns = sum(xplane.exposed_ns(run.trace, p, xplane.is_collective) for p in planes)
    return ns / len(planes) * 1e-6 / run.traced_steps
