"""Device: the share of the traced window in which no operation ran on the
device, 1 - (union of the device ops' intervals) / window, in %, averaged
over the chips."""
from benchmarks.chip import xplane


def read(run):
    if run.trace is None:
        return None
    return 100.0 * (1.0 - xplane.mean_busy_s(run.trace) / run.trace.window_s)
