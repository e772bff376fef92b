"""VR optimizer: the least bytes of one VR-LAMB step (work.update_bytes:
read parameters, mean, mean of squares, m, v, GSNR momentum; write
parameters, m, v, GSNR momentum; float32) at peak HBM bandwidth, over the
update's device time (as update_ms counts it), in %."""
from benchmarks.chip import work, xplane

KERNEL = xplane.named("flat_vr_lamb")


def read(run):
    if run.trace is None or run.peak is None or not run.traced_steps:
        return None
    ns = xplane.busy_after(run.trace, KERNEL)
    if not ns:
        return None
    return work.roofline_pct(0.0, work.update_bytes(run.conf) * run.traced_steps,
                             ns * 1e-9, run.peak)
