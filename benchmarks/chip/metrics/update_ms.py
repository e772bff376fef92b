"""VR optimizer: device time per step from the start of the flat VR-LAMB
kernel (kernels/flat_update.py) to the end of the step: the kernel, the jnp
LAMB epilogue, the unpack and the parameter add, in ms."""
from benchmarks.chip import xplane

KERNEL = xplane.named("flat_vr_lamb")


def read(run):
    if run.trace is None or not run.traced_steps:
        return None
    ns = xplane.busy_after(run.trace, KERNEL)
    return ns * 1e-6 / run.traced_steps if ns else None
