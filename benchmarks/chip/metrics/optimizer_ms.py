"""VR optimizer: summed device time per traced step of the ops under the
program's ``optimizer`` scope: gradient norm and clip, the VR-LAMB kernel,
the LAMB epilogue, the unpack and the parameter add (``scopes.phase``), in
ms.  Unlike ``update_ms`` it owns ops by scope, not by their place in the
schedule."""
from benchmarks.chip import scopes


def read(run):
    return scopes.phase_ms(run, "optimizer")
