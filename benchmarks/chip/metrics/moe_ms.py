"""Expert layer: summed device time per traced step of the ops under the
program's ``moe`` scope (the dropless expert layers: routing, dispatch, the
experts' grouped matmuls and the combine; forward, recompute and backward),
in ms.  A program without the scope, or a model without the layer, reads
nothing."""
from benchmarks.chip import scoped, scopes


def read(run):
    scope = getattr(scopes.program_obs(), "MOE", None)
    ns = scoped.scope_ns(run, scope) if scope else None
    return ns * 1e-6 / run.traced_steps if ns else None
