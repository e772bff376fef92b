"""Expert layer: the least work of the held experts' matmuls over the traced
steps (the family's ``expert_work``: 6 * 3 * D * F FLOPs per (token, choice)
row routed to a held expert, as the program's ``repro.moe.rows`` counter
logged them while the profiler recorded, and each row's input and output
and the experts' weights once a pass) at the chip's peaks, over the device
time of the ops under the program's ``moe_experts`` scope, in %.  The
experts' forward runs twice under rematerialization; its work counts once.
A program without the scope or the counter reads nothing."""
from benchmarks.chip import scoped, scopes, work
from benchmarks.chip.spec import family


def read(run):
    obs = scopes.program_obs()
    scope, counter = getattr(obs, "MOE_EXPERTS", None), getattr(obs, "MOE_ROWS", None)
    fam = family(run.conf)
    if (scope is None or counter is None or run.peak is None or not run.traced_steps
            or not hasattr(fam, "expert_work")):
        return None
    ns = scoped.scope_ns(run, scope)
    rows = obs.traced_counts(counter)[-run.traced_steps:]
    if not ns or len(rows) < run.traced_steps:
        return None
    flops, nbytes = fam.expert_work(run.conf, sum(rows),
                                    run.traced_steps * int(run.traffic["k"]), run.itemsize)
    return work.roofline_pct(flops, nbytes, ns * 1e-9, run.peak)
