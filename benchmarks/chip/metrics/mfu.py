"""Train step: model FLOPs of the traced steps' live tokens (work.model_flops:
6 per matmul weight per token plus attention over live pairs, no recompute)
over the traced window's length, the chips and their bf16 peak, in %."""
from benchmarks.chip import work


def read(run):
    if run.trace is None or run.peak is None or not run.traced_tokens:
        return None
    flops = work.model_flops(run.conf, run.traced_tokens, run.traced_pairs)
    return 100.0 * flops / (run.trace.window_s * run.chips * run.peak["flops"])
