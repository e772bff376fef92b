"""Attention kernels, backward: the least work of the backward over the live
in-document pairs of the traced steps (work.attention_bwd: 8 * hd FLOPs per
head and pair, no recompute; q, k, v, o, do, dq, dk and dv once) at the
chip's peaks, over the device time of the backward flash-attention kernels
(kernels/flash_attention_bwd.py; those that read the softmax statistics),
in %."""
from benchmarks.chip import work, xplane

KERNEL = xplane.named("flash_attention")


def read(run):
    if run.trace is None or run.peak is None or not run.traced_tokens:
        return None
    ns = xplane.op_ns(run.trace, lambda op: KERNEL(op) and xplane.reads_row_stats(op))
    if not ns:
        return None
    flops, nbytes = work.attention_bwd(run.conf, run.traced_tokens, run.traced_pairs,
                                       run.itemsize)
    return work.roofline_pct(flops, nbytes, ns * 1e-9, run.peak)
