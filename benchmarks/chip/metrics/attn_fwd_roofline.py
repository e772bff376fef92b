"""Attention kernels, forward: the least work of the forward over the live
in-document pairs of the traced steps (work.attention_fwd: 4 * hd FLOPs per
head and pair; q, k, v and o once) at the chip's peaks, over the device time
of the forward flash-attention kernels (kernels/flash_attention.py; those
that take no softmax statistics), in %.  Under rematerialization the
forward runs twice a step and its time counts twice; its work once."""
from benchmarks.chip import work, xplane

KERNEL = xplane.named("flash_attention")


def read(run):
    if run.trace is None or run.peak is None or not run.traced_tokens:
        return None
    ns = xplane.op_ns(run.trace, lambda op: KERNEL(op) and not xplane.reads_row_stats(op))
    if not ns:
        return None
    flops, nbytes = work.attention_fwd(run.conf, run.traced_tokens, run.traced_pairs,
                                       run.itemsize)
    return work.roofline_pct(flops, nbytes, ns * 1e-9, run.peak)
