"""Roofline share of the paged decode attention kernel: the least time
the chip could take for the attention over every decode row's valid
context in the window (counts.attn_decode_work) over the device time of
the `gqa_decode_paged` kernel in the trace."""
from benchmarks.chip import counts


def read(run):
    t, n = run.summary.kernel_time(run.conf["kernels"]["decode"])
    if not n:
        return None
    m = counts.Dims.of(run.conf)
    flops, byts = counts.attn_decode_work(
        m, counts.window_work(run).decode_contexts)
    return counts.roofline_share(flops, byts, t, run.peak)[0]
