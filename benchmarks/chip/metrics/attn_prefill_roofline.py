"""Roofline share of the paged prefill attention kernel: the least time
the chip could take for the causal attention of every prompt chunk in
the window (counts.attn_prefill_work) over the device time of the
`gqa_prefill_paged` kernel in the trace."""
from benchmarks.chip import counts


def read(run):
    t, n = run.summary.kernel_time(run.conf["kernels"]["prefill"])
    if not n:
        return None
    m = counts.Dims.of(run.conf)
    flops, byts = counts.attn_prefill_work(m, counts.window_work(run).chunks)
    return counts.roofline_share(flops, byts, t, run.peak)[0]
