"""Roofline share of the latent attention decode kernel: the least time
the chip could take for the absorbed attention over every decode row's
valid context in the window (each latent row of r + dr bfloat16
read once per layer, plus the queries and outputs,
`counts_mla_moe.attn_decode_work`) over the device time of the
`mla_decode_paged` kernel in the trace."""
from benchmarks.chip import counts, counts_mla_moe as cm


def read(run):
    kernel = run.conf.get("kernels", {}).get("decode")
    t, n = run.summary.kernel_time(kernel) if kernel else (0.0, 0)
    if not n:
        return None
    m = cm.Dims.of(run.conf)
    flops, byts = cm.attn_decode_work(
        m, counts.window_work(run).decode_contexts)
    return counts.roofline_share(flops, byts, t, run.peak)[0]
