"""Roofline share of the latent attention prefill kernel: the least time
the chip could take for the causal absorbed attention of every prompt
chunk in the window (each chunk's valid latent rows of r + dr bfloat16
read once per layer, plus its queries and outputs,
`counts_mla_moe.attn_prefill_work`) over the device time of the
`mla_prefill_paged` kernel in the trace."""
from benchmarks.chip import counts, counts_mla_moe as cm


def read(run):
    kernel = run.conf.get("kernels", {}).get("prefill")
    t, n = run.summary.kernel_time(kernel) if kernel else (0.0, 0)
    if not n:
        return None
    m = cm.Dims.of(run.conf)
    flops, byts = cm.attn_prefill_work(m, counts.window_work(run).chunks)
    return counts.roofline_share(flops, byts, t, run.peak)[0]
