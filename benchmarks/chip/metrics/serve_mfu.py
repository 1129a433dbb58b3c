"""The whole serve step's share of the chip's bf16 peak: forward matmul
FLOPs of every token processed (the LM head only where a logit is
sampled) plus attention over each valid context, over the window."""
from benchmarks.chip import counts


def read(run):
    m = counts.Dims.of(run.conf)
    w = counts.window_work(run)
    flops = counts.prefill_flops(m, w) + counts.decode_flops(m, w)
    return 100.0 * flops / run.window_s / run.peak.flops_bf16
