"""The prefill step's share of the chip's bf16 peak: the useful FLOPs of
every prompt chunk (counts.prefill_flops: valid tokens only, the LM head
for each prompt's first token) over the device time of the engine's
`jit_prefill_sample` programs in the trace."""
from benchmarks.chip import counts


def read(run):
    t, n = run.summary.program_time("jit_prefill_sample")
    if not n:
        return None
    m = counts.Dims.of(run.conf)
    return (100.0 * counts.prefill_flops(m, counts.window_work(run))
            / t / run.peak.flops_bf16)
