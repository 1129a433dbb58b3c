"""Roofline share of the held-expert grouped matmul kernel: the least
time the chip could take for the held experts' work in the window (the
FLOPs of every routed row and the bytes of every held expert that had a
row, `counts_mla_moe.expert_flops`/`expert_bytes`, from the engine's
held-expert counters) over the device time of the kernel's ops (the
configuration's `expert_kernel`) in the trace."""
from benchmarks.chip import counts, counts_mla_moe as cm


def read(run):
    t, n = run.summary.kernel_time(run.conf["expert_kernel"])
    c = cm.expert_counters(run)
    if not n or c is None:
        return None
    m = cm.Dims.of(run.conf)
    rows, _, groups = c
    return counts.roofline_share(cm.expert_flops(m, rows),
                                 cm.expert_bytes(m, rows, groups), t,
                                 run.peak)[0]
