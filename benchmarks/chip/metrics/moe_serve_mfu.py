"""The whole serve step's share of the chip's bf16 peak for a latent
attention MoE model: the useful FLOPs of the window (every valid token
through attention, shared and dense layers and the router, the held
experts' routed rows from the engine's `expert_rows`, each sampled
logit, attention over each valid context; `counts_mla_moe.serve_flops`)
over the window."""
from benchmarks.chip import counts, counts_mla_moe as cm


def read(run):
    c = cm.expert_counters(run)
    if c is None:
        return None
    m = cm.Dims.of(run.conf)
    flops = cm.serve_flops(m, counts.window_work(run), c[0])
    return 100.0 * flops / run.window_s / run.peak.flops_bf16
