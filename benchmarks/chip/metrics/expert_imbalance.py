"""How unevenly the router loads the held experts: the busiest held
expert's rows over the mean held expert's rows, per step and MoE layer,
weighted by the step's rows: n_held * sum(busiest) / sum(rows), from the
engine's `expert_rows_max` and `expert_rows`. 1 is even; n_held is
every row on one expert."""
from benchmarks.chip import counts_mla_moe as cm


def read(run):
    c = cm.expert_counters(run)
    if c is None or not c[0]:
        return None
    return cm.Dims.of(run.conf).held * c[1] / c[0]
