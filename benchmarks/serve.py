"""Serving benchmark: continuous vs static batching on the semantic
link — tokens/s, request-latency and TTFT percentiles vs concurrent
users, plus the paged-KV capacity factor (BENCH_serve.json). CPU counts
(cycles, pages, bits); its walls are CPU walls, not device numbers.

The paper serves one user at a time; this benchmark measures the
engine that serves MANY. For each user count a mixed-length
`RequestTrace` (same seed => same requests for every scheduler) runs
through `ServeEngine` on a fading bounded-ARQ radio:

* `continuous` vs `static` — in-flight admission vs the barrier
  (`speedup_cycles` > 1 at every width wherever lengths are mixed), on
  the same schedule-invariant radio bill.
* paged KV capacity — a long-prompt mix on the reduced transformer:
  `capacity_factor` = the KV columns a per-slot reservation would hold
  (n_slots * max_seq_len) over the pool's peak in-flight columns
  (peak_pages * page_size).

    PYTHONPATH=src python -m benchmarks.run --only serve
"""
from __future__ import annotations

import json
import os

import jax

from repro.configs import get_arch
from repro.models import api as M
from repro.nn import init_params
from repro.schemes.radio import Radio
from repro.serve import ServeEngine, make_trace

RESULTS = os.path.join(os.path.dirname(__file__), "results")

CHUNK = 16


def _case_dict(rep) -> dict:
    d = rep.to_dict()
    d["tokens_per_cycle"] = d["generated_tokens"] / max(d["cycles"], 1)
    # billing invariant, per run: every attempted bit is either
    # delivered or erased
    assert abs(d["delivered_bits"] + d["erased_bits"] - d["bits"]) < 1e-6
    return d


def _paged_capacity(seed: int) -> dict:
    """Paged KV on the reduced transformer: one long-prompt request
    drives max_seq_len while short requests churn — a per-slot
    reservation would hold n_slots * S columns for the whole run; the
    pool holds only the tokens actually in flight."""
    from repro.serve import Request, RequestTrace
    cfg = get_arch("qwen1.5-0.5b").reduced()
    params = init_params(jax.random.PRNGKey(seed), M.param_specs(cfg))
    page = 16
    reqs = (Request(0, 0, 96, 8),) + tuple(
        Request(rid, 0, 4 + rid % 5, 2 + rid % 4)
        for rid in range(1, 10))
    trace = RequestTrace(31, reqs)
    n_slots = 4
    paged = ServeEngine(cfg, params, n_slots=n_slots, page_size=page,
                        chunk_size=CHUNK).serve(trace)
    assert all(r.status == "ok" for r in paged.results)
    S = trace.max_seq_len()
    slot_cols = n_slots * S
    paged_cols = paged.peak_pages * page
    return {
        "arch": cfg.name, "n_slots": n_slots, "page_size": page,
        "max_seq_len": S, "slot_reserved_cols": slot_cols,
        "paged_peak_cols": paged_cols,
        "peak_pages": paged.peak_pages, "n_pages": paged.n_pages,
        "capacity_factor": slot_cols / max(paged_cols, 1),
    }


def run(full: bool = False, seed: int = 0) -> dict:
    cfg = get_arch("paper-tinylstm")
    params = init_params(jax.random.PRNGKey(seed), M.param_specs(cfg))
    radio = Radio(snr_db=10.0, fading=True, arq_max_tx=2, arq_attempts=2)
    n_slots = 8
    # more users than slots, else there is only one batch and nothing
    # for the barrier to lose
    user_counts = (16, 32, 64, 128) if full else (16, 32)
    engine = ServeEngine(cfg, params, n_slots=n_slots, radio=radio,
                         chunk_size=CHUNK)

    out = {"arch": cfg.name, "n_slots": n_slots, "snr_db": radio.snr_db,
           "arq_max_tx": radio.arq_max_tx, "chunk_size": CHUNK,
           "cases": {}}
    specs = [(f"users{u}", u, (4, 16)) for u in user_counts]
    # the long-prompt mix: prompts of up to six chunks
    specs.append(("longprompt16", 16, (8, 96)))
    for name, users, plens in specs:
        trace = make_trace(seed + users + (97 if "long" in name else 0),
                           users, prompt_lens=plens,
                           new_tokens=(1, 12), mean_gap=0.0)
        case = {}
        for mode in ("continuous", "static"):
            engine.serve(trace, mode)               # warm the jit caches
            case[mode] = _case_dict(engine.serve(trace, mode))
        case["speedup_cycles"] = (case["static"]["cycles"]
                                  / max(case["continuous"]["cycles"], 1))
        # same trace, same radio draws: the bill is schedule-invariant
        assert case["continuous"]["bits"] == case["static"]["bits"]
        out["cases"][name] = case
    out["paged_kv"] = _paged_capacity(seed)
    return out


def main(full: bool = False) -> list[str]:
    res = run(full)
    os.makedirs(RESULTS, exist_ok=True)
    with open(os.path.join(RESULTS, "BENCH_serve.json"), "w") as f:
        json.dump(res, f, indent=1)
    rows = []
    for case, rec in res["cases"].items():
        for mode in ("continuous", "static"):
            d = rec[mode]
            rows.append(f"serve,{case}/{mode},cycles,{d['cycles']}")
            rows.append(f"serve,{case}/{mode},tokens_per_cycle,"
                        f"{d['tokens_per_cycle']:.3f}")
            rows.append(f"serve,{case}/{mode},tokens_per_s,"
                        f"{d['tokens_per_s']:.1f}")
            rows.append(f"serve,{case}/{mode},p50_latency_cycles,"
                        f"{d['p50_latency_cycles']:.0f}")
            rows.append(f"serve,{case}/{mode},p99_latency_cycles,"
                        f"{d['p99_latency_cycles']:.0f}")
            rows.append(f"serve,{case}/{mode},p50_ttft_cycles,"
                        f"{d['p50_ttft_cycles']:.0f}")
            rows.append(f"serve,{case}/{mode},p99_ttft_cycles,"
                        f"{d['p99_ttft_cycles']:.0f}")
            rows.append(f"serve,{case}/{mode},p99_ttft_s,"
                        f"{d['p99_ttft_s']:.4f}")
            rows.append(f"serve,{case}/{mode},erased_bits,"
                        f"{d['erased_bits']:.0f}")
        rows.append(f"serve,{case},speedup_cycles,"
                    f"{rec['speedup_cycles']:.2f}")
    pk = res["paged_kv"]
    rows.append(f"serve,paged_kv,capacity_factor,"
                f"{pk['capacity_factor']:.2f}")
    rows.append(f"serve,paged_kv,peak_pages,{pk['peak_pages']}")
    return rows


if __name__ == "__main__":
    import sys
    for row in main("--full" in sys.argv):
        print(row)
