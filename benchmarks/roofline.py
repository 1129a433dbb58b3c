"""§Roofline — three-term roofline per (arch x shape x mesh) from the
multi-pod dry-run artifacts (benchmarks/results/dryrun/*.json).

  compute    = HLO_FLOPs        / (chips x 197 TFLOP/s bf16)
  memory     = HLO_bytes        / (chips x 819 GB/s HBM)
  collective = collective_bytes / (chips x 50 GB/s/link ICI)

HLO_FLOPs uses the trip-count-scaled dot/conv census (launch/hlo_analysis)
because XLA's cost_analysis counts scan bodies once. HLO_bytes comes from
cost_analysis "bytes accessed" (per-device; XLA reports the partitioned
program). collective_bytes is the hlo census sum over all-gather /
all-reduce / reduce-scatter / all-to-all / collective-permute result
bytes, already multiplied by loop trip counts.

MODEL_FLOPS = 6*N*D (dense) or 6*N_active*D (MoE) for training;
2*N*D forward-only for prefill; 2*N*D_new for decode (D_new = new tokens).
"""
from __future__ import annotations

import glob
import json
import math
import os

from repro.configs import SHAPES, get_arch

RESULTS = os.path.join(os.path.dirname(__file__), "results")
DRYRUN = os.path.join(RESULTS, "dryrun")

PEAK_FLOPS = 197e12          # bf16 / chip (TPU v5e)
HBM_BW = 819e9               # B/s / chip
ICI_BW = 50e9                # B/s / link


def param_count(cfg, active_only: bool = False) -> float:
    """Analytic parameter count (embedding + per-layer) for MODEL_FLOPS."""
    d, v = cfg.d_model, cfg.vocab_size
    hd = cfg.hd
    emb = v * d * (1 if cfg.tie_embed else 2)         # + untied head
    if cfg.is_mla:
        h, r, dn, dr = cfg.n_heads, cfg.kv_lora_rank, cfg.qk_nope_dim, \
            cfg.qk_rope_dim
        attn = d * h * (dn + dr) + d * (r + dr) \
            + r * h * (dn + cfg.v_head_dim) + h * cfg.v_head_dim * d
    else:
        attn = d * (cfg.n_heads * hd) + 2 * d * (cfg.n_kv_heads * hd) \
            + (cfg.n_heads * hd) * d
    if cfg.is_moe:
        n_e = cfg.top_k if active_only else cfg.n_experts
        mlp = 3 * d * cfg.expert_ff * n_e + d * cfg.n_experts  # + router
        mlp += 3 * d * cfg.expert_ff * cfg.shared_experts
    elif cfg.family == "ssm":
        # xlstm mLSTM: qkv + gates + out
        di = cfg.ssm_expand * d
        mlp = 2 * (d * di) + 3 * di * di // max(cfg.n_heads, 1) + di * d
    else:
        mlp = 3 * d * cfg.d_ff if cfg.d_ff else 4 * d * d
    n_layers = cfg.n_layers + cfg.enc_layers
    dense = cfg.first_dense * (3 * d * cfg.d_ff - mlp)  # leading dense FFNs
    return float(emb + n_layers * (attn + mlp) + dense)


def model_flops(cfg, shape_cfg) -> float:
    """6*N*D train / 2*N*D prefill / 2*N*B decode (per step)."""
    n_act = param_count(cfg, active_only=True) - cfg.vocab_size * cfg.d_model
    toks = shape_cfg.global_batch * shape_cfg.seq_len
    if shape_cfg.kind == "train":
        return 6.0 * n_act * toks
    if shape_cfg.kind == "prefill":
        return 2.0 * n_act * toks
    return 2.0 * n_act * shape_cfg.global_batch      # one new token


def roofline_row(rec: dict) -> dict:
    cfg = get_arch(rec["arch"])
    shape = SHAPES[rec["shape"]]
    chips = rec["n_chips"]
    # The compiled HLO is the post-SPMD PER-DEVICE program, so the census
    # FLOPs / bytes / collective bytes are already per chip: the roofline
    # terms divide by single-chip peaks, and the useful-compute ratio
    # compares MODEL_FLOPS against census x chips.
    flops = rec.get("flops", 0.0)
    mem_bytes = rec.get("xla_bytes_accessed", 0.0)
    coll = rec.get("collective_bytes", 0.0)

    t_compute = flops / PEAK_FLOPS
    t_memory = mem_bytes / HBM_BW
    t_coll = coll / ICI_BW
    terms = {"compute": t_compute, "memory": t_memory, "collective": t_coll}
    dominant = max(terms, key=terms.get)
    mf = model_flops(cfg, shape)
    return {
        "arch": rec["arch"], "shape": rec["shape"], "mesh": rec["mesh"],
        "t_compute_s": t_compute, "t_memory_s": t_memory,
        "t_collective_s": t_coll, "dominant": dominant,
        "model_flops": mf,
        "useful_ratio": mf / (flops * chips) if flops else 0.0,
        "hlo_flops_per_chip": flops, "bytes": mem_bytes, "coll_bytes": coll,
    }


def load(mesh: str = "16x16", tag: str = "", base_dir: str = DRYRUN) -> list[dict]:
    rows = []
    for f in sorted(glob.glob(os.path.join(base_dir, f"*_{mesh}{tag}.json"))):
        rec = json.load(open(f))
        if rec.get("ok") and (rec.get("tag", "") == tag.lstrip("_")):
            rows.append(roofline_row(rec))
    return rows


def main() -> list[str]:
    rows = load("16x16")
    out = []
    with open(os.path.join(RESULTS, "roofline.json"), "w") as f:
        json.dump(rows, f, indent=1)
    for r in rows:
        out.append(
            f"roofline,{r['arch']},{r['shape']},"
            f"compute={r['t_compute_s']:.3e},memory={r['t_memory_s']:.3e},"
            f"collective={r['t_collective_s']:.3e},dominant={r['dominant']},"
            f"useful={r['useful_ratio']:.3f}")
    # baseline vs optimized delta (if the post-§Perf sweep exists)
    opt_dir = os.path.join(RESULTS, "dryrun_opt")
    if os.path.isdir(opt_dir):
        opt = {(r["arch"], r["shape"]): r for r in
               load("16x16", base_dir=opt_dir)}
        with open(os.path.join(RESULTS, "roofline_opt.json"), "w") as f:
            json.dump(list(opt.values()), f, indent=1)
        for r in rows:
            o = opt.get((r["arch"], r["shape"]))
            if not o:
                continue
            dom = r["dominant"]
            b, a = r[f"t_{dom}_s"], o[f"t_{dom}_s"]
            if b > 0:
                out.append(f"roofline-opt,{r['arch']},{r['shape']},"
                           f"{dom}_delta,{(a - b) / b:+.1%}")
    return out


if __name__ == "__main__":
    for r in main():
        print(r)
