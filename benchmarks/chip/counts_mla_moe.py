"""Operations and bytes of a serve window of a latent-attention MoE model
(DeepSeek-V2), counted from shapes, from the engine's per-request
counters and from its held-expert counters (`ServeReport.expert_rows`,
`expert_rows_max`, `expert_groups`), never from the program's cost model.

Per token and layer, with H heads, latent r, nope/rope/value dims dn,
dr, dv: the attention's matmul weights are
    d*H*(dn+dr) + d*(r+dr) + H*r*(dn+dv) + H*dv*d
(q; latent and k_pe; the latent's up-projection, which the absorbed form
applies to the query and the output instead of the keys; o). A dense
layer adds 3*d*ff, an MoE layer d*E (the router over every expert) and
3*d*sh (the shared experts), and each row routed to a held expert
3*d*eff. A token costs twice its weights in FLOPs; a sampled logit 2*d*V.

Served attention is the absorbed form: one key head of width r + dr
whose value is its first r columns, so a query over n valid keys costs
2*H*(r+dr)*n (scores) + 2*H*r*n (weighted sum), and reads each key's
(r+dr) * 2 bytes once per layer.
"""
from __future__ import annotations

import dataclasses

from benchmarks.chip import counts

#: bytes of a stored weight, cached latent element and activation
#: (bfloat16), and of a float32 kernel output
W_BYTES = 2
KV_BYTES = 2
ACT_BYTES = 2
OUT_BYTES = 4


@dataclasses.dataclass(frozen=True)
class Dims:
    layers: int
    dense_layers: int
    d: int
    heads: int
    r: int
    dn: int
    dr: int
    dv: int
    ff: int
    expert_ff: int
    shared_ff: int
    experts: int
    held: int
    vocab: int

    @classmethod
    def of(cls, conf: dict) -> "Dims":
        """From a configuration file's published keys; `held` is the
        file's `n_routed_experts` (the experts this chip holds),
        `experts` the published count the router spans."""
        return cls(
            layers=conf["num_hidden_layers"],
            dense_layers=conf["first_k_dense_replace"],
            d=conf["hidden_size"], heads=conf["num_attention_heads"],
            r=conf["kv_lora_rank"], dn=conf["qk_nope_head_dim"],
            dr=conf["qk_rope_head_dim"], dv=conf["v_head_dim"],
            ff=conf["intermediate_size"],
            expert_ff=conf["moe_intermediate_size"],
            shared_ff=conf["n_shared_experts"]
            * conf["moe_intermediate_size"],
            experts=conf["published"]["n_routed_experts"],
            held=conf["n_routed_experts"], vocab=conf["vocab_size"])

    @property
    def moe_layers(self) -> int:
        return self.layers - self.dense_layers

    @property
    def row(self) -> int:
        """Width of one cached latent row, [c | k_pe]."""
        return self.r + self.dr


def token_flops(m: Dims) -> float:
    """Forward matmul FLOPs of one token through every layer, the held
    experts' rows and the LM head excluded."""
    attn = (m.d * m.heads * (m.dn + m.dr) + m.d * m.row
            + m.heads * m.r * (m.dn + m.dv) + m.heads * m.dv * m.d)
    return 2.0 * (m.layers * attn + m.dense_layers * 3 * m.d * m.ff
                  + m.moe_layers * (m.d * m.experts + 3 * m.d * m.shared_ff))


def expert_flops(m: Dims, rows: float) -> float:
    """FLOPs of `rows` rows through a held expert's SwiGLU."""
    return 2.0 * 3 * m.d * m.expert_ff * rows


def expert_bytes(m: Dims, rows: float, groups: float) -> float:
    """Least bytes of the three grouped matmuls: each held expert that
    had a row reads its three weight matrices once (`groups` counts them
    over layers and steps); each row reads its input twice (gate, up)
    and the down projection's bfloat16 input once, and writes two
    float32 rows of eff and one of d."""
    weights = groups * 3 * m.d * m.expert_ff * W_BYTES
    acts = rows * (2 * m.d * ACT_BYTES + 2 * m.expert_ff * OUT_BYTES
                   + m.expert_ff * ACT_BYTES + m.d * OUT_BYTES)
    return weights + acts


def _attn_flops_per_key(m: Dims) -> float:
    return 2.0 * m.heads * (m.row + m.r)


def attn_decode_work(m: Dims, contexts) -> tuple:
    """(FLOPs, bytes) of one-token absorbed attention over each valid
    context length in `contexts`, over all layers: the latent rows read
    once, the absorbed query read (bfloat16) and the output written
    (float32, the full row width)."""
    n = float(sum(contexts))
    rows = len(contexts)
    flops = _attn_flops_per_key(m) * n * m.layers
    byts = m.layers * (n * m.row * KV_BYTES
                       + rows * m.heads * m.row * (ACT_BYTES + OUT_BYTES))
    return flops, byts


def attn_prefill_work(m: Dims, chunks) -> tuple:
    """(FLOPs, bytes) of causal absorbed attention of prompt chunks
    given as (start, n_valid), over all layers (see
    `counts.attn_prefill_work` for the key counting)."""
    keys = rows = toks = 0.0
    for start, nv in chunks:
        keys += nv * start + nv * (nv + 1) / 2.0
        rows += start + nv
        toks += nv
    flops = _attn_flops_per_key(m) * keys * m.layers
    byts = m.layers * (rows * m.row * KV_BYTES
                       + toks * m.heads * m.row * (ACT_BYTES + OUT_BYTES))
    return flops, byts


def expert_counters(run):
    """(rows, busiest expert's rows, experts with a row) summed over the
    window's waves, or None where the engine keeps no such counters."""
    reps = [w.report for w in run.cell.waves]
    if not reps or not all(hasattr(r, "expert_rows") for r in reps):
        return None
    return (sum(r.expert_rows for r in reps),
            sum(r.expert_rows_max for r in reps),
            sum(r.expert_groups for r in reps))


def serve_flops(m: Dims, w: counts.ServeWork, expert_rows: float) -> float:
    """Useful FLOPs of a serve window: every valid token through every
    layer, the held experts' routed rows, the sampled logits, and the
    attention over each valid context."""
    return (token_flops(m) * (w.prefill_tokens + w.decode_tokens)
            + expert_flops(m, expert_rows)
            + 2.0 * m.d * m.vocab * (w.first_tokens + w.decode_sampled)
            + attn_prefill_work(m, w.chunks)[0]
            + attn_decode_work(m, w.decode_contexts)[0])
