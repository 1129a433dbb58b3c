"""Operations and bytes of the work a serve window did, counted from
shapes and from the engine's per-request counters, never from the
program's own cost model (which counts recomputation and which a change
to the program could alter).

A dense decoder layer of width d, H query heads and Hkv key/value heads
of size hd, and feed-forward width ff holds
    d*H*hd + 2*d*Hkv*hd + H*hd*d + 3*d*ff
matmul weights (q, k, v, o; gate, up, down), so a token costs twice that
in multiply-adds. Attention over a valid context of n keys costs
4*H*hd*n (scores and the weighted sum), and reads the n keys and values
of each layer. A sampled logit costs 2*d*V for the LM head.
"""
from __future__ import annotations

import dataclasses

#: bytes of one cached key/value element and one activation (bfloat16)
KV_BYTES = 2
ACT_BYTES = 2


@dataclasses.dataclass(frozen=True)
class Dims:
    layers: int
    d: int
    heads: int
    kv_heads: int
    hd: int
    ff: int
    vocab: int

    @classmethod
    def of(cls, conf: dict) -> "Dims":
        """From a configuration file's published keys."""
        heads = conf["num_attention_heads"]
        return cls(layers=conf["num_hidden_layers"], d=conf["hidden_size"],
                   heads=heads, kv_heads=conf["num_key_value_heads"],
                   hd=conf.get("head_dim") or conf["hidden_size"] // heads,
                   ff=conf["intermediate_size"], vocab=conf["vocab_size"])


def matmul_flops_per_token(m: Dims) -> float:
    """Forward matmul FLOPs of one token through every layer, LM head
    excluded."""
    per_layer = (m.d * m.heads * m.hd + 2 * m.d * m.kv_heads * m.hd
                 + m.heads * m.hd * m.d + 3 * m.d * m.ff)
    return 2.0 * m.layers * per_layer


def lm_head_flops(m: Dims) -> float:
    return 2.0 * m.d * m.vocab


def attn_decode_work(m: Dims, contexts) -> tuple:
    """(FLOPs, bytes) of one-token attention over each valid context
    length in `contexts`, over all layers: the keys and values read, the
    query read and the output written."""
    n = float(sum(contexts))
    rows = len(contexts)
    flops = 4.0 * m.heads * m.hd * n * m.layers
    byts = m.layers * (2.0 * n * m.kv_heads * m.hd * KV_BYTES
                       + 2.0 * rows * m.heads * m.hd * ACT_BYTES)
    return flops, byts


def attn_prefill_work(m: Dims, chunks) -> tuple:
    """(FLOPs, bytes) of chunk attention, over all layers, for chunks
    given as (start, n_valid): query i of a chunk attends start + i + 1
    keys (causal), and the chunk reads the start + n_valid keys and
    values and its queries, and writes its outputs."""
    keys = 0.0
    kv = 0.0
    toks = 0.0
    for start, nv in chunks:
        keys += nv * start + nv * (nv + 1) / 2.0
        kv += start + nv
        toks += nv
    flops = 4.0 * m.heads * m.hd * keys * m.layers
    byts = m.layers * (2.0 * kv * m.kv_heads * m.hd * KV_BYTES
                       + 2.0 * toks * m.heads * m.hd * ACT_BYTES)
    return flops, byts


@dataclasses.dataclass
class ServeWork:
    """The useful work of a set of finished requests under chunked
    prefill: every prompt chunk, every decode step's valid context, and
    the logits that were sampled."""
    chunks: list            # (start, n_valid) per prompt chunk
    decode_contexts: list   # valid context length per decode step row
    prefill_tokens: int
    decode_tokens: int
    first_tokens: int       # logits sampled at the end of a prompt
    decode_sampled: int     # logits sampled by decode steps


def serve_work(requests, chunk: int) -> ServeWork:
    """`requests`: (prompt_len, generated tokens) pairs of finished
    requests. A prompt of P tokens enters in chunks of at most `chunk`;
    its first token comes out of the last chunk; generated token k >= 2
    comes out of a decode step at position P + k - 2, attending P + k - 1
    keys."""
    w = ServeWork([], [], 0, 0, 0, 0)
    for p, n in requests:
        for s in range(0, p, chunk):
            w.chunks.append((s, min(chunk, p - s)))
        w.prefill_tokens += p
        if n >= 1:
            w.first_tokens += 1
        for k in range(2, n + 1):
            w.decode_contexts.append(p + k - 1)
        w.decode_tokens += max(0, n - 1)
        w.decode_sampled += max(0, n - 1)
    return w


def prefill_flops(m: Dims, w: ServeWork) -> float:
    return (matmul_flops_per_token(m) * w.prefill_tokens
            + lm_head_flops(m) * w.first_tokens
            + attn_prefill_work(m, w.chunks)[0])


def decode_flops(m: Dims, w: ServeWork) -> float:
    return (matmul_flops_per_token(m) * w.decode_tokens
            + lm_head_flops(m) * w.decode_sampled
            + attn_decode_work(m, w.decode_contexts)[0])


def roofline_share(flops: float, byts: float, seconds: float,
                   peak) -> tuple:
    """(share of the roofline in %, "compute" | "memory"): the least
    time the chip could take for the work, the larger of FLOPs over peak
    FLOP/s and bytes over peak bytes/s, over the time it took."""
    t_flops = flops / peak.flops_bf16
    t_bytes = byts / peak.hbm_bytes_s
    bound = "compute" if t_flops >= t_bytes else "memory"
    return 100.0 * max(t_flops, t_bytes) / seconds, bound


def window_work(run) -> ServeWork:
    """The useful work of every request a serve run's window finished."""
    return serve_work([(q.prompt_len, len(r.tokens))
                       for _, q, r in run.cell.results()],
                      int(run.mix["engine"]["chunk_size"]))
