"""Multi-head latent attention (MLA; DeepSeek-V2, arXiv 2405.04434, §2.1)
with YaRN RoPE on its rope half (arXiv 2309.00071).

Per token x, with r = kv_lora_rank, heads H of dn (nope) + dr (rope)
query/key dims and dv value dims:

    q = x W_q -> per head [q_nope | q_pe]
    [c | k_pe] = x W_kva;   c = RMSNorm(c)            (the latent, r wide)
    [k_nope | v] = c W_kvb  -> per head
    q_pe, k_pe rotated (k_pe is one head shared by all H)
    out = softmax(scale * [q_nope | q_pe] . [k_nope | k_pe]) v,  then W_o

`mla_train` computes this published ("naive") form. Serving caches only
the normalised latent and the rotated k_pe, one row [c | k_pe] of
r + dr per token and layer in the paged pool (`pool_width`), and
computes the absorbed form, which gives the same scores and outputs:

    q_lat[h] = q_nope[h] W_UK[h]^T          (W_kvb = [W_UK | W_UV] per head)
    scores   = q_lat . c + q_pe . k_pe = [q_lat | q_pe] . [c | k_pe]
    out[h]   = (sum p c) W_UV[h]

that is, multi-query attention with one key head of width r + dr whose
value is its first r columns: the shared paged kernels run it with one
key/value head (`mla_decode_paged`, `mla_prefill_paged`).
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from repro.models import layers as L
from repro.nn import Spec, constrain


def mla_specs(cfg) -> dict:
    d, H, r = cfg.d_model, cfg.n_heads, cfg.kv_lora_rank
    dn, dr, dv = cfg.qk_nope_dim, cfg.qk_rope_dim, cfg.v_head_dim
    return {
        "wq": L.linear_specs(d, H * (dn + dr), ("embed", "qkv")),
        "wkv_a": L.linear_specs(d, r + dr, ("embed", None)),
        "kv_norm": {"scale": Spec((r,), (None,), init="ones")},
        "wkv_b": L.linear_specs(r, H * (dn + dv), (None, "qkv")),
        "wo": L.linear_specs(H * dv, d, ("qkv", "embed")),
    }


def pool_width(cfg) -> int:
    """Width of one cached row: the latent and the rotated k_pe."""
    return cfg.kv_lora_rank + cfg.qk_rope_dim


def softmax_scale(cfg) -> float:
    """(dn + dr)^-1/2, times YaRN's mscale(mscale_all_dim)^2."""
    s = (cfg.qk_nope_dim + cfg.qk_rope_dim) ** -0.5
    rs = cfg.rope_scaling
    if rs is not None and rs.mscale_all_dim:
        s *= L.yarn_mscale(rs.factor, rs.mscale_all_dim) ** 2
    return s


def _project(p, x, cfg, positions) -> tuple:
    """x [B,S,d] -> q_nope [B,S,H,dn], q_pe [B,S,H,dr] (rotated),
    c [B,S,r] (normalised), k_pe [B,S,dr] (rotated)."""
    B, S, _ = x.shape
    H, r = cfg.n_heads, cfg.kv_lora_rank
    dn, dr = cfg.qk_nope_dim, cfg.qk_rope_dim
    q = L.linear(p["wq"], x).reshape(B, S, H, dn + dr)
    kva = L.linear(p["wkv_a"], x)
    c = L.apply_norm(p["kv_norm"], kva[..., :r], "rmsnorm", cfg.norm_eps)
    sin, cos = L.rope_angles(positions, dr, cfg.rope_theta, cfg.rope_scaling)
    q_pe = L.apply_rope(q[..., dn:], sin, cos)
    k_pe = L.apply_rope(kva[..., None, r:], sin, cos)[:, :, 0]
    return q[..., :dn], q_pe, c, k_pe


def mla_train(p, x, cfg, positions=None, causal=True, window=0):
    """The published form over a whole sequence: x [B,S,d] -> [B,S,d]."""
    B, S, _ = x.shape
    H, dn, dr, dv = cfg.n_heads, cfg.qk_nope_dim, cfg.qk_rope_dim, \
        cfg.v_head_dim
    if positions is None:
        positions = jnp.broadcast_to(jnp.arange(S)[None], (B, S))
    q_nope, q_pe, c, k_pe = _project(p, x, cfg, positions)
    kv = L.linear(p["wkv_b"], c).reshape(B, S, H, dn + dv)
    q = jnp.concatenate([q_nope, q_pe], -1)
    k = jnp.concatenate([kv[..., :dn], jnp.broadcast_to(
        k_pe[:, :, None], (B, S, H, dr))], -1)
    v = jnp.pad(kv[..., dn:], ((0, 0), (0, 0), (0, 0), (0, dn + dr - dv)))
    out = L.chunked_attention(q, k, v, cfg, causal=causal, window=window,
                              scale=softmax_scale(cfg))[..., :dv]
    out = out.reshape(B, S, H * dv)
    return constrain(L.linear(p["wo"], out), "batch", "seq", "act_embed")


def _absorbed_query(p, q_nope, q_pe, cfg):
    """[q_nope W_UK^T | q_pe] per head: [..., H, r + dr]."""
    H, r, dn, dv = cfg.n_heads, cfg.kv_lora_rank, cfg.qk_nope_dim, \
        cfg.v_head_dim
    w_uk = p["wkv_b"]["w"].reshape(r, H, dn + dv)[..., :dn]
    q_lat = jnp.einsum("...hn,rhn->...hr", q_nope,
                       w_uk.astype(q_nope.dtype))
    return jnp.concatenate([q_lat, q_pe.astype(q_lat.dtype)], -1)


def _absorbed_out(p, o, cfg, dtype):
    """Attention output over the pool [..., H, r + dr] -> W_UV per head,
    flattened to [..., H * dv]."""
    H, r, dn, dv = cfg.n_heads, cfg.kv_lora_rank, cfg.qk_nope_dim, \
        cfg.v_head_dim
    w_uv = p["wkv_b"]["w"].reshape(r, H, dn + dv)[..., dn:]
    out = jnp.einsum("...hr,rhv->...hv", o[..., :r].astype(dtype),
                     w_uv.astype(dtype))
    return out.reshape(out.shape[:-2] + (H * dv,))


def mla_decode_slots(p, x, cfg, pool, layer, indices, pages, kernel: bool):
    """One token per slot: x [B,1,d] at per-slot positions `indices` [B];
    its [c | k_pe] row is written in place to layer `layer` of the
    stacked pool [L,n_pages,1,page,r+dr] through each slot's page table
    (inactive rows dropped), then the absorbed query attends the slot's
    prefix there. Returns (out [B,1,d], new pool)."""
    if pages is None:
        L.no_pages()
    B = x.shape[0]
    positions = indices[:, None]
    with jax.named_scope("mla"):
        q_nope, q_pe, c, k_pe = _project(p, x, cfg, positions)
        q = _absorbed_query(p, q_nope, q_pe, cfg)              # [B,1,H,D]
        row = jnp.concatenate([c, k_pe], -1)[:, :, None]       # [B,1,1,D]
        keep = jnp.ones((B, 1), bool) if pages.get("active") is None \
            else pages["active"][:, None]
        tables = pages["tables"]
        pool = L.paged_insert(pool, layer, tables, indices, row, keep)
        scale = softmax_scale(cfg)
        if kernel:
            from repro.kernels.decode_attention.ops import mla_decode_paged
            o = mla_decode_paged(q[:, 0].astype(pool.dtype), pool, layer,
                                 tables, indices + 1, scale=scale)
        else:
            view = L.paged_view(pool, layer, tables)
            o = L.decode_attention_jnp(q[:, 0], view, view, indices + 1,
                                       scale=scale)
        out = _absorbed_out(p, o, cfg, x.dtype)[:, None]       # [B,1,H*dv]
    return constrain(L.linear(p["wo"], out), "batch", "seq",
                     "act_embed"), pool


def mla_prefill_slots(p, x, cfg, pool, layer, start, n_valid, pages,
                      kernel: bool):
    """Prompt chunks: x [B,C,d] at positions start[b] + i, written in
    place to layer `layer` of the stacked pool (see `mla_decode_slots`);
    rows past n_valid[b] are masked out of the pool write. Returns
    (out [B,C,d], new pool)."""
    if pages is None:
        L.no_pages()
    B, C, _ = x.shape
    positions = start[:, None] + jnp.arange(C)[None]
    with jax.named_scope("mla"):
        q_nope, q_pe, c, k_pe = _project(p, x, cfg, positions)
        q = _absorbed_query(p, q_nope, q_pe, cfg)              # [B,C,H,D]
        row = jnp.concatenate([c, k_pe], -1)[:, :, None]       # [B,C,1,D]
        keep = jnp.arange(C)[None, :] < n_valid[:, None]
        if pages.get("active") is not None:
            keep &= pages["active"][:, None]
        tables = pages["tables"]
        pool = L.paged_insert(pool, layer, tables, start, row, keep)
        scale = softmax_scale(cfg)
        if kernel:
            from repro.kernels.prefill_attention.ops import mla_prefill_paged
            o = mla_prefill_paged(q.astype(pool.dtype), pool, layer, tables,
                                  start, scale=scale)
        else:
            view = L.paged_view(pool, layer, tables)
            o = L.prefill_attention_jnp(q, view, view, start, scale=scale)
        out = _absorbed_out(p, o, cfg, x.dtype)                # [B,C,H*dv]
    return constrain(L.linear(p["wo"], out), "batch", "seq",
                     "act_embed"), pool
