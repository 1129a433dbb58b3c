"""Shared layer library: norms, RoPE, GQA attention (chunked train/prefill +
cached decode), gated MLP, embeddings. Pure functions over Spec-declared
param dicts; activation shardings via logical-axis constraints."""
from __future__ import annotations

import math
from functools import partial

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from repro.kernels import resolve_interpret
from repro.nn import Spec, constrain

NEG_INF = -1e30
import os as _os
USE_DIST_DECODE = _os.environ.get("REPRO_DIST_DECODE", "0") == "1"


# ---------------------------------------------------------------- norms
def norm_specs(d: int, kind: str = "rmsnorm") -> dict:
    s = {"scale": Spec((d,), ("embed",), init="ones")}
    if kind == "layernorm":
        s["bias"] = Spec((d,), ("embed",), init="zeros")
    return s


def apply_norm(p: dict, x: jax.Array, kind: str = "rmsnorm",
               eps: float = 1e-5) -> jax.Array:
    xf = x.astype(jnp.float32)
    if kind == "rmsnorm":
        var = jnp.mean(jnp.square(xf), axis=-1, keepdims=True)
        y = xf * jax.lax.rsqrt(var + eps) * p["scale"].astype(jnp.float32)
    else:
        mean = jnp.mean(xf, axis=-1, keepdims=True)
        var = jnp.var(xf, axis=-1, keepdims=True)
        y = (xf - mean) * jax.lax.rsqrt(var + eps)
        y = y * p["scale"].astype(jnp.float32) + p["bias"].astype(jnp.float32)
    return y.astype(x.dtype)


# ---------------------------------------------------------------- embeddings
def embed_specs(vocab: int, d: int) -> dict:
    return {"table": Spec((vocab, d), ("vocab", "embed"), init="embed",
                          scale=0.02)}


def embed_lookup(p: dict, tokens: jax.Array, dtype) -> jax.Array:
    x = jnp.take(p["table"].astype(dtype), tokens, axis=0)
    return constrain(x, "batch", "seq", "act_embed")


def unembed(p: dict, x: jax.Array) -> jax.Array:
    logits = jnp.einsum("...d,vd->...v", x, p["table"].astype(x.dtype))
    return constrain(logits, "batch", "seq", "vocab")


# ---------------------------------------------------------------- linear
def linear_specs(d_in: int, d_out: int, axes=("embed", "mlp"),
                 bias: bool = False, scale: float = 1.0) -> dict:
    s = {"w": Spec((d_in, d_out), axes, init="fan_in", scale=scale)}
    if bias:
        s["b"] = Spec((d_out,), (axes[1],), init="zeros")
    return s


def linear(p: dict, x: jax.Array) -> jax.Array:
    y = x @ p["w"].astype(x.dtype)
    if "b" in p:
        y = y + p["b"].astype(x.dtype)
    return y


# ---------------------------------------------------------------- RoPE
def yarn_mscale(factor: float, mscale: float) -> float:
    """YaRN's attention temperature term: 0.1 * mscale * ln(factor) + 1
    (1 when the context is not stretched)."""
    return 0.1 * mscale * math.log(factor) + 1.0 if factor > 1 else 1.0


def rope_inv_freq(dim: int, theta: float, scaling=None) -> jax.Array:
    """[dim/2] fp32 inverse frequencies; with a `RopeScaling` (YaRN) they
    ramp linearly, over the pair indices between the correction dims of
    `beta_fast` and `beta_slow` rotations, from the original frequency
    to the frequency over `factor`."""
    base = theta ** (jnp.arange(0, dim, 2, dtype=jnp.float32) / dim)
    if scaling is None:
        return 1.0 / base

    def corr_dim(rotations):
        return (dim * math.log(scaling.original_max_len
                               / (rotations * 2 * math.pi))
                / (2 * math.log(theta)))

    lo = max(math.floor(corr_dim(scaling.beta_fast)), 0)
    hi = min(math.ceil(corr_dim(scaling.beta_slow)), dim - 1)
    if lo == hi:
        hi += 0.001
    ramp = jnp.clip((jnp.arange(dim // 2, dtype=jnp.float32) - lo)
                    / (hi - lo), 0.0, 1.0)
    extra = 1.0 - ramp            # 1: keep the original frequency
    return (1.0 / (scaling.factor * base)) * ramp + (1.0 / base) * extra


def rope_angles(positions: jax.Array, dim: int, theta: float,
                scaling=None) -> tuple:
    """positions [...,S] -> (sin, cos) each [...,S,dim/2] fp32, scaled
    by YaRN's cos/sin factor under a `RopeScaling`."""
    freqs = rope_inv_freq(dim, theta, scaling)
    ang = positions.astype(jnp.float32)[..., None] * freqs
    m = 1.0 if scaling is None else (
        yarn_mscale(scaling.factor, scaling.mscale)
        / yarn_mscale(scaling.factor, scaling.mscale_all_dim))
    return jnp.sin(ang) * m, jnp.cos(ang) * m


def apply_rope(x: jax.Array, sin: jax.Array, cos: jax.Array,
               fraction: float = 1.0) -> jax.Array:
    """x [B,S,H,hd]; rotate the first `fraction` of the head dim
    (fraction=0.5 reproduces ChatGLM's 2D/partial RoPE)."""
    hd = x.shape[-1]
    rot = int(hd * fraction)
    rot -= rot % 2
    xr, xp = x[..., :rot], x[..., rot:]
    x1, x2 = xr[..., ::2], xr[..., 1::2]
    sin = sin[..., : rot // 2][:, :, None, :].astype(jnp.float32)
    cos = cos[..., : rot // 2][:, :, None, :].astype(jnp.float32)
    x1f, x2f = x1.astype(jnp.float32), x2.astype(jnp.float32)
    o1 = x1f * cos - x2f * sin
    o2 = x2f * cos + x1f * sin
    out = jnp.stack([o1, o2], axis=-1).reshape(xr.shape).astype(x.dtype)
    return jnp.concatenate([out, xp], axis=-1) if rot < hd else out


# ---------------------------------------------------------------- attention
def attention_specs(cfg) -> dict:
    d, hd = cfg.d_model, cfg.hd
    return {
        "wq": linear_specs(d, cfg.n_heads * hd, ("embed", "qkv"), bias=cfg.qkv_bias),
        "wk": linear_specs(d, cfg.n_kv_heads * hd, ("embed", "qkv"), bias=cfg.qkv_bias),
        "wv": linear_specs(d, cfg.n_kv_heads * hd, ("embed", "qkv"), bias=cfg.qkv_bias),
        "wo": linear_specs(cfg.n_heads * hd, d, ("qkv", "embed")),
    }


def _qkv(p, x, cfg, positions):
    B, S, _ = x.shape
    hd = cfg.hd
    q = linear(p["wq"], x).reshape(B, S, cfg.n_heads, hd)
    k = linear(p["wk"], x).reshape(B, S, cfg.n_kv_heads, hd)
    v = linear(p["wv"], x).reshape(B, S, cfg.n_kv_heads, hd)
    if cfg.rope_theta:
        sin, cos = rope_angles(positions, hd, cfg.rope_theta)
        q = apply_rope(q, sin, cos, cfg.rope_fraction)
        k = apply_rope(k, sin, cos, cfg.rope_fraction)
    q = constrain(q, "batch", "seq", "heads", None)
    k = constrain(k, "batch", "seq", "kv_heads", None)
    v = constrain(v, "batch", "seq", "kv_heads", None)
    return q, k, v


def chunked_attention(q, k, v, cfg, causal: bool = True,
                      window: int = 0, kv_offset: int = 0,
                      scale: float | None = None) -> jax.Array:
    """Memory-bounded multi-query-block attention with online softmax.

    q [B,Sq,H,hd], k/v [B,Skv,Hkv,hd]. Scans query chunks (outer) and key
    chunks (inner) keeping running (max, sum, acc) — an XLA-level flash
    attention; scores never materialize beyond [B,H,cq,ck]. `scale`
    multiplies the scores (default 1/sqrt(hd)).
    """
    B, Sq, H, hd = q.shape
    Skv = k.shape[1]
    G = H // k.shape[2]
    cq = min(cfg.attn_chunk, Sq)
    ck = min(cfg.attn_chunk, Skv)
    # pad to chunk multiples (e.g. VLM prefix makes S non-divisible);
    # padded keys are masked out below, padded queries sliced off at the end.
    Sq0, Skv0 = Sq, Skv
    pq = (-Sq) % cq
    pk = (-Skv) % ck
    if pq:
        q = jnp.pad(q, ((0, 0), (0, pq), (0, 0), (0, 0)))
        Sq += pq
    if pk:
        k = jnp.pad(k, ((0, 0), (0, pk), (0, 0), (0, 0)))
        v = jnp.pad(v, ((0, 0), (0, pk), (0, 0), (0, 0)))
        Skv += pk
    nq, nk = Sq // cq, Skv // ck
    if scale is None:
        scale = 1.0 / math.sqrt(hd)

    kh = k.reshape(B, nk, ck, k.shape[2], hd)
    vh = v.reshape(B, nk, ck, v.shape[2], hd)
    qh = q.reshape(B, nq, cq, H, hd)

    q_pos = kv_offset + jnp.arange(Sq).reshape(nq, cq)
    k_pos = jnp.arange(Skv).reshape(nk, ck)

    def q_block(carry, inp):
        qb, qp = inp  # [B,cq,H,hd], [cq]

        def kv_block(st, kin):
            m, s, acc = st
            kb, vb, kp = kin  # [B,ck,Hkv,hd], [B,ck,Hkv,hd], [ck]
            kbg = jnp.repeat(kb, G, axis=2)
            vbg = jnp.repeat(vb, G, axis=2)
            logits = jnp.einsum("bqhd,bkhd->bhqk", qb, kbg) * scale
            mask = (kp < Skv0)[None, :] & jnp.ones((cq, 1), bool)
            if causal:
                mask &= qp[:, None] >= kp[None, :]
            if window:
                mask &= qp[:, None] - kp[None, :] < window
            logits = jnp.where(mask[None, None], logits.astype(jnp.float32), NEG_INF)
            bm = jnp.maximum(m, logits.max(-1))
            p = jnp.exp(logits - bm[..., None])
            corr = jnp.exp(m - bm)
            s = s * corr + p.sum(-1)
            acc = acc * corr[..., None] + jnp.einsum(
                "bhqk,bkhd->bhqd", p.astype(qb.dtype), vbg).astype(jnp.float32)
            return (bm, s, acc), None

        m0 = jnp.full((B, H, cq), NEG_INF, jnp.float32)
        s0 = jnp.zeros((B, H, cq), jnp.float32)
        a0 = jnp.zeros((B, H, cq, hd), jnp.float32)
        (m, s, acc), _ = jax.lax.scan(
            kv_block, (m0, s0, a0),
            (kh.swapaxes(0, 1), vh.swapaxes(0, 1), k_pos))
        out = acc / jnp.maximum(s[..., None], 1e-30)
        return carry, out.swapaxes(1, 2).astype(q.dtype)  # [B,cq,H,hd]

    _, outs = jax.lax.scan(q_block, None, (qh.swapaxes(0, 1), q_pos))
    out = outs.swapaxes(0, 1).reshape(B, Sq, H, hd)
    return out[:, :Sq0]


def decode_attention_jnp(q, k_cache, v_cache, length, window: int = 0,
                         offset=0, scale: float | None = None):
    """One-token GQA attention against a cache. q [B,H,hd],
    caches [B,Hkv,S,hd], `length` = count of valid positions — a global
    scalar, or a per-row [B] vector (continuous-batching serving, where
    every slot sits at its own depth). `offset` = global position of
    cache column 0 (used when the caller pre-slices a window out of a
    longer cache — §Perf-3). `scale` multiplies the scores (default
    1/sqrt(hd))."""
    B, Hkv, S, hd = k_cache.shape
    H = q.shape[1]
    G = H // Hkv
    qf = q.reshape(B, Hkv, G, hd)
    logits = jnp.einsum("bhgd,bhsd->bhgs", qf, k_cache.astype(qf.dtype))
    logits = (logits.astype(jnp.float32) / math.sqrt(hd) if scale is None
              else logits.astype(jnp.float32) * scale)
    pos = offset + jnp.arange(S)
    lth = jnp.asarray(length).reshape(-1, 1)          # [1,1] or [B,1]
    valid = pos[None, :] < lth
    if window:
        valid &= pos[None, :] >= lth - window
    logits = jnp.where(valid[:, None, None, :], logits, NEG_INF)
    w = jax.nn.softmax(logits, axis=-1)
    out = jnp.einsum("bhgs,bhsd->bhgd", w.astype(v_cache.dtype), v_cache)
    return out.reshape(B, H, -1)


def prefill_attention_jnp(q, k_cache, v_cache, start, window: int = 0,
                          scale: float | None = None):
    """Chunk GQA attention against a cache. q [B,C,H,hd] — a C-token
    prompt chunk per row; caches [B,Hkv,S,hd] already holding the
    chunk's own K/V columns; `start` = global position of chunk token 0,
    a scalar or per-row [B] vector (staggered admissions). Query c of
    row b attends cache positions <= start[b] + c, optionally
    sliding-window limited — the multi-query generalisation of
    `decode_attention_jnp` (C=1, start=length-1 coincide bitwise).
    `scale` multiplies the scores (default 1/sqrt(hd))."""
    B, Hkv, S, hd = k_cache.shape
    C, H = q.shape[1], q.shape[2]
    G = H // Hkv
    qf = q.reshape(B, C, Hkv, G, hd)
    logits = jnp.einsum("bchgd,bhsd->bchgs", qf, k_cache.astype(qf.dtype))
    logits = (logits.astype(jnp.float32) / math.sqrt(hd) if scale is None
              else logits.astype(jnp.float32) * scale)
    qpos = jnp.asarray(start).reshape(-1, 1) + jnp.arange(C)[None]  # [B|1,C]
    pos = jnp.arange(S)
    valid = pos[None, None, :] <= qpos[..., None]                   # [B,C,S]
    if window:
        valid &= pos[None, None, :] > qpos[..., None] - window
    logits = jnp.where(valid[:, :, None, None, :], logits, NEG_INF)
    w = jax.nn.softmax(logits, axis=-1)
    out = jnp.einsum("bchgs,bhsd->bchgd", w.astype(v_cache.dtype), v_cache)
    return out.reshape(B, C, H, -1)


# ---------------------------------------------------------------- paged KV
def paged_view(pool, tables):
    """Gather a slot-major dense view [B, Hkv, n_lp*page, hd] out of a
    shared page pool [n_pages, Hkv, page, hd] via per-slot page tables
    [B, n_lp]: logical column c of row b lives at
    pool[tables[b, c // page], :, c % page]. Placeholder table entries
    surface whatever the pool holds there — always masked downstream by
    the valid-prefix length, so they contribute exact zeros."""
    B, n_lp = tables.shape
    n_pages, Hkv, page, hd = pool.shape
    v = pool[tables]                                  # [B, n_lp, Hkv, page, hd]
    return v.transpose(0, 2, 1, 3, 4).reshape(B, Hkv, n_lp * page, hd)


def paged_insert(pool, tables, cols, vals, keep):
    """Scatter vals [B, C, Hkv, hd] into the pool at each slot's logical
    columns `cols` [B, C]; positions with keep=False route out of
    bounds and are dropped. The pool has no batch axis — slots share
    it — so per-row masking (inactive slots, padded chunk tails) must
    happen here at the write, not by a post-hoc batch select."""
    n_pages, Hkv, page, hd = pool.shape
    phys = jnp.take_along_axis(tables, cols // page, axis=1)    # [B, C]
    phys = jnp.where(keep, phys, n_pages)                       # OOB -> drop
    off = cols % page
    return pool.at[phys, :, off, :].set(vals.astype(pool.dtype),
                                        mode="drop")


def use_attn_kernel(kernel: bool | None = None) -> bool:
    """The serving attention route: the Pallas kernels when `kernel` is
    True, the plain jnp attention when False, and — when None — the
    kernels exactly when the program runs on a TPU (elsewhere they
    would only run in the Pallas interpreter)."""
    return not resolve_interpret() if kernel is None else bool(kernel)


def _pages_kernel(pages) -> bool:
    return use_attn_kernel(None if pages is None else pages.get("kernel"))


def decode_attention_slots_paged(q, k_pool, v_pool, tables, lengths,
                                 window: int = 0, kernel: bool = False):
    """Per-slot flash-decode over the shared page pool: q [B,H,hd],
    pools [n_pages,Hkv,page,hd], `tables` [B,n_lp], `lengths` [B].
    `kernel=True` streams pool pages straight off the scalar-prefetched
    page table (Pallas); the jnp route gathers a dense per-slot view
    first — both are bit-equivalent to dense decode on the valid
    prefix."""
    if kernel:
        from repro.kernels.decode_attention.ops import gqa_decode_paged
        return gqa_decode_paged(q, k_pool, v_pool, tables, lengths,
                                window=window).astype(q.dtype)
    return decode_attention_jnp(q, paged_view(k_pool, tables),
                                paged_view(v_pool, tables), lengths,
                                window=window).astype(q.dtype)


def attention_prefill_slots(p, x, cfg, cache_k, cache_v, start, n_valid,
                            window=0, pages=None):
    """Fused chunk prefill: x [B,C,d] — C prompt tokens per slot
    starting at per-row cache position `start` [B]; chunk positions
    >= n_valid[b] are padded tail and masked out of the KV insert. One
    bulk K/V column write + one chunk-vs-cache attention launch replace
    C decode steps. `pages` = {"tables": [B,n_lp], "page_size": int,
    "active": [B] bool or None, "kernel": bool or None} switches the
    cache to the shared page pool and picks the attention route
    (`use_attn_kernel`). Returns (out [B,C,d], new_k, new_v)."""
    B, C, _ = x.shape
    hd = cfg.hd
    positions = start[:, None] + jnp.arange(C)[None]        # [B, C]
    q, k, v = _qkv(p, x, cfg, positions)                    # [B,C,H|Hkv,hd]
    valid = jnp.arange(C)[None, :] < n_valid[:, None]       # [B, C]
    kernel = _pages_kernel(pages)
    if pages is not None:
        keep = valid
        if pages.get("active") is not None:
            keep &= pages["active"][:, None]
        cache_k = paged_insert(cache_k, pages["tables"], positions, k, keep)
        cache_v = paged_insert(cache_v, pages["tables"], positions, v, keep)
        if kernel:
            from repro.kernels.prefill_attention.ops import gqa_prefill_paged
            out = gqa_prefill_paged(q, cache_k, cache_v, pages["tables"],
                                    start, window=window)
        else:
            out = prefill_attention_jnp(q, paged_view(cache_k, pages["tables"]),
                                        paged_view(cache_v, pages["tables"]),
                                        start, window=window)
    else:
        S = cache_k.shape[2]
        rows = jnp.arange(B)[:, None]
        cols = jnp.where(valid, positions, S)               # OOB -> drop
        cache_k = cache_k.at[rows, :, cols, :].set(
            k.astype(cache_k.dtype), mode="drop")
        cache_v = cache_v.at[rows, :, cols, :].set(
            v.astype(cache_v.dtype), mode="drop")
        if kernel:
            from repro.kernels.prefill_attention.ops import gqa_prefill
            out = gqa_prefill(q, cache_k, cache_v, start, window=window)
        else:
            out = prefill_attention_jnp(q, cache_k, cache_v, start,
                                        window=window)
    out = out.reshape(B, C, cfg.n_heads * hd).astype(x.dtype)
    return constrain(linear(p["wo"], out), "batch", "seq",
                     "act_embed"), cache_k, cache_v


def attention_train(p, x, cfg, positions=None, causal=True, window=0):
    B, S, _ = x.shape
    if positions is None:
        positions = jnp.broadcast_to(jnp.arange(S)[None], (B, S))
    q, k, v = _qkv(p, x, cfg, positions)
    out = chunked_attention(q, k, v, cfg, causal=causal, window=window)
    out = out.reshape(B, S, cfg.n_heads * cfg.hd)
    return constrain(linear(p["wo"], out), "batch", "seq", "act_embed")


def decode_attention_dist(q, k_cache, v_cache, length, window, mesh,
                          axis: str = "model"):
    """Distributed flash-decode over a sequence-sharded cache: each shard
    of the `axis`-sharded kv_seq dim computes masked partial softmax
    stats over its LOCAL cache slice; partials combine with one tiny
    psum (log-sum-exp combine). Replaces both the full-cache read and
    the dynamic window slice, which XLA could only realize by
    all-gathering the entire cache (350 GB/step for long_500k —
    EXPERIMENTS.md §Perf-3)."""
    B, Hkv, S, hd = k_cache.shape
    H = q.shape[1]
    G = H // Hkv
    n_sh = mesh.shape[axis]
    bax = tuple(a for a in ("pod", "data") if a in mesh.shape)
    bspec = bax if (bax and B % math.prod(mesh.shape[a] for a in bax) == 0) \
        else None

    def f(ql, kl, vl):
        j = jax.lax.axis_index(axis)
        Bl = kl.shape[0]
        S_loc = kl.shape[2]
        offset = j * S_loc
        qf = ql.reshape(Bl, Hkv, G, hd)
        logits = jnp.einsum("bhgd,bhsd->bhgs", qf, kl.astype(qf.dtype))
        logits = logits.astype(jnp.float32) / math.sqrt(hd)
        pos = offset + jnp.arange(S_loc)
        valid = pos < length
        if window:
            valid &= pos >= length - window
        logits = jnp.where(valid[None, None, None, :], logits, NEG_INF)
        m = logits.max(-1)                                   # [B,Hkv,G]
        p = jnp.where(valid[None, None, None, :],
                      jnp.exp(logits - m[..., None]), 0.0)
        s = p.sum(-1)
        acc = jnp.einsum("bhgs,bhsd->bhgd", p.astype(vl.dtype),
                         vl).astype(jnp.float32)
        gm = jax.lax.pmax(m, axis)
        corr = jnp.exp(m - gm)                               # 0 if local -inf
        s = jax.lax.psum(s * corr, axis)
        acc = jax.lax.psum(acc * corr[..., None], axis)
        out = acc / jnp.maximum(s[..., None], 1e-30)
        return out.reshape(Bl, H, hd).astype(ql.dtype)

    return jax.shard_map(
        f, mesh=mesh,
        in_specs=(P(bspec, None, None), P(bspec, None, axis, None),
                  P(bspec, None, axis, None)),
        out_specs=P(bspec, None, None),
        check_vma=False,
    )(q, k_cache, v_cache)


def decode_attention_slots(q, k_cache, v_cache, lengths, window: int = 0,
                           kernel: bool = False):
    """Per-slot flash-decode: q [B,H,hd], caches [B,Hkv,S,hd],
    `lengths` [B] — each row attends its OWN prefix (the serving
    engine's hot path, where every slot is at a different depth).
    `kernel=True` routes through the Pallas decode_attention kernel;
    the pure-jnp masked softmax is the bit-equivalent plain route."""
    if kernel:
        from repro.kernels.decode_attention.ops import gqa_decode
        return gqa_decode(q, k_cache, v_cache, lengths,
                          window=window).astype(q.dtype)
    return decode_attention_jnp(q, k_cache, v_cache, lengths,
                                window=window).astype(q.dtype)


def attention_decode_slots(p, x, cfg, cache_k, cache_v, indices, window=0,
                           pages=None):
    """Slot-axis decode: x [B,1,d], `indices` [B] — each row writes its
    k/v at its own cache position and attends its own prefix. The
    continuous-batching analogue of `attention_decode`; rows are fully
    independent, so admitting a new request into a freed slot never
    perturbs its neighbours. With `pages` = {"tables", "page_size",
    "active"} the caches are the shared page pool [n_pages,Hkv,page,hd]
    and writes land through each slot's page table (inactive rows'
    writes are dropped — the pool has no batch axis to select over);
    `pages["kernel"]` picks the attention route (`use_attn_kernel`)."""
    B = x.shape[0]
    hd = cfg.hd
    positions = indices[:, None]                           # [B,1]
    q, k, v = _qkv(p, x, cfg, positions)
    kernel = _pages_kernel(pages)
    if pages is not None:
        keep = jnp.ones((B, 1), bool) if pages.get("active") is None \
            else pages["active"][:, None]
        cache_k = paged_insert(cache_k, pages["tables"], positions, k, keep)
        cache_v = paged_insert(cache_v, pages["tables"], positions, v, keep)
        out = decode_attention_slots_paged(q[:, 0], cache_k, cache_v,
                                           pages["tables"], indices + 1,
                                           window, kernel)
    else:
        S = cache_k.shape[2]
        hit = jnp.arange(S)[None, :] == indices[:, None]   # [B,S]
        cache_k = jnp.where(hit[:, None, :, None],
                            k.transpose(0, 2, 1, 3).astype(cache_k.dtype),
                            cache_k)
        cache_v = jnp.where(hit[:, None, :, None],
                            v.transpose(0, 2, 1, 3).astype(cache_v.dtype),
                            cache_v)
        out = decode_attention_slots(q[:, 0], cache_k, cache_v, indices + 1,
                                     window, kernel)
    out = out.reshape(B, 1, cfg.n_heads * hd).astype(x.dtype)
    return constrain(linear(p["wo"], out), "batch", "seq",
                     "act_embed"), cache_k, cache_v


def attention_decode(p, x, cfg, cache_k, cache_v, index, window=0,
                     pages=None):
    """x [B,1,d]; cache [B,Hkv,S,hd]; index = scalar write position, or
    a per-slot [B] vector (dispatches to `attention_decode_slots`; the
    scalar path stays bitwise the legacy decode).
    Returns (out [B,1,d], new_k, new_v)."""
    from repro.nn.sharding import current_mesh

    if jnp.asarray(index).ndim:
        return attention_decode_slots(p, x, cfg, cache_k, cache_v, index,
                                      window, pages=pages)
    B = x.shape[0]
    hd = cfg.hd
    positions = jnp.broadcast_to(index[None, None], (B, 1))
    q, k, v = _qkv(p, x, cfg, positions)          # [B,1,H,hd] / [B,1,Hkv,hd]
    cache_k = jax.lax.dynamic_update_slice_in_dim(
        cache_k, k.transpose(0, 2, 1, 3).astype(cache_k.dtype), index, axis=2)
    cache_v = jax.lax.dynamic_update_slice_in_dim(
        cache_v, v.transpose(0, 2, 1, 3).astype(cache_v.dtype), index, axis=2)
    S = cache_k.shape[2]
    mesh = current_mesh()
    # decode_attention_dist is available but OFF by default: measured
    # neutral on collectives and 2-3x worse on the memory term vs XLA's
    # native handling of the seq-sharded masked softmax (§Perf-C2).
    if USE_DIST_DECODE and mesh is not None \
            and mesh.shape.get("model", 1) > 1 \
            and S % mesh.shape["model"] == 0:
        out = decode_attention_dist(q[:, 0], cache_k, cache_v, index + 1,
                                    window, mesh)
    else:
        out = decode_attention_jnp(q[:, 0], cache_k, cache_v, index + 1,
                                   window=window)
    out = out.reshape(B, 1, cfg.n_heads * hd).astype(x.dtype)
    return constrain(linear(p["wo"], out), "batch", "seq", "act_embed"), cache_k, cache_v


# ---------------------------------------------------------------- MLP
def mlp_specs(cfg, d_ff: int = 0) -> dict:
    d, ff = cfg.d_model, d_ff or cfg.d_ff
    return {
        "wi": linear_specs(d, ff, ("embed", "mlp")),
        "wg": linear_specs(d, ff, ("embed", "mlp")),
        "wo": linear_specs(ff, d, ("mlp", "embed")),
    }


def apply_mlp(p, x):
    h = jax.nn.silu(linear(p["wg"], x)) * linear(p["wi"], x)
    h = constrain(h, "batch", "seq", "mlp")
    return constrain(linear(p["wo"], h), "batch", "seq", "act_embed")
