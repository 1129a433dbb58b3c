"""Shared layer library: norms, RoPE, GQA attention (chunked train/prefill +
cached decode), gated MLP, embeddings. Pure functions over Spec-declared
param dicts; activation shardings via logical-axis constraints."""
from __future__ import annotations

import math

import jax
import jax.numpy as jnp

from repro.kernels import resolve_interpret
from repro.nn import Spec, constrain

NEG_INF = -1e30


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
LANES = 128


def kv_heads_per_row(n_kv_heads: int, hd: int) -> int:
    """KV heads a pool row holds side by side: as many hd-wide heads as
    fill a 128-lane row, where hd divides 128 and the heads split evenly
    (else 1). A TPU stores a pool whose rows are narrower than 128 lanes
    with its page axis minor-most, while the paged kernels read it
    row-major; a pool of full rows is stored row-major, so the step
    programs neither relay it out nor pad its rows."""
    if hd >= LANES or LANES % hd:
        return 1
    return math.gcd(n_kv_heads, LANES // hd)


def paged_view(pool, layer, tables, hd: int | None = None):
    """Gather a slot-major dense view [B, Hkv, n_lp*page, hd] of layer
    `layer` of the stacked page pool [L, n_pages, Hkv/p, page, p*hd]
    (p = `kv_heads_per_row`, head i of a row in lanes [i*hd, (i+1)*hd);
    `hd` defaults to the row width, p = 1) via per-slot page tables
    [B, n_lp]: logical column c of row b lives at pool[layer,
    tables[b, c // page], :, c % page]. Placeholder table entries
    surface whatever the pool holds there — always masked downstream by
    the valid-prefix length, so they contribute exact zeros."""
    B, n_lp = tables.shape
    _, _, hr, page, w = pool.shape
    hd = hd or w
    p = w // hd
    v = pool[layer, tables]                    # [B, n_lp, Hkv/p, page, p*hd]
    v = v.reshape(B, n_lp, hr, page, p, hd).transpose(0, 2, 4, 1, 3, 5)
    return v.reshape(B, hr * p, n_lp * page, hd)


def paged_insert(pool, layer, tables, start, vals, keep):
    """Write C consecutive rows a slot, vals [B, C, Hkv, hd], into layer
    `layer` of the stacked pool [L, n_pages, Hkv/p, page, p*hd] in place
    (see `paged_view`): row c of slot b at its logical column start[b] +
    c, through its page table. Rows with keep [B, C] False are dropped:
    the pool has no batch axis — slots share it — so per-row masking
    (inactive slots, padded chunk tails) must happen here at the write,
    not by a post-hoc batch select. Each pool row is its own scatter
    window, so XLA writes it in the pool's row-major layout."""
    n_pages, hr, page, w = pool.shape[1:]
    B, C = keep.shape
    cols = start[:, None] + jnp.arange(C)[None]                # [B, C]
    phys = jnp.take_along_axis(tables, cols // page, axis=1)
    phys = jnp.where(keep, phys, n_pages)[..., None]            # OOB -> drop
    rows = vals.reshape(B, C, hr, w).astype(pool.dtype)
    return pool.at[layer, phys, jnp.arange(hr), (cols % page)[..., None]] \
        .set(rows, mode="drop")


def use_attn_kernel(kernel: bool | None = None) -> bool:
    """The serving attention route: the Pallas kernels when `kernel` is
    True, the plain jnp attention when False, and — when None — the
    kernels exactly when the program runs on a TPU (elsewhere they
    would only run in the Pallas interpreter)."""
    return not resolve_interpret() if kernel is None else bool(kernel)


def _pages_kernel(pages) -> bool:
    return use_attn_kernel(None if pages is None else pages.get("kernel"))


def no_pages():
    """Raise: per-slot serving attention (GQA or latent), and any cache
    of latent attention, live only in the shared page pool."""
    raise NotImplementedError("per-slot attention serves from the paged "
                              "pool only: pass `pages`")


def attention_prefill_slots(p, x, cfg, cache_k, cache_v, layer, start,
                            n_valid, window=0, pages=None):
    """Fused chunk prefill over the shared page pool: x [B,C,d] — C
    prompt tokens per slot starting at per-row cache position `start`
    [B]; chunk positions >= n_valid[b] are padded tail and masked out of
    the KV insert. One bulk K/V column write + one chunk-vs-cache
    attention launch replace C decode steps. `pages` = {"tables":
    [B,n_lp], "page_size": int, "active": [B] bool or None, "kernel":
    bool or None} is required: the caches are the pools
    [L,n_pages,Hkv/p,page,p*hd] stacked over the layers (`paged_view`),
    written and read in place at layer `layer`, and `kernel` picks the
    attention route (`use_attn_kernel`). Returns (out [B,C,d], new_k,
    new_v)."""
    if pages is None:
        no_pages()
    B, C, _ = x.shape
    hd = cfg.hd
    positions = start[:, None] + jnp.arange(C)[None]        # [B, C]
    q, k, v = _qkv(p, x, cfg, positions)                    # [B,C,H|Hkv,hd]
    keep = jnp.arange(C)[None, :] < n_valid[:, None]        # [B, C]
    if pages.get("active") is not None:
        keep &= pages["active"][:, None]
    tables = pages["tables"]
    cache_k = paged_insert(cache_k, layer, tables, start, k, keep)
    cache_v = paged_insert(cache_v, layer, tables, start, v, keep)
    if _pages_kernel(pages):
        from repro.kernels.prefill_attention.ops import gqa_prefill_paged
        out = gqa_prefill_paged(q, cache_k, cache_v, layer, tables, start,
                                window=window)
    else:
        out = prefill_attention_jnp(q, paged_view(cache_k, layer, tables, hd),
                                    paged_view(cache_v, layer, tables, hd),
                                    start, window=window)
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


def attention_decode_slots(p, x, cfg, cache_k, cache_v, layer, indices,
                           window=0, pages=None):
    """Slot-axis decode over the shared page pool: x [B,1,d], `indices`
    [B] — each row writes its k/v at its own cache position and attends
    its own prefix. The continuous-batching analogue of
    `attention_decode`; rows are fully independent, so admitting a new
    request into a freed slot never perturbs its neighbours. `pages` =
    {"tables", "page_size", "active", "kernel"} is required: the caches
    are the pools [L,n_pages,Hkv/p,page,p*hd] stacked over the layers,
    written and read in place at layer `layer`; writes land through each
    slot's page table (inactive rows' writes are dropped — the pool has
    no batch axis to select over), and `pages["kernel"]` picks the
    attention route (`use_attn_kernel`): the Pallas kernel streams pool
    pages straight off the scalar-prefetched page table and layer index,
    the jnp route gathers a dense per-slot view first."""
    if pages is None:
        no_pages()
    B = x.shape[0]
    hd = cfg.hd
    positions = indices[:, None]                           # [B,1]
    q, k, v = _qkv(p, x, cfg, positions)
    keep = jnp.ones((B, 1), bool) if pages.get("active") is None \
        else pages["active"][:, None]
    tables = pages["tables"]
    cache_k = paged_insert(cache_k, layer, tables, indices, k, keep)
    cache_v = paged_insert(cache_v, layer, tables, indices, v, keep)
    q, lengths = q[:, 0], indices + 1
    if _pages_kernel(pages):
        from repro.kernels.decode_attention.ops import gqa_decode_paged
        out = gqa_decode_paged(q, cache_k, cache_v, layer, tables, lengths,
                               window=window)
    else:
        out = decode_attention_jnp(q, paged_view(cache_k, layer, tables, hd),
                                   paged_view(cache_v, layer, tables, hd),
                                   lengths, window=window)
    out = out.astype(x.dtype).reshape(B, 1, cfg.n_heads * hd)
    return constrain(linear(p["wo"], out), "batch", "seq",
                     "act_embed"), cache_k, cache_v


def attention_decode(p, x, cfg, cache_k, cache_v, index, window=0):
    """x [B,1,d]; one layer's dense cache [B,Hkv,S,hd]; index = scalar
    write position (per-slot positions serve from the paged pool,
    `attention_decode_slots`). Returns (out [B,1,d], new_k, new_v)."""
    B = x.shape[0]
    hd = cfg.hd
    positions = jnp.broadcast_to(index[None, None], (B, 1))
    q, k, v = _qkv(p, x, cfg, positions)          # [B,1,H,hd] / [B,1,Hkv,hd]
    cache_k = jax.lax.dynamic_update_slice_in_dim(
        cache_k, k.transpose(0, 2, 1, 3).astype(cache_k.dtype), index, axis=2)
    cache_v = jax.lax.dynamic_update_slice_in_dim(
        cache_v, v.transpose(0, 2, 1, 3).astype(cache_v.dtype), index, axis=2)
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
