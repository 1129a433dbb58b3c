"""Decoder-only transformer LM covering the dense, MoE, and VLM families.

Layers are homogeneous and scanned (`lax.scan` over stacked params) so the
HLO is O(1) in depth — required for the 64-94 layer assigned configs to
compile quickly in the dry-run. `cfg.first_dense` leading dense layers
(DeepSeek's) are a second stack, `dense_layers`, scanned before
`layers`. VLM configs prepend `n_frontend_tokens` projected patch
embeddings (the vision tower is a stub per the assignment).

Attention is GQA, or latent attention (`models/mla.py`) where
`cfg.kv_lora_rank > 0`; its serving cache is one latent pool instead of
K and V pools (`paged_cache_shapes`). In the serve steps
(`decode_step`, `prefill_step`) an MoE layer is the dropless held-expert
layer (`moe.moe_held`), and with `stats=True` the steps also return its
counters summed over the layers; `forward` keeps capacity dispatch.
"""
from __future__ import annotations

import dataclasses

import jax
import jax.numpy as jnp

from repro.nn import is_spec, stack_specs, constrain
from repro.models import layers as L
from repro.models import mla
from repro.models.moe import N_STATS, apply_moe, moe_held, moe_specs


# ------------------------------------------------------------- specs
def block_specs(cfg, moe: bool | None = None) -> dict:
    moe = cfg.is_moe if moe is None else moe
    s = {
        "ln_attn": L.norm_specs(cfg.d_model, cfg.norm),
        "attn": mla.mla_specs(cfg) if cfg.is_mla else L.attention_specs(cfg),
    }
    if not cfg.parallel_block:
        s["ln_mlp"] = L.norm_specs(cfg.d_model, cfg.norm)
    s["moe" if moe else "mlp"] = (
        moe_specs(cfg) if moe else L.mlp_specs(cfg))
    return s


def model_specs(cfg) -> dict:
    s = {
        "embed": L.embed_specs(cfg.vocab_size, cfg.d_model),
        "layers": stack_specs(block_specs(cfg),
                              cfg.n_layers - cfg.first_dense),
        "ln_f": L.norm_specs(cfg.d_model, cfg.norm),
    }
    if cfg.first_dense:
        s["dense_layers"] = stack_specs(block_specs(cfg, moe=False),
                                        cfg.first_dense)
    if not cfg.tie_embed:
        s["lm_head"] = L.embed_specs(cfg.vocab_size, cfg.d_model)
    if cfg.frontend == "vision":
        # projector from the (stub) vision tower hidden size to d_model
        s["vis_proj"] = L.linear_specs(cfg.d_model, cfg.d_model,
                                       ("embed", "act_embed"))
    return jax.tree.map(lambda sp: dataclasses.replace(
        sp, dtype=cfg.param_dtype), s, is_leaf=is_spec)


def _norm(p, x, cfg):
    return L.apply_norm(p, x, cfg.norm, cfg.norm_eps)


def logits_of(params, x):
    """LM head: the untied `lm_head` table where the model has one, else
    the embedding table."""
    return L.unembed(params.get("lm_head", params["embed"]), x)


def _stacks(params, cfg) -> list:
    """(stacked layer params, first layer index) of each layer stack, in
    order: the leading dense layers, then the rest."""
    out = []
    if cfg.first_dense:
        out.append((params["dense_layers"], 0))
    out.append((params["layers"], cfg.first_dense))
    return out


# ------------------------------------------------------------- blocks
def apply_block(lp: dict, x: jax.Array, cfg, positions=None, causal=True,
                window: int = 0) -> tuple[jax.Array, jax.Array]:
    """Returns (x, aux_loss)."""
    aux = jnp.zeros((), jnp.float32)
    h = _norm(lp["ln_attn"], x, cfg)
    if cfg.is_mla:
        with jax.named_scope("mla"):
            attn = mla.mla_train(lp["attn"], h, cfg, positions, causal,
                                 window)
    else:
        attn = L.attention_train(lp["attn"], h, cfg, positions, causal,
                                 window)
    if not cfg.parallel_block:
        x = x + attn
        h = _norm(lp["ln_mlp"], x, cfg)
    if "moe" in lp:
        m, a = apply_moe(lp["moe"], h, cfg)
        aux += a["lb_loss"]
    else:
        m = L.apply_mlp(lp["mlp"], h)
    x = x + attn + m if cfg.parallel_block else x + m
    return constrain(x, "batch", "seq", "act_embed"), aux


def _serve_block(lp, x, cfg, attend, valid, kernel, li):
    """One layer of a serve step: `attend(h)` -> (attn, new caches);
    an MoE layer's feed-forward is the dropless held-expert layer (its
    expert weights stacked over the stack's layers, `li` picking this
    one), with its counters; a dense layer's counters are zeros."""
    h = _norm(lp["ln_attn"], x, cfg)
    attn, cache = attend(h)
    if not cfg.parallel_block:
        x = x + attn
        h = _norm(lp["ln_mlp"], x, cfg)
    if "moe" in lp:
        m, stats = moe_held(lp["moe"], h, cfg, li, valid, kernel)
    else:
        m = L.apply_mlp(lp["mlp"], h)
        stats = jnp.zeros((N_STATS,), jnp.int32)
    x = x + attn + m if cfg.parallel_block else x + m
    return x, cache, stats


def apply_block_decode(lp: dict, x, cfg, cache: dict, layer, index,
                       window=0, pages=None, valid=None, li=None):
    """One layer of a decode step over the stacked caches `cache` ({"k",
    "v"}, or {"latent"} for latent attention) at layer `layer`; for an
    MoE layer, `li` indexes its stacked expert weights (see
    `moe.moe_held`). A per-slot [B] `index` reads and writes the paged
    pools in place; a scalar one is the dense per-row cache, whose layer
    is sliced out and written back. Returns (x, new cache, counters)."""
    kernel = L._pages_kernel(pages)

    def attend(h):
        if cfg.is_mla:
            out, pool = mla.mla_decode_slots(lp["attn"], h, cfg,
                                             cache["latent"], layer, index,
                                             pages, kernel)
            return out, {"latent": pool}
        if jnp.ndim(index):
            out, ck, cv = L.attention_decode_slots(
                lp["attn"], h, cfg, cache["k"], cache["v"], layer, index,
                window, pages=pages)
            return out, {"k": ck, "v": cv}
        out, ck, cv = L.attention_decode(
            lp["attn"], h, cfg, cache["k"][layer], cache["v"][layer], index,
            window)
        return out, {k: jax.lax.dynamic_update_index_in_dim(
            cache[k], c, layer, 0) for k, c in (("k", ck), ("v", cv))}

    return _serve_block(lp, x, cfg, attend, valid, kernel, li)


def apply_block_prefill(lp: dict, x, cfg, cache: dict, layer, start,
                        n_valid, window=0, pages=None, li=None):
    """Chunk analogue of `apply_block_decode` over the paged pools: x
    [B,C,d] prompt chunks at per-row positions start[b]..start[b]+C-1,
    chunk tails >= n_valid[b] masked out of the cache insert and of the
    expert dispatch."""
    kernel = L._pages_kernel(pages)
    valid = jnp.arange(x.shape[1])[None, :] < n_valid[:, None]

    def attend(h):
        if cfg.is_mla:
            out, pool = mla.mla_prefill_slots(lp["attn"], h, cfg,
                                              cache["latent"], layer, start,
                                              n_valid, pages, kernel)
            return out, {"latent": pool}
        out, ck, cv = L.attention_prefill_slots(lp["attn"], h, cfg,
                                                cache["k"], cache["v"], layer,
                                                start, n_valid, window, pages)
        return out, {"k": ck, "v": cv}

    return _serve_block(lp, x, cfg, attend, valid, kernel, li)


# ------------------------------------------------------------- forward
def embed_inputs(params, batch, cfg):
    """tokens (+ optional patch_embeds) -> [B, S_total, d] activations."""
    x = L.embed_lookup(params["embed"], batch["tokens"], cfg.dtype)
    if cfg.frontend == "vision" and "patch_embeds" in batch:
        vis = L.linear(params["vis_proj"], batch["patch_embeds"].astype(cfg.dtype))
        x = jnp.concatenate([vis, x], axis=1)
    return constrain(x, "batch", "seq", "act_embed")


def forward(params: dict, batch: dict, cfg, window: int = 0) -> tuple:
    """Full-sequence forward (train / prefill). Returns (logits, aux)."""
    x = embed_inputs(params, batch, cfg)
    B, S, _ = x.shape
    positions = jnp.broadcast_to(jnp.arange(S)[None], (B, S))

    def body(carry, lp):
        x, aux = carry
        x, a = apply_block(lp, x, cfg, positions, True, window)
        return (x, aux + a), None

    if cfg.remat:
        body = jax.checkpoint(body)
    carry = (x, jnp.zeros((), jnp.float32))
    for stack, _ in _stacks(params, cfg):
        carry, _ = jax.lax.scan(body, carry, stack)
    x, aux = carry
    x = _norm(params["ln_f"], x, cfg)
    logits = logits_of(params, x)
    return logits, {"aux_loss": aux / cfg.n_layers}


# ------------------------------------------------------------- decode
def init_cache_shapes(cfg, batch_size: int, seq_len: int):
    if cfg.is_mla:
        L.no_pages()
    hd = cfg.hd
    shape = (cfg.n_layers, batch_size, cfg.n_kv_heads, seq_len, hd)
    axes = ("layers", "batch", "kv_heads", "kv_seq", None)
    return {
        "k": (shape, axes, cfg.dtype),
        "v": (shape, axes, cfg.dtype),
    }


def init_cache(cfg, batch_size: int, seq_len: int) -> dict:
    return {name: jnp.zeros(shape, dtype)
            for name, (shape, axes, dtype) in
            init_cache_shapes(cfg, batch_size, seq_len).items()}


def paged_cache_shapes(cfg, n_pages: int, page_size: int):
    """Paged KV layout: fixed-size pages from one shared pool — NO batch
    axis; slots map logical columns onto pool pages via per-slot page
    tables (serve/paging.py owns allocation). Capacity is bounded by
    total tokens in flight (n_pages * page_size), not B * seq_len.
    A K or V pool row holds `layers.kv_heads_per_row` heads side by
    side. Latent attention caches one pool, `latent`, of [c | k_pe] rows
    under a single head."""
    axes = ("layers", None, "kv_heads", None, None)
    if cfg.is_mla:
        shape = (cfg.n_layers, n_pages, 1, page_size, mla.pool_width(cfg))
        return {"latent": (shape, (axes[0], None, None, None, None),
                           cfg.dtype)}
    p = L.kv_heads_per_row(cfg.n_kv_heads, cfg.hd)
    shape = (cfg.n_layers, n_pages, cfg.n_kv_heads // p, page_size,
             p * cfg.hd)
    return {
        "k": (shape, axes, cfg.dtype),
        "v": (shape, axes, cfg.dtype),
    }


def init_paged_cache(cfg, n_pages: int, page_size: int) -> dict:
    return {name: jnp.zeros(shape, dtype)
            for name, (shape, axes, dtype) in
            paged_cache_shapes(cfg, n_pages, page_size).items()}


def _serve_layers(params, cache, x, cfg, block):
    """Every layer stack in order. The stacked [L, ...] caches ride the
    scan CARRY whole — scanning them as xs/ys would make XLA allocate a
    second full cache for the stacked ys (§Perf-3) — and each layer's
    block reads and writes its own layer of them in place, at its index
    in the stack, as the paged kernels read the pools. The held experts'
    weights are not scanned either: each layer's MoE block gets them
    stacked, with its index in their stack, so the grouped matmul reads
    them in place. `block(lp, x, cache, layer, index in the stack)` ->
    (x, cache, counters). Returns (x, cache, counters summed over the
    layers: the held-expert layer's rows, busiest expert's rows and
    experts used)."""
    counts = jnp.zeros((N_STATS,), jnp.int32)
    for stack, first in _stacks(params, cfg):
        experts = {}
        if "moe" in stack:
            moe = dict(stack["moe"])
            experts = {k: moe.pop(k) for k in ("wi", "wg", "wo")}
            stack = dict(stack, moe=moe)

        def body(carry, lp_l, first=first, experts=experts):
            x, cache = carry
            lp, l = lp_l
            if experts:
                lp = dict(lp, moe=dict(lp["moe"], **experts))
            x, cache, st = block(lp, x, cache, l, l - first)
            return (x, cache), st

        n = jax.tree.leaves(stack)[0].shape[0]
        (x, cache), st = jax.lax.scan(body, (x, cache),
                                      (stack, first + jnp.arange(n)))
        counts = counts + st.sum(0)
    return x, cache, counts if cfg.is_moe else counts[:0]


def decode_step(params: dict, cache: dict, token: jax.Array, index: jax.Array,
                cfg, window: int = 0, pages=None, stats: bool = False
                ) -> tuple:
    """token [B,1] int32; index scalar int32 (current position, over the
    dense cache of `init_cache`) or a per-slot [B] vector, which needs
    `pages` = {"tables": [B,n_lp], "page_size": int, "active": [B] bool
    or None, "kernel": bool or None}: the cache leaves are then the
    shared page pool from `init_paged_cache`, writes route through each
    slot's page table, and inactive rows are left out of the expert
    dispatch too. Returns (logits [B,1,V], new_cache), and with `stats`
    a third element: the held-expert counters summed over the layers
    (int32 [moe.N_STATS] for an MoE model, [0] otherwise)."""
    x = L.embed_lookup(params["embed"], token, cfg.dtype)
    valid = None
    if pages is not None and pages.get("active") is not None:
        valid = pages["active"][:, None]

    def block(lp, x, cache, layer, li):
        return apply_block_decode(lp, x, cfg, cache, layer, index, window,
                                  pages=pages, valid=valid, li=li)

    x, cache, counts = _serve_layers(params, cache, x, cfg, block)
    x = _norm(params["ln_f"], x, cfg)
    logits = logits_of(params, x)
    return (logits, cache, counts) if stats else (logits, cache)


def prefill_step(params: dict, cache: dict, tokens: jax.Array,
                 start: jax.Array, n_valid: jax.Array, cfg,
                 window: int = 0, pages=None, stats: bool = False) -> tuple:
    """Fused chunk prefill over the shared page pool: tokens [B,C] — one
    prompt chunk per slot, row b's chunk starting at cache position
    start[b] with n_valid[b] real tokens (the rest padded tail, masked
    out of the cache insert and the expert dispatch; a row with
    n_valid=0 is untouched). `pages` is required, as in `decode_step`
    with a per-slot index. One launch writes the chunk's cache columns
    in bulk and attends the whole chunk, instead of C decode steps.
    Returns (last_logits [B,V] fp32 — the logits of each row's LAST
    valid chunk token, exactly what sampling the first generated token
    needs — and new_cache), and the counters with `stats` (see
    `decode_step`)."""
    B, C = tokens.shape
    x = L.embed_lookup(params["embed"], tokens, cfg.dtype)

    def block(lp, x, cache, layer, li):
        return apply_block_prefill(lp, x, cfg, cache, layer, start, n_valid,
                                   window, pages=pages, li=li)

    x, cache, counts = _serve_layers(params, cache, x, cfg, block)
    x = _norm(params["ln_f"], x, cfg)
    last = jnp.clip(n_valid - 1, 0, C - 1)
    xl = jnp.take_along_axis(x, last[:, None, None], axis=1)   # [B,1,d]
    logits = logits_of(params, xl)[:, 0].astype(jnp.float32)
    return (logits, cache, counts) if stats else (logits, cache)
