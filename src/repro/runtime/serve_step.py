"""Decode (serving) steps: ONE new token — or one bucketed prompt CHUNK —
per slot, against the family's serving cache. The family decides the
cache kind:

  * dense per-slot state, for a family without a pageable cache (the
    paper classifier's O(1) recurrent state): `make_decode_step`, and
    `make_prefill_step`, which replays the family's OWN decode_step
    position by position inside one lax.scan, masking cache updates per
    row — the exact prefill of any family that has a decode step;
  * the shared page pool, for every family with
    `ModelApi.paged_cache_shapes`: `make_paged_decode_step`, and
    `make_paged_prefill_step`, the family's fused `prefill_step` — a
    bulk cache insert and one chunk-attention launch per layer.

The chunked-prefill contract:
    prefill(params, cache, tokens [B,C], start [B], n_valid [B] [, tables])
        -> (last_logits [B,V] fp32, new_cache)
where row b consumes chunk tokens 0..n_valid[b]-1 at cache positions
start[b].. and rows with n_valid=0 are untouched.

The paged steps take `kernel`: True routes attention through the Pallas
paged kernels, False through the plain jnp attention, None (default) —
the kernels exactly on a TPU (`models/layers.use_attn_kernel`).
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from repro.models import api as M
from repro.runtime.train_step import window_for


def make_decode_step(cfg, shape_cfg):
    model = M.get_model(cfg)
    window = window_for(cfg, shape_cfg)

    def decode_step(params, cache, token, index):
        logits, cache = model.decode_step(params, cache, token, index, cfg,
                                          window)
        return logits, cache

    return decode_step


def cache_specs(cfg, shape_cfg):
    """(ShapeDtypeStruct tree, logical-axes tree) for the decode cache."""
    model = M.get_model(cfg)
    shapes = model.cache_shapes(cfg, shape_cfg.global_batch, shape_cfg.seq_len)
    sds = {k: jax.ShapeDtypeStruct(sh, dt) for k, (sh, ax, dt) in shapes.items()}
    axes = {k: ax for k, (sh, ax, dt) in shapes.items()}
    return sds, axes


# ------------------------------------------------------------- paged KV
def _paged_model(cfg):
    """The family's ModelApi, which must page its cache."""
    model = M.get_model(cfg)
    if model.paged_cache_shapes is None:
        raise ValueError(f"paged KV unsupported for family {cfg.family!r}")
    return model


def paged_cache_specs(cfg, n_pages: int, page_size: int):
    """(ShapeDtypeStruct tree, logical-axes tree) for the shared-pool
    paged cache (families with `ModelApi.paged_cache_shapes` only —
    recurrent O(1) caches have nothing to page)."""
    shapes = _paged_model(cfg).paged_cache_shapes(cfg, n_pages, page_size)
    sds = {k: jax.ShapeDtypeStruct(sh, dt) for k, (sh, ax, dt) in shapes.items()}
    axes = {k: ax for k, (sh, ax, dt) in shapes.items()}
    return sds, axes


def init_paged_cache(cfg, n_pages: int, page_size: int) -> dict:
    """The zeroed shared page pool of the family's paged cache."""
    sds, _ = paged_cache_specs(cfg, n_pages, page_size)
    return {k: jnp.zeros(s.shape, s.dtype) for k, s in sds.items()}


def make_paged_decode_step(cfg, shape_cfg, page_size: int,
                           kernel: bool | None = None, stats: bool = False):
    """Decode against the shared page pool. `tables` [B, n_lp] per-slot
    page tables; `active` [B] bool — inactive rows' pool writes are
    DROPPED in-graph (the pool has no batch axis for the engine to
    select over). With `stats` the step returns (logits, cache,
    counters): the held-expert counters of an MoE model
    (`transformer.decode_step`), [0] otherwise."""
    model = _paged_model(cfg)
    window = window_for(cfg, shape_cfg)

    def decode_step(params, cache, token, index, tables, active):
        pages = {"tables": tables, "page_size": page_size, "active": active,
                 "kernel": kernel}
        return model.decode_step(params, cache, token, index, cfg, window,
                                 pages=pages, stats=stats)

    return decode_step


# ------------------------------------------------------------- prefill
def _batch_mask(mask, new, old, axes):
    """Per-leaf batch-row select (the cache leaf's own axes name where
    its batch dim sits)."""
    i = axes.index("batch")
    shape = [1] * new.ndim
    shape[i] = -1
    return jnp.where(mask.reshape(shape), new, old)


def logit_width(cfg) -> int:
    """Width of the logits a serve step samples from: the paper
    classifier's two labels, else the vocabulary."""
    return 2 if cfg.family == "tiny" else cfg.vocab_size


def make_prefill_step(cfg, shape_cfg):
    """Chunked prefill over a DENSE per-slot cache: the exact scan of the
    family's decode step (module docstring)."""
    model = M.get_model(cfg)
    window = window_for(cfg, shape_cfg)
    V = logit_width(cfg)
    shapes = model.cache_shapes(cfg, shape_cfg.global_batch,
                                shape_cfg.seq_len)
    axes = {k: ax for k, (sh, ax, dt) in shapes.items()}

    def prefill_scan(params, cache, tokens, start, n_valid):
        B, C = tokens.shape

        def body(carry, i):
            cache, lg = carry
            tok = jax.lax.dynamic_slice_in_dim(tokens, i, 1, axis=1)
            logits, new_cache = model.decode_step(params, cache, tok,
                                                  start + i, cfg, window)
            act = i < n_valid                                  # [B]
            cache = {k: _batch_mask(act, new_cache[k], cache[k], axes[k])
                     for k in new_cache}
            lg = jnp.where((i == n_valid - 1)[:, None],
                           logits[:, 0].astype(jnp.float32), lg)
            return (cache, lg), None

        (cache, lg), _ = jax.lax.scan(
            body, (cache, jnp.zeros((B, V), jnp.float32)),
            jnp.arange(C, dtype=jnp.int32))
        return lg, cache

    return prefill_scan


def make_paged_prefill_step(cfg, shape_cfg, page_size: int,
                            kernel: bool | None = None, stats: bool = False):
    """Chunked prefill over the shared page pool through the family's
    fused `prefill_step`; the step additionally takes `tables` [B, n_lp].
    Row masking happens at the pool write (dropped scatters), not by
    batch select. With `stats` it returns the counters too, as the paged
    decode step."""
    model = _paged_model(cfg)
    window = window_for(cfg, shape_cfg)

    def prefill_fused(params, cache, tokens, start, n_valid, tables):
        pages = {"tables": tables, "page_size": page_size,
                 "active": None, "kernel": kernel}
        return model.prefill_step(params, cache, tokens, start, n_valid,
                                  cfg, window, pages=pages, stats=stats)

    return prefill_fused
