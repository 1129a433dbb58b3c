"""Flash-decode attention kernel: one query token per row against the
paged KV cache, with online softmax, causal length masking and sliding
windows. Head-group dim G (= H / Hkv) rides the sublane axis, hd the
lanes.

`paged_decode_attention` reads one layer of the stacked page pool [L,
n_pages, Hkv, page, hd], shared by all slots, through per-slot page
tables, at the pool's stored width. The layer index is
scalar-prefetched into the page BlockSpecs' index maps, as
`kernels/moe_gmm` reads its stacked experts, so no per-layer slice of
the pool is ever made.
Grid (B, blocks): a step takes one slot and one block of up to
`_pages_per_block` pages, with all its KV heads; where the table fits
one block, as in serving, that is one step per slot. Each page of the
block is a BlockSpec of its own that fetches one whole pool page (its
heads are contiguous) through a scalar-prefetched table of the pages
to fetch, so the pipeline copies slot b + 1's pages while slot b
computes. Only the pages that hold a slot's valid context are copied:
a page past it keeps the previous step's block index, which the
pipeline does not fetch again, and is masked. (Copies started by hand
from a pool left in HBM would do the same, but Mosaic refuses a DMA of
a slice whose minor dim is narrower than its 128-lane tiling, as hd 64
and the latent 576 are; a BlockSpec over the whole minor dim is legal.)
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels import resolve_interpret

NEG_INF = -1e30
# the paged kernel's page buffers: at most this many pages a block, in at
# most this much VMEM
MAX_PAGES_PER_BLOCK = 16
PAGE_BLOCK_VMEM = 4 * 1024 * 1024


def _paged_decode_kernel(fetch_ref, len_ref, layer_ref, q_ref, *refs,
                         scale: float, window: int, page: int, ppb: int,
                         shared_kv: bool):
    del fetch_ref, layer_ref  # consumed by the page BlockSpecs' index maps
    k_refs = refs[:ppb]
    v_refs = k_refs if shared_kv else refs[ppb:2 * ppb]
    o_ref, m_scr, l_scr, acc_scr = refs[-4:]
    b, i = pl.program_id(0), pl.program_id(1)
    length = len_ref[b]
    first, end = _span(length, window, page)

    @pl.when(i == 0)
    def _init():
        m_scr[...] = jnp.full_like(m_scr, NEG_INF)
        l_scr[...] = jnp.zeros_like(l_scr)
        acc_scr[...] = jnp.zeros_like(acc_scr)

    @pl.when(first + i * ppb < end)
    def _block():
        rows = ppb * page

        def valid(shape, dim):
            pos = (first + i * ppb) * page + jax.lax.broadcasted_iota(
                jnp.int32, shape, dim)
            ok = pos < length
            return ok & (pos >= length - window) if window else ok

        q = q_ref[0]                                    # [Hkv, G, hd]
        k = jnp.concatenate([r[0] for r in k_refs], axis=1)  # [Hkv, rows, hd]
        s = jax.lax.dot_general(
            q, k, (((2,), (2,)), ((0,), (0,))),
            preferred_element_type=jnp.float32) * scale  # [Hkv, G, rows]
        s = jnp.where(valid((1, 1, rows), 2), s, NEG_INF)
        v = k if shared_kv else jnp.concatenate([r[0] for r in v_refs],
                                                 axis=1)
        # a page past the length holds another slot's page: zero it, as
        # its weight is exactly 0 but 0 * NaN is NaN
        v = jnp.where(valid((1, rows, 1), 1), v, 0)
        m_prev = m_scr[...]
        m_new = jnp.maximum(m_prev, s.max(axis=2, keepdims=True))
        p = jnp.exp(s - m_new)
        corr = jnp.exp(m_prev - m_new)
        l_scr[...] = l_scr[...] * corr + p.sum(axis=2, keepdims=True)
        acc_scr[...] = acc_scr[...] * corr + jax.lax.dot_general(
            p.astype(v.dtype), v, (((2,), (1,)), ((0,), (0,))),
            preferred_element_type=jnp.float32)
        m_scr[...] = m_new

    @pl.when(i == pl.num_programs(1) - 1)
    def _done():
        o_ref[0] = (acc_scr[...] /
                    jnp.maximum(l_scr[...], 1e-30)).astype(o_ref.dtype)


def _span(length, window: int, page: int):
    """The pages [first, end) a slot of `length` reads: those that hold
    its valid prefix, less those wholly before `window`."""
    end = (length + page - 1) // page
    first = jnp.maximum(length - window, 0) // page if window else 0
    return first, end


def _pages_per_block(n_lp: int, n_pools: int, hkv: int, page: int,
                     hd: int, itemsize: int) -> int:
    """Pages a block holds: all of a slot's table where that fits
    PAGE_BLOCK_VMEM (both pipeline buffers of every pool, lanes padded to
    128), at most MAX_PAGES_PER_BLOCK, so VMEM does not grow with the
    table."""
    per_page = 2 * n_pools * hkv * page * (-(-hd // 128) * 128) * itemsize
    return max(1, min(n_lp, MAX_PAGES_PER_BLOCK,
                      PAGE_BLOCK_VMEM // per_page))


def _fetch_table(tables, length, window: int, page: int, ppb: int):
    """The pool page that page-slot j of block i of slot b fetches,
    flattened in grid order. A valid page is the slot's table entry; a
    page past the slot's span repeats what that page-slot held the step
    before (pool page 0 before any), so the pipeline sees an unchanged
    block index and makes no copy. Entries past a slot's span are
    never used."""
    B, n_lp = tables.shape
    n_blk = -(-n_lp // ppb)
    first, end = _span(length, window, page)
    p = jnp.broadcast_to(
        jnp.asarray(first).reshape(-1, 1, 1)
        + (jnp.arange(n_blk)[:, None] * ppb + jnp.arange(ppb))[None],
        (B, n_blk, ppb))
    valid = (p < end[:, None, None]).reshape(B * n_blk, ppb)
    ids = jnp.take_along_axis(tables, jnp.clip(p, 0, n_lp - 1).reshape(B, -1),
                              axis=1).reshape(B * n_blk, ppb)
    step = jnp.arange(B * n_blk)[:, None]
    last = jax.lax.cummax(jnp.where(valid, step, -1), axis=0)
    held = jnp.take_along_axis(ids, jnp.maximum(last, 0), axis=0)
    return jnp.where(last >= 0, held, 0).reshape(-1)


def paged_decode_attention(q: jax.Array, k_pool: jax.Array,
                           v_pool: jax.Array | None, layer: jax.Array,
                           tables: jax.Array, length: jax.Array,
                           window: int = 0, scale: float | None = None,
                           interpret: bool | None = None) -> jax.Array:
    """Flash-decode over a PAGED cache: q [B, Hkv, G, hd]; pools
    [L, n_pages, Hkv, page, hd], stacked over the layers and shared by
    all slots, of which layer `layer` (int32 scalar) is read in place at
    its stored width; `tables` [B, n_lp] int32 maps each row's logical
    page j to its physical pool page; `length` [B] (or scalar)
    valid-prefix counts. `v_pool` None means the values are the keys
    (latent attention): each page is then fetched once.

    Grid (B, blocks): one step per slot and block of `_pages_per_block`
    pages, all KV heads at once; where the whole table fits one block,
    as in serving, that is one step per slot. A block's pages are its
    BlockSpecs, one per page, each a whole pool page (all KV heads, one
    contiguous copy) found through the scalar-prefetched `_fetch_table`,
    so the pipeline streams slot b + 1's pages while slot b computes.
    Only the slot's `ceil(length / page)` pages are copied (less those
    wholly before `window`); a page past them keeps the previous step's
    block index, which the pipeline does not fetch again, and is masked.
    A slot of length 0 attends nothing and returns zeros. The online
    softmax is carried across a slot's blocks. Returns [B, Hkv, G, hd]
    fp32."""
    B, Hkv, G, hd = q.shape
    page = k_pool.shape[3]
    n_lp = tables.shape[1]
    if scale is None:
        scale = 1.0 / (hd ** 0.5)
    pools = [k_pool] if v_pool is None else [k_pool, v_pool]
    ppb = _pages_per_block(n_lp, len(pools), Hkv, page, hd,
                           k_pool.dtype.itemsize)
    n_blk = -(-n_lp // ppb)
    length = jnp.broadcast_to(jnp.asarray(length, jnp.int32).reshape(-1),
                              (B,))
    fetch = _fetch_table(jnp.asarray(tables, jnp.int32), length, window,
                         page, ppb)

    def page_spec(j):
        return pl.BlockSpec(
            (None, 1, Hkv, page, hd),
            lambda b, i, f, ln, lay: (lay[0], f[(b * n_blk + i) * ppb + j],
                                      0, 0, 0))

    slot_spec = pl.BlockSpec((1, Hkv, G, hd),
                             lambda b, i, f, ln, lay: (b, 0, 0, 0))
    return pl.pallas_call(
        functools.partial(_paged_decode_kernel, scale=scale, window=window,
                          page=page, ppb=ppb, shared_kv=v_pool is None),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=3,
            grid=(B, n_blk),
            in_specs=[slot_spec] + [page_spec(j) for j in range(ppb)]
            * len(pools),
            out_specs=slot_spec,
            scratch_shapes=[
                pltpu.VMEM((Hkv, G, 1), jnp.float32),
                pltpu.VMEM((Hkv, G, 1), jnp.float32),
                pltpu.VMEM((Hkv, G, hd), jnp.float32),
            ],
        ),
        out_shape=jax.ShapeDtypeStruct((B, Hkv, G, hd), jnp.float32),
        interpret=resolve_interpret(interpret),
    )(fetch, length, jnp.reshape(layer, (1,)).astype(jnp.int32), q,
      *[pool for pool in pools for _ in range(ppb)])

