"""Flash-decode GQA attention kernel: one query token vs. a blocked KV
cache with online softmax — the perf-critical op of the decode_32k /
long_500k shapes.

Grid (B, Hkv, S/bs); the S axis is the innermost (sequential on TPU)
grid dim, so the running (m, l, acc) state lives in VMEM scratch across
KV blocks. Supports causal length masking and sliding windows. Head-group
dim G (= H / Hkv) rides the sublane axis; hd rides lanes (ops.py pads
both to hardware multiples).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels import resolve_interpret

BLOCK_S = 512
NEG_INF = -1e30


def _decode_kernel(len_ref, q_ref, k_ref, v_ref, o_ref,
                   m_scr, l_scr, acc_scr, *, scale: float, window: int,
                   block_s: int):
    j = pl.program_id(2)
    nj = pl.num_programs(2)

    @pl.when(j == 0)
    def _init():
        m_scr[...] = jnp.full_like(m_scr, NEG_INF)
        l_scr[...] = jnp.zeros_like(l_scr)
        acc_scr[...] = jnp.zeros_like(acc_scr)

    q = q_ref[0, 0]                       # [G, hd]
    k = k_ref[0, 0]                       # [bs, hd]
    v = v_ref[0, 0]                       # [bs, hd]
    length = len_ref[pl.program_id(0)]    # this batch row's valid prefix

    s = jnp.dot(q, k.T, preferred_element_type=jnp.float32) * scale  # [G, bs]
    pos = j * block_s + jax.lax.broadcasted_iota(jnp.int32, (1, block_s), 1)
    valid = pos < length
    if window:
        valid &= pos >= length - window
    s = jnp.where(valid, s, NEG_INF)

    m_prev = m_scr[...]                   # [G, 1]
    m_new = jnp.maximum(m_prev, s.max(axis=1, keepdims=True))
    p = jnp.exp(s - m_new)                # [G, bs]
    corr = jnp.exp(m_prev - m_new)        # [G, 1]
    l_scr[...] = l_scr[...] * corr + p.sum(axis=1, keepdims=True)
    acc_scr[...] = acc_scr[...] * corr + jnp.dot(
        p.astype(v.dtype), v, preferred_element_type=jnp.float32)
    m_scr[...] = m_new

    @pl.when(j == nj - 1)
    def _done():
        o_ref[0, 0] = (acc_scr[...] /
                       jnp.maximum(l_scr[...], 1e-30)).astype(o_ref.dtype)


def _paged_decode_kernel(tbl_ref, len_ref, q_ref, k_ref, v_ref, o_ref,
                         m_scr, l_scr, acc_scr, *, scale: float,
                         window: int, page: int):
    del tbl_ref  # consumed by the BlockSpec index maps
    j = pl.program_id(2)
    nj = pl.num_programs(2)

    @pl.when(j == 0)
    def _init():
        m_scr[...] = jnp.full_like(m_scr, NEG_INF)
        l_scr[...] = jnp.zeros_like(l_scr)
        acc_scr[...] = jnp.zeros_like(acc_scr)

    q = q_ref[0, 0]                       # [G, hd]
    k = k_ref[0, 0]                       # [page, hd]
    v = v_ref[0, 0]
    length = len_ref[pl.program_id(0)]

    s = jnp.dot(q, k.T, preferred_element_type=jnp.float32) * scale
    pos = j * page + jax.lax.broadcasted_iota(jnp.int32, (1, page), 1)
    valid = pos < length
    if window:
        valid &= pos >= length - window
    s = jnp.where(valid, s, NEG_INF)

    m_prev = m_scr[...]
    m_new = jnp.maximum(m_prev, s.max(axis=1, keepdims=True))
    p = jnp.exp(s - m_new)
    corr = jnp.exp(m_prev - m_new)
    l_scr[...] = l_scr[...] * corr + p.sum(axis=1, keepdims=True)
    acc_scr[...] = acc_scr[...] * corr + jnp.dot(
        p.astype(v.dtype), v, preferred_element_type=jnp.float32)
    m_scr[...] = m_new

    @pl.when(j == nj - 1)
    def _done():
        o_ref[0, 0] = (acc_scr[...] /
                       jnp.maximum(l_scr[...], 1e-30)).astype(o_ref.dtype)


def paged_decode_attention(q: jax.Array, k_pool: jax.Array,
                           v_pool: jax.Array, tables: jax.Array,
                           length: jax.Array, window: int = 0,
                           scale: float | None = None,
                           interpret: bool | None = None) -> jax.Array:
    """Flash-decode over a PAGED cache: q [B, Hkv, G, hd]; pools
    [n_pages, Hkv, page, hd] shared by all slots; `tables` [B, n_lp]
    int32 maps each row's logical page j to its physical pool page —
    scalar-prefetched so the KV BlockSpec index_map walks the page table
    directly (block j of row b streams pool page tables[b, j], no
    gather materializes). `length` [B] (or scalar) valid-prefix counts;
    logical columns past `length` are masked, so placeholder table
    entries only ever contribute exact zeros. Returns [B, Hkv, G, hd]
    fp32."""
    B, Hkv, G, hd = q.shape
    n_pages, _, page, _ = k_pool.shape
    n_lp = tables.shape[1]
    if scale is None:
        scale = 1.0 / (hd ** 0.5)
    grid = (B, Hkv, n_lp)
    return pl.pallas_call(
        functools.partial(_paged_decode_kernel, scale=scale, window=window,
                          page=page),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=2,
            grid=grid,
            in_specs=[
                pl.BlockSpec((1, 1, G, hd), lambda b, h, j, t, ln: (b, h, 0, 0)),
                pl.BlockSpec((1, 1, page, hd),
                             lambda b, h, j, t, ln: (t[b, j], h, 0, 0)),
                pl.BlockSpec((1, 1, page, hd),
                             lambda b, h, j, t, ln: (t[b, j], h, 0, 0)),
            ],
            out_specs=pl.BlockSpec((1, 1, G, hd),
                                   lambda b, h, j, t, ln: (b, h, 0, 0)),
            scratch_shapes=[
                pltpu.VMEM((G, 1), jnp.float32),
                pltpu.VMEM((G, 1), jnp.float32),
                pltpu.VMEM((G, hd), jnp.float32),
            ],
        ),
        out_shape=jax.ShapeDtypeStruct((B, Hkv, G, hd), jnp.float32),
        interpret=resolve_interpret(interpret),
    )(jnp.asarray(tables, jnp.int32),
      jnp.broadcast_to(jnp.asarray(length, jnp.int32).reshape(-1), (B,)),
      q, k_pool, v_pool)


def decode_attention(q: jax.Array, k: jax.Array, v: jax.Array,
                     length: jax.Array, window: int = 0,
                     scale: float | None = None,
                     interpret: bool | None = None) -> jax.Array:
    """q [B, Hkv, G, hd]; k/v [B, Hkv, S, hd]; length scalar int32 OR a
    per-batch-row [B] vector (continuous-batching decode: every slot
    masks its own prefix; a scalar is broadcast to all rows).
    `scale` defaults to 1/sqrt(hd) — pass explicitly when hd is padded.
    Returns [B, Hkv, G, hd] fp32."""
    B, Hkv, G, hd = q.shape
    S = k.shape[2]
    bs = min(BLOCK_S, S)
    assert S % bs == 0, (S, bs)
    if scale is None:
        scale = 1.0 / (hd ** 0.5)
    grid = (B, Hkv, S // bs)
    return pl.pallas_call(
        functools.partial(_decode_kernel, scale=scale, window=window,
                          block_s=bs),
        grid=grid,
        in_specs=[
            pl.BlockSpec(memory_space=pltpu.SMEM),
            pl.BlockSpec((1, 1, G, hd), lambda b, h, j: (b, h, 0, 0)),
            pl.BlockSpec((1, 1, bs, hd), lambda b, h, j: (b, h, j, 0)),
            pl.BlockSpec((1, 1, bs, hd), lambda b, h, j: (b, h, j, 0)),
        ],
        out_specs=pl.BlockSpec((1, 1, G, hd), lambda b, h, j: (b, h, 0, 0)),
        out_shape=jax.ShapeDtypeStruct((B, Hkv, G, hd), jnp.float32),
        scratch_shapes=[
            pltpu.VMEM((G, 1), jnp.float32),
            pltpu.VMEM((G, 1), jnp.float32),
            pltpu.VMEM((G, hd), jnp.float32),
        ],
        interpret=resolve_interpret(interpret),
    )(jnp.broadcast_to(jnp.asarray(length, jnp.int32).reshape(-1), (B,)),
      q, k, v)
