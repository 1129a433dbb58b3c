"""Flash-prefill GQA attention kernel: a whole prompt chunk of C query
tokens vs. the paged KV cache with online softmax — the serving
engine's admission hot path (one launch per chunk instead of C decode
launches).

Grid (B, Hkv, n_lp); KV block j is page tables[b, j] of layer `layer`
of the pools stacked over the layers, routed by the scalar-prefetched
BlockSpec index maps (the layer index too, as `kernels/moe_gmm` reads
its stacked experts, so no per-layer slice of the pool is made), whose
logical columns start at j * page. The page axis is the innermost
(sequential on TPU) grid dim, so the running (m, l, acc) state lives in
VMEM scratch across pages. The C chunk positions and the G head-group
dim are flattened onto the sublane axis as C*G query rows; row r is
chunk position r // G, whose global query position is start[b] + r //
G. Causality is per-query-row: row r attends cache columns <= start[b]
+ r // G (with an optional sliding window), so a single launch covers
every token of the chunk including its self-causal triangle. ops.py
pads G to a sublane multiple; the pools are read at their stored width.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels import resolve_interpret

NEG_INF = -1e30


def _paged_prefill_kernel(tbl_ref, start_ref, layer_ref, q_ref, k_ref, v_ref,
                          o_ref, m_scr, l_scr, acc_scr, *, scale: float,
                          window: int, page: int, g: int):
    del tbl_ref, layer_ref  # consumed by the KV BlockSpecs' index maps
    j = pl.program_id(2)
    nj = pl.num_programs(2)

    @pl.when(j == 0)
    def _init():
        m_scr[...] = jnp.full_like(m_scr, NEG_INF)
        l_scr[...] = jnp.zeros_like(l_scr)
        acc_scr[...] = jnp.zeros_like(acc_scr)

    q = q_ref[0, 0]                       # [C*G, hd]
    k = k_ref[0, 0]                       # [page, hd]
    v = v_ref[0, 0]                       # [page, hd]
    start = start_ref[pl.program_id(0)]   # this row's first chunk position

    # [CG, page]
    s = jnp.dot(q, k.T, preferred_element_type=jnp.float32) * scale
    rows = q.shape[0]
    qpos = start + jax.lax.broadcasted_iota(jnp.int32, (rows, 1), 0) // g
    kpos = j * page + jax.lax.broadcasted_iota(jnp.int32, (1, page), 1)
    valid = kpos <= qpos                  # causal: own position included
    if window:
        valid &= kpos > qpos - window
    s = jnp.where(valid, s, NEG_INF)

    m_prev = m_scr[...]                   # [CG, 1]
    m_new = jnp.maximum(m_prev, s.max(axis=1, keepdims=True))
    p = jnp.exp(s - m_new)                # [CG, page]
    corr = jnp.exp(m_prev - m_new)        # [CG, 1]
    l_scr[...] = l_scr[...] * corr + p.sum(axis=1, keepdims=True)
    acc_scr[...] = acc_scr[...] * corr + jnp.dot(
        p.astype(v.dtype), v, preferred_element_type=jnp.float32)
    m_scr[...] = m_new

    @pl.when(j == nj - 1)
    def _done():
        o_ref[0, 0] = (acc_scr[...] /
                       jnp.maximum(l_scr[...], 1e-30)).astype(o_ref.dtype)


def paged_prefill_attention(q: jax.Array, k_pool: jax.Array,
                            v_pool: jax.Array, layer: jax.Array,
                            tables: jax.Array, start: jax.Array, g: int,
                            window: int = 0, scale: float | None = None,
                            interpret: bool | None = None) -> jax.Array:
    """Flash-prefill over a PAGED cache: q [B, Hkv, C*G, hd] chunk-major
    query rows; pools [L, n_pages, Hkv, page, hd] stacked over the
    layers, of which layer `layer` (int32 scalar) is read in place;
    `tables` [B, n_lp]
    per-slot page tables (scalar-prefetched into the KV BlockSpec index
    maps); `start` [B] global position of chunk token 0. Logical
    columns past each query's causal horizon are masked, so placeholder
    table entries contribute exact zeros. Returns [B, Hkv, C*G, hd]
    fp32."""
    B, Hkv, CG, hd = q.shape
    assert CG % g == 0, (CG, g)
    page = k_pool.shape[3]
    n_lp = tables.shape[1]
    if scale is None:
        scale = 1.0 / (hd ** 0.5)
    grid = (B, Hkv, n_lp)
    kv_spec = pl.BlockSpec((None, 1, 1, page, hd),
                           lambda b, h, j, t, st, lay: (lay[0], t[b, j], h,
                                                        0, 0))
    return pl.pallas_call(
        functools.partial(_paged_prefill_kernel, scale=scale, window=window,
                          page=page, g=g),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=3,
            grid=grid,
            in_specs=[
                pl.BlockSpec((1, 1, CG, hd),
                             lambda b, h, j, t, st, lay: (b, h, 0, 0)),
                kv_spec,
                kv_spec,
            ],
            out_specs=pl.BlockSpec((1, 1, CG, hd),
                                   lambda b, h, j, t, st, lay: (b, h, 0, 0)),
            scratch_shapes=[
                pltpu.VMEM((CG, 1), jnp.float32),
                pltpu.VMEM((CG, 1), jnp.float32),
                pltpu.VMEM((CG, hd), jnp.float32),
            ],
        ),
        out_shape=jax.ShapeDtypeStruct((B, Hkv, CG, hd), jnp.float32),
        interpret=resolve_interpret(interpret),
    )(jnp.asarray(tables, jnp.int32),
      jnp.broadcast_to(jnp.asarray(start, jnp.int32).reshape(-1), (B,)),
      jnp.reshape(layer, (1,)).astype(jnp.int32), q, k_pool, v_pool)

