"""jit'd wrappers of the paged decode kernel. Both take the pools stacked
over the layers and the layer to read, which the kernel reads in place
at the pools' stored width. A GQA pool row may hold several KV heads
side by side (`layers.kv_heads_per_row`): each head's queries then
occupy its own lanes of a full-width query row (`pack_queries`), and
keep their own lanes of the output (`unpack_heads`). The GQA wrapper
pads the query rows to a sublane multiple (8); padded rows are sliced
away after the kernel."""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from repro.kernels.decode_attention.kernel import paged_decode_attention


def pack_queries(q: jax.Array, p: int) -> jax.Array:
    """q [..., p, G, hd], the queries of the p KV heads one pool row
    holds -> [..., p*G, p*hd]: head i's G rows hold its queries in lanes
    [i*hd, (i+1)*hd) and zeros elsewhere, so they score only their own
    head's lanes of each pool row."""
    *lead, _, G, hd = q.shape
    own = jnp.eye(p, dtype=bool)[:, None, :, None]          # [p, 1, p, 1]
    rows = jnp.where(own, q[..., None, :], jnp.zeros((), q.dtype))
    return rows.reshape(*lead, p * G, p * hd)


def unpack_heads(o: jax.Array, p: int, hd: int) -> jax.Array:
    """The kernel's output rows [..., p*G, p*hd] of `pack_queries`'
    query rows -> [..., p, G, hd]: each head's rows keep its own lanes."""
    *lead, R, _ = o.shape
    o = o.reshape(*lead, p, R // p, p, hd)
    return jnp.moveaxis(jnp.diagonal(o, axis1=-4, axis2=-2), -1, -3)


@functools.partial(jax.jit, static_argnames=("window", "interpret"))
def gqa_decode_paged(q: jax.Array, k_pool: jax.Array, v_pool: jax.Array,
                     layer: jax.Array, tables: jax.Array, length: jax.Array,
                     window: int = 0,
                     interpret: bool | None = None) -> jax.Array:
    """q [B, H, hd]; pools [L, n_pages, Hkv/p, page, p*hd] (p KV heads a
    row), of which layer `layer` is read in place; `tables` [B, n_lp]
    per-slot page tables; `length` scalar or per-row [B] valid-prefix
    counts. Returns [B, H, hd] fp32."""
    B, H, hd = q.shape
    hr, w = k_pool.shape[2], k_pool.shape[4]
    p = w // hd
    G = H // (hr * p)
    qg = pack_queries(q.reshape(B, hr, p, G, hd), p)      # [B, hr, p*G, w]
    rp = (-p * G) % 8
    if rp:
        qg = jnp.pad(qg, ((0, 0), (0, 0), (0, rp), (0, 0)))
    out = paged_decode_attention(qg, k_pool, v_pool, layer, tables, length,
                                 window=window, scale=1.0 / (hd ** 0.5),
                                 interpret=interpret)
    return unpack_heads(out[:, :, :p * G], p, hd).reshape(B, H, hd)


@functools.partial(jax.jit, static_argnames=("scale", "interpret"))
def mla_decode_paged(q: jax.Array, pool: jax.Array, layer: jax.Array,
                     tables: jax.Array, length: jax.Array, scale: float,
                     interpret: bool | None = None) -> jax.Array:
    """Absorbed latent attention (MLA) over the paged latent pool: one
    key/value head whose rows are [c | k_pe]; q [B, H, D] holds each
    head's absorbed query [q_nope W_UK | q_pe]; pool [L, n_pages, 1,
    page, D], of which layer `layer` is read in place; the value is the
    same pool, so each page is fetched once.
    Multi-query, so the H heads ride the kernel's group axis (H a
    multiple of 8) and D its lanes: no padding, no copy of the pool.
    Returns [B, H, D] fp32; the caller keeps the latent columns."""
    out = paged_decode_attention(q[:, None], pool, None, layer, tables,
                                 length, scale=scale, interpret=interpret)
    return out[:, 0]
