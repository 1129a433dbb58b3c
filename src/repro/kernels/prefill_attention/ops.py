"""jit'd wrappers of the paged prefill kernel. Both take the pools stacked
over the layers and the layer to read, which the kernel reads in place
at the pools' stored width. The GQA wrapper packs the queries of the KV
heads a pool row holds as the decode wrapper does
(`decode_attention.ops.pack_queries`), and pads each chunk position's
query rows to a sublane multiple (8) so the flattened query rows stay
aligned; padded rows are sliced away after the kernel."""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from repro.kernels.decode_attention.ops import pack_queries, unpack_heads
from repro.kernels.prefill_attention.kernel import paged_prefill_attention


@functools.partial(jax.jit, static_argnames=("window", "interpret"))
def gqa_prefill_paged(q: jax.Array, k_pool: jax.Array, v_pool: jax.Array,
                      layer: jax.Array, tables: jax.Array, start: jax.Array,
                      window: int = 0,
                      interpret: bool | None = None) -> jax.Array:
    """q [B, C, H, hd] prompt chunks; pools [L, n_pages, Hkv/p, page,
    p*hd] (p KV heads a row) whose layer `layer` already holds the
    chunk's own K/V columns; `tables` [B, n_lp] per-slot page tables;
    `start` [B]. Returns [B, C, H, hd] fp32."""
    B, C, H, hd = q.shape
    hr, w = k_pool.shape[2], k_pool.shape[4]
    p = w // hd
    G = H // (hr * p)
    qg = q.reshape(B, C, hr, p, G, hd).transpose(0, 2, 1, 3, 4, 5)
    qg = pack_queries(qg, p)                          # [B, hr, C, p*G, w]
    R = p * G
    Rp = R + (-R) % 8
    if Rp > R:
        qg = jnp.pad(qg, ((0, 0), (0, 0), (0, 0), (0, Rp - R), (0, 0)))

    qf = qg.reshape(B, hr, C * Rp, w)
    out = paged_prefill_attention(qf, k_pool, v_pool, layer, tables, start,
                                  g=Rp, window=window, scale=1.0 / (hd ** 0.5),
                                  interpret=interpret)
    out = unpack_heads(out.reshape(B, hr, C, Rp, w)[:, :, :, :R], p, hd)
    return out.transpose(0, 2, 1, 3, 4, 5).reshape(B, C, H, hd)


@functools.partial(jax.jit, static_argnames=("scale", "interpret"))
def mla_prefill_paged(q: jax.Array, pool: jax.Array, layer: jax.Array,
                      tables: jax.Array, start: jax.Array, scale: float,
                      interpret: bool | None = None) -> jax.Array:
    """Absorbed latent attention (MLA) of prompt chunks over the paged
    latent pool (see `mla_decode_paged`): q [B, C, H, D]; pool
    [L, n_pages, 1, page, D] whose layer `layer` already holds the
    chunk's own rows; `start` [B]. The C x H query rows share the one
    key head. Returns [B, C, H, D] fp32."""
    B, C, H, D = q.shape
    out = paged_prefill_attention(q.reshape(B, 1, C * H, D), pool, pool,
                                  layer, tables, start, g=H, scale=scale,
                                  interpret=interpret)
    return out.reshape(B, C, H, D)
