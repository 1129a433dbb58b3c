"""jit'd wrappers of the paged decode kernel. The GQA wrapper pads only
G to a sublane multiple (8), and reads the pools at their stored width;
padded queries are sliced away after the kernel."""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from repro.kernels.decode_attention.kernel import paged_decode_attention


@functools.partial(jax.jit, static_argnames=("window", "interpret"))
def gqa_decode_paged(q: jax.Array, k_pool: jax.Array, v_pool: jax.Array,
                     tables: jax.Array, length: jax.Array, window: int = 0,
                     interpret: bool | None = None) -> jax.Array:
    """q [B, H, hd]; pools [n_pages, Hkv, page, hd] read in place at
    their stored width; `tables` [B, n_lp] per-slot page tables;
    `length` scalar or per-row [B] valid-prefix counts. Only q's group
    axis is padded (to 8 sublanes). Returns [B, H, hd] fp32."""
    B, H, hd = q.shape
    Hkv = k_pool.shape[1]
    G = H // Hkv
    qg = q.reshape(B, Hkv, G, hd)
    gp = (-G) % 8
    if gp:
        qg = jnp.pad(qg, ((0, 0), (0, 0), (0, gp), (0, 0)))
    out = paged_decode_attention(qg, k_pool, v_pool, tables, length,
                                 window=window, interpret=interpret)
    return out[:, :, :G].reshape(B, H, hd)


@functools.partial(jax.jit, static_argnames=("scale", "interpret"))
def mla_decode_paged(q: jax.Array, pool: jax.Array, tables: jax.Array,
                     length: jax.Array, scale: float,
                     interpret: bool | None = None) -> jax.Array:
    """Absorbed latent attention (MLA) over the paged latent pool: one
    key/value head whose rows are [c | k_pe]; q [B, H, D] holds each
    head's absorbed query [q_nope W_UK | q_pe]; pool [n_pages, 1, page,
    D]; the value is the same pool, so each page is fetched once.
    Multi-query, so the H heads ride the kernel's group axis (H a
    multiple of 8) and D its lanes: no padding, no copy of the pool.
    Returns [B, H, D] fp32; the caller keeps the latent columns."""
    out = paged_decode_attention(q[:, None], pool, None, tables, length,
                                 scale=scale, interpret=interpret)
    return out[:, 0]
