"""jit'd wrappers of the paged prefill kernel. The GQA wrapper pads the
head-group dim G to a sublane multiple (8) so the flattened C*G query
rows stay aligned, and hd to a lane multiple (128); padded rows/columns
are sliced away after the kernel."""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from repro.kernels.prefill_attention.kernel import paged_prefill_attention


@functools.partial(jax.jit, static_argnames=("window", "interpret"))
def gqa_prefill_paged(q: jax.Array, k_pool: jax.Array, v_pool: jax.Array,
                      tables: jax.Array, start: jax.Array, window: int = 0,
                      interpret: bool | None = None) -> jax.Array:
    """q [B, C, H, hd] prompt chunks; pools [n_pages, Hkv, page, hd]
    already holding the chunk's own K/V columns; `tables` [B, n_lp]
    per-slot page tables; `start` [B]. Returns [B, C, H, hd] fp32."""
    B, C, H, hd = q.shape
    Hkv = k_pool.shape[1]
    G = H // Hkv
    qg = q.reshape(B, C, Hkv, G, hd).transpose(0, 2, 1, 3, 4)

    gp = (-G) % 8
    dp = (-hd) % 128
    Gp = G + gp
    if gp:
        qg = jnp.pad(qg, ((0, 0), (0, 0), (0, 0), (0, gp), (0, 0)))
    if dp:
        qg = jnp.pad(qg, ((0, 0), (0, 0), (0, 0), (0, 0), (0, dp)))
        k_pool = jnp.pad(k_pool, ((0, 0), (0, 0), (0, 0), (0, dp)))
        v_pool = jnp.pad(v_pool, ((0, 0), (0, 0), (0, 0), (0, dp)))

    qf = qg.reshape(B, Hkv, C * Gp, hd + dp)
    out = paged_prefill_attention(qf, k_pool, v_pool, tables, start, g=Gp,
                                  window=window, scale=1.0 / (hd ** 0.5),
                                  interpret=interpret)
    out = out.reshape(B, Hkv, C, Gp, hd + dp)[:, :, :, :G, :hd]
    return out.transpose(0, 2, 1, 3, 4).reshape(B, C, H, hd)


@functools.partial(jax.jit, static_argnames=("scale", "interpret"))
def mla_prefill_paged(q: jax.Array, pool: jax.Array, tables: jax.Array,
                      start: jax.Array, scale: float,
                      interpret: bool | None = None) -> jax.Array:
    """Absorbed latent attention (MLA) of prompt chunks over the paged
    latent pool (see `mla_decode_paged`): q [B, C, H, D]; pool
    [n_pages, 1, page, D] already holding the chunk's own rows; `start`
    [B]. The C x H query rows share the one key head. Returns
    [B, C, H, D] fp32."""
    B, C, H, D = q.shape
    out = paged_prefill_attention(q.reshape(B, 1, C * H, D), pool, pool,
                                  tables, start, g=H, scale=scale,
                                  interpret=interpret)
    return out.reshape(B, C, H, D)
