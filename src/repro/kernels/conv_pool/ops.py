"""jit'd wrapper with hardware-alignment padding: E and F pad to lane
multiples, batch pads to the block multiple; padding sliced away after."""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from repro.kernels.conv_pool.kernel import conv_pool, BLOCK_B


@functools.partial(jax.jit, static_argnames=("interpret",))
def user_conv_pool(x: jax.Array, w: jax.Array, b: jax.Array,
                   interpret: bool | None = None) -> jax.Array:
    """Alignment-safe fused conv+relu+pool. x [B,T,E] float."""
    B, T, E = x.shape
    K, _, F = w.shape
    ep = (-E) % 8
    fp = (-F) % 128
    bp = (-B) % min(BLOCK_B, max(B, 1))
    if ep:
        x = jnp.pad(x, ((0, 0), (0, 0), (0, ep)))
        w = jnp.pad(w, ((0, 0), (0, ep), (0, 0)))
    if fp:
        w = jnp.pad(w, ((0, 0), (0, 0), (0, fp)))
        b = jnp.pad(b, (0, fp))
    if bp:
        x = jnp.pad(x, ((0, bp), (0, 0), (0, 0)))
    out = conv_pool(x, w, b, interpret=interpret)
    return out[:B, :, :F]
