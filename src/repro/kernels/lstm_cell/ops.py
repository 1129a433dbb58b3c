"""jit'd wrapper: full tiny-model LSTM layer (input matmul + fused
recurrence kernel)."""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from repro.kernels.lstm_cell.kernel import lstm_final_state


@functools.partial(jax.jit, static_argnames=("interpret",))
def lstm_layer(x: jax.Array, wx: jax.Array, wh: jax.Array, b: jax.Array,
               interpret: bool | None = None) -> jax.Array:
    """x [B,T,F] -> final hidden [B,H]; wx [F,4H], wh [H,4H], b [4H]."""
    xw = jnp.einsum("btf,fg->btg", x.astype(jnp.float32),
                    wx.astype(jnp.float32)) + b.astype(jnp.float32)
    h, _ = lstm_final_state(xw, wh, interpret=interpret)
    return h
