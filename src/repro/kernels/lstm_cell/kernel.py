"""Fused LSTM recurrence kernel — the paper model's compute hot loop.

The input contribution x_t @ W_x + b is precomputed (one big MXU matmul
outside); the kernel runs the *sequential* part that XLA cannot batch:
for each t, gates = xw[t] + h @ W_h, gate nonlinearities, (h, c) update.
h and c live in VMEM scratch for the whole sequence — zero HBM traffic
for the recurrent state, one [bB, H] x [H, 4H] MXU matmul per step.

Grid: one program per batch block; scratch persists across the fori_loop.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels import resolve_interpret

BLOCK_B = 128


def _lstm_kernel(xw_ref, wh_ref, h_ref, c_ref, *, seq_len: int):
    H = wh_ref.shape[0]

    def step(t, carry):
        h, c = carry
        gates = xw_ref[:, t, :] + jnp.dot(
            h, wh_ref[...], preferred_element_type=jnp.float32)
        i, f, g, o = (gates[:, :H], gates[:, H:2 * H],
                      gates[:, 2 * H:3 * H], gates[:, 3 * H:])
        c = jax.nn.sigmoid(f) * c + jax.nn.sigmoid(i) * jnp.tanh(g)
        h = jax.nn.sigmoid(o) * jnp.tanh(c)
        return h, c

    B = xw_ref.shape[0]
    h0 = jnp.zeros((B, H), jnp.float32)
    h, c = jax.lax.fori_loop(0, seq_len, step, (h0, h0))
    h_ref[...] = h
    c_ref[...] = c


def lstm_final_state(xw: jax.Array, wh: jax.Array,
                     interpret: bool | None = None) -> tuple[jax.Array, jax.Array]:
    """xw [B, T, 4H] (x@Wx + b precomputed), wh [H, 4H].
    Returns (h_T, c_T) each [B, H] fp32."""
    B, T, H4 = xw.shape
    H = H4 // 4
    bb = min(BLOCK_B, B)
    pad = (-B) % bb
    if pad:
        xw = jnp.pad(xw, ((0, pad), (0, 0), (0, 0)))
    grid = ((B + pad) // bb,)
    h, c = pl.pallas_call(
        functools.partial(_lstm_kernel, seq_len=T),
        grid=grid,
        in_specs=[
            pl.BlockSpec((bb, T, H4), lambda i: (i, 0, 0)),
            pl.BlockSpec((H, H4), lambda i: (0, 0)),
        ],
        out_specs=[
            pl.BlockSpec((bb, H), lambda i: (i, 0)),
            pl.BlockSpec((bb, H), lambda i: (i, 0)),
        ],
        out_shape=[jax.ShapeDtypeStruct(((B + pad), H), jnp.float32),
                   jax.ShapeDtypeStruct(((B + pad), H), jnp.float32)],
        interpret=resolve_interpret(interpret),
    )(xw.astype(jnp.float32), wh.astype(jnp.float32))
    return h[:B], c[:B]
