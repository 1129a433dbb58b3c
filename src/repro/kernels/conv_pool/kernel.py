"""Fused Conv1D(valid) + ReLU + MaxPool1D(2) — the paper's user-side
partition hot loop (Sec. III-A2: the split device runs embedding ->
conv -> pool every batch, so this is the kernel an MCU-class TPU-edge
deployment would run per uplink).

One grid step processes a [bm, T, E] batch tile held in VMEM: the K
kernel taps are K shifted [bm*(T-K+1), E] x [E, F] MXU matmuls
accumulated in fp32, then ReLU and the stride-2 pairwise max — all
before anything returns to HBM. The composed jnp ops round-trip HBM
three times (conv out, relu out, pool out).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from repro.kernels import resolve_interpret

BLOCK_B = 8


def _conv_pool_kernel(x_ref, w_ref, b_ref, o_ref, *, K: int, T_out: int,
                      P: int):
    x = x_ref[...]                       # [bm, T, E]
    w = w_ref[...]                       # [K, E, F]
    b = b_ref[...]                       # [F]
    bm = x.shape[0]
    F = w.shape[2]
    acc = jnp.zeros((bm, T_out, F), jnp.float32)
    for k in range(K):
        xs = x[:, k:k + T_out, :].astype(jnp.float32)
        acc += jax.lax.dot_general(
            xs, w[k].astype(jnp.float32),
            (((2,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)
    acc += b.astype(jnp.float32)[None, None, :]
    acc = jnp.maximum(acc, 0.0)          # ReLU
    pooled = jnp.maximum(acc[:, 0:2 * P:2, :], acc[:, 1:2 * P:2, :])
    o_ref[...] = pooled.astype(o_ref.dtype)


def conv_pool(x: jax.Array, w: jax.Array, b: jax.Array,
              interpret: bool | None = None) -> jax.Array:
    """x [B, T, E], w [K, E, F], b [F] -> [B, (T-K+1)//2, F]."""
    B, T, E = x.shape
    K, _, F = w.shape
    T_out = T - K + 1
    P = T_out // 2
    bm = min(BLOCK_B, B)
    assert B % bm == 0, (B, bm)
    return pl.pallas_call(
        functools.partial(_conv_pool_kernel, K=K, T_out=T_out, P=P),
        grid=(B // bm,),
        in_specs=[
            pl.BlockSpec((bm, T, E), lambda i: (i, 0, 0)),
            pl.BlockSpec((K, E, F), lambda i: (0, 0, 0)),
            pl.BlockSpec((F,), lambda i: (0,)),
        ],
        out_specs=pl.BlockSpec((bm, P, F), lambda i: (i, 0, 0)),
        out_shape=jax.ShapeDtypeStruct((B, P, F), x.dtype),
        interpret=resolve_interpret(interpret),
    )(x, w, b)
