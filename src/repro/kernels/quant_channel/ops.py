"""jit'd public wrappers for the fused quantize+channel kernels.

`transmit` — single tensor, per-BLOCK scales: arbitrary-shape input ->
padded 2D blocks -> quant_channel_2d. Accelerated version of
core.channel.transmit_quantized.

Whole-pytree (and stacked multi-user) transmissions should go through
core.wire.transmit_tree / transmit_stacked with impl="kernel", which
pack once and hit `packed_wire_2d` in a single launch with per-tensor
scales and per-packet fading."""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from repro.core import channel as CH
from repro.kernels.quant_channel.kernel import quant_channel_2d, BLOCK_N


@functools.partial(jax.jit, static_argnames=("bits", "fading", "interpret"))
def transmit(key: jax.Array, x: jax.Array, bits: int = 8,
             snr_db: float = 20.0, fading: bool = True,
             interpret: bool | None = None) -> jax.Array:
    """Quantize+channel+dequantize `x` (any shape/float dtype)."""
    kf, kb = jax.random.split(key)
    f2 = CH.rayleigh_gain(kf) if fading else jnp.float32(1.0)
    p = CH.bpsk_bit_error_prob(snr_db, f2).reshape(1)

    flat = x.reshape(-1)
    n = flat.shape[0]
    cols = BLOCK_N if n >= BLOCK_N else n
    rows = -(-n // cols)
    pad = rows * cols - n
    x2 = jnp.pad(flat, (0, pad)).reshape(rows, cols)
    # pad rows to a block multiple
    bm = min(128, rows)
    rpad = (-rows) % bm
    if rpad:
        x2 = jnp.pad(x2, ((0, rpad), (0, 0)))
    rand = jax.random.bits(kb, x2.shape, jnp.uint32)
    y = quant_channel_2d(x2.astype(jnp.float32), rand, p, bits,
                         interpret=interpret)
    return y.reshape(-1)[:n].reshape(x.shape).astype(x.dtype)
