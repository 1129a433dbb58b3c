"""Fused semantic-wireless link kernel: blockwise b-bit quantize ->
BPSK/Rayleigh bit-flip channel -> dequantize, one VMEM round-trip.

This is the paper's wire (Alg. 1 lines 8-11 / Alg. 2 line 6) as a single
TPU kernel: in FL it runs over every weight tensor each communication
cycle, in SL over every smashed-activation batch, so fusing
quantize+channel+dequantize removes two full HBM round-trips vs. the
composed jnp ops.

TPU adaptation notes (DESIGN.md §5):
  * scales are per (block_m x block_n) VMEM tile (the per-tensor paper
    scale is available through ops.transmit with per_tensor=True);
  * the BPSK/fading/AWGN chain is the exact bit-flip equivalence
    p = Q(sqrt(2 |f|^2 SNR)) — see core/channel.py;
  * randomness: one uint32 word per element enters the kernel; each of
    the b bit-planes derives an independent uniform via a Murmur3-style
    integer finalizer (VPU int ops only, shared with core/wire.py). On
    real TPU hardware the rand input can be replaced by
    `pltpu.prng_random_bits` (not available in interpret mode, which is
    how this container validates the kernel).

Two entry points:
  * `quant_channel_2d` — blockwise scales, scalar p (single tensor);
  * `packed_wire_2d` — the packed-pytree wire (core/wire.py): per-ROW
    scale and bit-error vectors ([bm, 1] tiles beside the data tile),
    so a whole pytree — or a stacked N-user FL upload reshaped to
    [N*R, C] — is ONE kernel launch with per-packet fading.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from repro.core.wire import GOLDEN as _GOLDEN          # noqa: F401 (re-export)
from repro.core.wire import bit_flip_mask, flip_threshold, fmix32
from repro.kernels import resolve_interpret

BLOCK_M = 128
BLOCK_N = 512
ROW_TILE = 8        # float32 sublane tile: row blocks are multiples of it

# Opt-in: on real TPU (compiled, not interpret) generate the per-element
# rand word with pltpu.prng_random_bits INSIDE the kernel instead of the
# host-side jax.random.bits input. Changes the bit-flip stream (the TPU
# PRNG is not the threefry stream), so it is a flag, never a default —
# the host-vs-kernel bitwise-equivalence tests only hold with this off.
TPU_KERNEL_RNG = False

# back-compat alias: ref.py and older callers import the finalizer here
_finalize = fmix32


def _qc_kernel(x_ref, rand_ref, t_ref, o_ref, *, bits: int):
    x = x_ref[...]
    qmax = float(2 ** (bits - 1) - 1)
    # blockwise symmetric scale (Eq. 1)
    amax = jnp.maximum(jnp.max(jnp.abs(x)), 1e-12)
    scale = amax / qmax
    q = jnp.clip(jnp.round(x / scale), -qmax, qmax).astype(jnp.int32)
    code = (q + jnp.int32(qmax)).astype(jnp.uint32)

    # per-bit-plane Bernoulli(p) flips from one rand word per element
    code = code ^ bit_flip_mask(rand_ref[...], bits, t_ref[0])

    q_hat = jnp.clip(code.astype(jnp.int32) - jnp.int32(qmax), -qmax, qmax)
    o_ref[...] = (q_hat.astype(jnp.float32) * scale).astype(o_ref.dtype)


def _wire_tile(x, rand, scale, thresh, *, bits: int,
               code_dtype=jnp.uint32):
    """One tile of the packed-wire math (quantize -> flip -> dequantize),
    shared by the plain and fused-mean kernel bodies. `thresh` is the
    per-row uint32 `flip_threshold(p)`. Returns float32.

    `code_dtype=jnp.uint8` is the on-wire int8 mode (bits <= 8): the
    codeword tile lives as one byte per element between quantize and
    dequantize — same codes, same flip mask, bit-identical output. The
    int4 mode (bits <= 4) also lands here with uint8 codewords: nibble
    XOR never carries across the nibble boundary, so the physically
    byte-packed layout (two codewords per byte, Q.pack_nibbles — done
    for real by the jnp packed path in core/wire.py) produces values
    identical to per-codeword uint8 XOR; the kernel keeps the
    vector-friendly one-codeword-per-lane tile and stays bit-exact
    against it (tests/test_wire.py)."""
    qmax = float(2 ** (bits - 1) - 1)
    q = jnp.clip(jnp.round(x / scale), -qmax, qmax).astype(jnp.int32)
    code = (q + jnp.int32(qmax)).astype(code_dtype)
    code = code ^ bit_flip_mask(rand, bits, thresh).astype(code_dtype)
    q_hat = jnp.clip(code.astype(jnp.int32) - jnp.int32(qmax), -qmax, qmax)
    return q_hat.astype(jnp.float32) * scale


def _packed_kernel(x_ref, rand_ref, scale_ref, t_ref, o_ref, *, bits: int,
                   code_dtype=jnp.uint32):
    """Packed-wire body: per-ROW quantization scale and flip threshold
    (delivered as [bm, 1] tiles) instead of a blockwise scale — each row
    belongs to exactly one packet (leaf / user), see core/wire.py."""
    y = _wire_tile(x_ref[...], rand_ref[...], scale_ref[...], t_ref[...],
                   bits=bits, code_dtype=code_dtype)
    o_ref[...] = y.astype(o_ref.dtype)


def _packed_kernel_tpu_rng(seed_ref, x_ref, scale_ref, t_ref, o_ref, *,
                           bits: int, code_dtype, grid_j: int):
    """Packed-wire body with the rand word generated IN-KERNEL by the
    TPU hardware PRNG (pltpu.prng_random_bits) instead of arriving as a
    [bm, bn] input tile — kills the host-side jax.random.bits draw and
    its HBM round-trip. Each grid tile seeds with (caller seed, flat
    tile id) so tiles draw independent streams. Compiled-TPU only: the
    interpret path keeps the input-word kernel (`_packed_kernel`)."""
    from jax.experimental.pallas import tpu as pltpu

    i, j = pl.program_id(0), pl.program_id(1)
    pltpu.prng_seed(seed_ref[0, 0], i * grid_j + j)
    rand = pltpu.bitcast(pltpu.prng_random_bits(x_ref.shape), jnp.uint32)
    y = _wire_tile(x_ref[...], rand, scale_ref[...], t_ref[...],
                   bits=bits, code_dtype=code_dtype)
    o_ref[...] = y.astype(o_ref.dtype)


def _packed_mean_kernel(x_ref, rand_ref, scale_ref, t_ref, w_ref, o_ref, *,
                        bits: int, code_dtype=jnp.uint32):
    """Fused quant -> channel -> dequant -> WEIGHTED-MEAN body for a
    stacked N-user upload: the user axis is the innermost grid dim, and
    each user's dequantized tile is scaled by its aggregation weight
    ([bm, 1] w tile: alive / n_alive) and accumulated straight into the
    output block — the [N, R, C] received buffer never exists. Users
    accumulate in ascending order, matching the jnp fallback's ordered
    sum bit-for-bit (core/wire._transmit_stacked_mean_planned)."""
    u = pl.program_id(2)
    y = _wire_tile(x_ref[...], rand_ref[...], scale_ref[...], t_ref[...],
                   bits=bits, code_dtype=code_dtype)
    contrib = (w_ref[...] * y).astype(o_ref.dtype)

    @pl.when(u == 0)
    def _init():
        o_ref[...] = contrib

    @pl.when(u != 0)
    def _accum():
        o_ref[...] += contrib


def _code_dtype_for(wire_dtype: str):
    return jnp.uint8 if wire_dtype in ("int8", "int4") else jnp.uint32


def _row_block(r: int) -> int:
    """Largest row block (at most BLOCK_M) dividing `r`, a multiple of
    ROW_TILE — the TPU tiles a block's rows in whole sublane tiles."""
    return next(b for b in (BLOCK_M, 64, 32, 16, ROW_TILE) if r % b == 0)


def _pad_rows(a: jax.Array, rows: int, value=0) -> jax.Array:
    """Pad the row axis (-2) of `a` up to `rows`; the kernels' padding
    rows are computed and then sliced off."""
    extra = rows - a.shape[-2]
    if not extra:
        return a
    pad = [(0, 0)] * (a.ndim - 2) + [(0, extra), (0, 0)]
    return jnp.pad(a, pad, constant_values=value)


def packed_wire_2d(buf: jax.Array, rand: jax.Array, scale_row: jax.Array,
                   p_row: jax.Array, bits: int,
                   interpret: bool | None = None,
                   wire_dtype: str = "float32",
                   rng_mode: str = "host",
                   seed: jax.Array | None = None) -> jax.Array:
    """buf [R, C] float32, rand [R, C] uint32, scale_row/p_row [R, 1]
    float32. Grid over the packed 2D view; one launch per pytree (or per
    N-user upload when the caller stacks users into R). Any R is
    accepted: rows pad to a ROW_TILE multiple inside.
    `wire_dtype="int8"` (bits <= 8) keeps the codeword tile in uint8 —
    4x less VMEM for the buffer that crosses the channel; `"int4"`
    (bits <= 4) bills two codewords per byte (see _wire_tile).
    `rng_mode="tpu"` (compiled TPU only; gated by TPU_KERNEL_RNG at the
    wire layer) generates the rand words in-kernel from `seed` [1, 1]
    int32 and ignores `rand`; interpret mode must stay "host"."""
    interpret = resolve_interpret(interpret)
    R, C = buf.shape
    rp = -(-R // ROW_TILE) * ROW_TILE
    bm = _row_block(rp)
    bn = min(BLOCK_N, C)
    assert C % bn == 0, (R, C, bm, bn)
    grid = (rp // bm, C // bn)
    code_dtype = _code_dtype_for(wire_dtype)
    buf_p = _pad_rows(buf, rp)
    scale_p = _pad_rows(scale_row, rp, 1.0)
    thresh_p = _pad_rows(flip_threshold(p_row), rp)
    row_spec = pl.BlockSpec((bm, 1), lambda i, j: (i, 0))
    tile_spec = pl.BlockSpec((bm, bn), lambda i, j: (i, j))
    out_shape = jax.ShapeDtypeStruct((rp, C), buf.dtype)
    if rng_mode not in ("host", "tpu"):
        raise ValueError(f"unknown rng_mode {rng_mode!r}")
    if rng_mode == "tpu":
        if interpret:
            raise ValueError(
                "rng_mode='tpu' (in-kernel pltpu.prng_random_bits) needs "
                "compiled TPU execution; interpret mode keeps the "
                "host-side rand-word input (rng_mode='host')")
        if seed is None:
            raise ValueError("rng_mode='tpu' requires a [1, 1] int32 seed")
        out = pl.pallas_call(
            functools.partial(_packed_kernel_tpu_rng, bits=bits,
                              code_dtype=code_dtype, grid_j=C // bn),
            grid=grid,
            in_specs=[pl.BlockSpec((1, 1), lambda i, j: (0, 0)),
                      tile_spec, row_spec, row_spec],
            out_specs=tile_spec,
            out_shape=out_shape,
            interpret=interpret,
        )(seed, buf_p, scale_p, thresh_p)
        return out[:R]
    out = pl.pallas_call(
        functools.partial(_packed_kernel, bits=bits, code_dtype=code_dtype),
        grid=grid,
        in_specs=[tile_spec, tile_spec, row_spec, row_spec],
        out_specs=tile_spec,
        out_shape=out_shape,
        interpret=interpret,
    )(buf_p, _pad_rows(rand, rp), scale_p, thresh_p)
    return out[:R]


def packed_wire_mean_2d(buf: jax.Array, rand: jax.Array,
                        scale_row: jax.Array, p_row: jax.Array,
                        w_row: jax.Array, bits: int, n: int,
                        interpret: bool | None = None,
                        wire_dtype: str = "float32") -> jax.Array:
    """Fused stacked transmit + weighted mean: buf/rand [N*R, C] (users
    stacked along rows), scale_row/p_row/w_row [N*R, 1] -> [R, C] the
    weighted sum over users of the dequantized rows. ONE kernel launch
    for FL's whole quantize -> channel -> dequantize -> aggregate upload
    (grid (R/bm, C/bn, N), user axis innermost so each output block is
    revisited consecutively). Each user's R rows pad to a ROW_TILE
    multiple inside, with zero weight."""
    interpret = resolve_interpret(interpret)
    NR, C = buf.shape
    assert NR % n == 0, (NR, n)
    R = NR // n
    rp = -(-R // ROW_TILE) * ROW_TILE
    bm = _row_block(rp)
    bn = min(BLOCK_N, C)
    assert C % bn == 0, (R, C, bm, bn)
    gi = rp // bm
    grid = (gi, C // bn, n)
    code_dtype = _code_dtype_for(wire_dtype)

    def per_user(a, value=0):
        a = _pad_rows(a.reshape(n, R, a.shape[-1]), rp, value)
        return a.reshape(n * rp, a.shape[-1])

    row_spec = pl.BlockSpec((bm, 1), lambda i, j, u: (u * gi + i, 0))
    tile_spec = pl.BlockSpec((bm, bn), lambda i, j, u: (u * gi + i, j))
    out = pl.pallas_call(
        functools.partial(_packed_mean_kernel, bits=bits,
                          code_dtype=code_dtype),
        grid=grid,
        in_specs=[tile_spec, tile_spec, row_spec, row_spec, row_spec],
        out_specs=pl.BlockSpec((bm, bn), lambda i, j, u: (i, j)),
        out_shape=jax.ShapeDtypeStruct((rp, C), jnp.float32),
        interpret=interpret,
    )(per_user(buf), per_user(rand), per_user(scale_row, 1.0),
      per_user(flip_threshold(p_row)), per_user(w_row))
    return out[:R]


def quant_channel_2d(x: jax.Array, rand: jax.Array, p: jax.Array,
                     bits: int, interpret: bool | None = None) -> jax.Array:
    """x [M, N] float, rand [M, N] uint32, p [1] float32 (bit-error prob)."""
    M, N = x.shape
    bm, bn = min(BLOCK_M, M), min(BLOCK_N, N)
    assert M % bm == 0 and N % bn == 0, (M, N, bm, bn)
    grid = (M // bm, N // bn)
    return pl.pallas_call(
        functools.partial(_qc_kernel, bits=bits),
        grid=grid,
        in_specs=[
            pl.BlockSpec((bm, bn), lambda i, j: (i, j)),
            pl.BlockSpec((bm, bn), lambda i, j: (i, j)),
            pl.BlockSpec((1,), lambda i, j: (0,)),
        ],
        out_specs=pl.BlockSpec((bm, bn), lambda i, j: (i, j)),
        out_shape=jax.ShapeDtypeStruct((M, N), x.dtype),
        interpret=resolve_interpret(interpret),
    )(x, rand, flip_threshold(p))
