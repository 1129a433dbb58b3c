"""Packed-pytree fused wire: one-shot quantize -> bit-flip channel ->
dequantize for whole weight/activation pytrees (the FL/SL hot path).

Every FL communication cycle pushes the full weight pytree through the
radio chain (Alg. 1 lines 8-11) and every SL step pushes the smashed
activation and its gradient through it (Alg. 2 line 6). The per-leaf /
per-user Python loops this module replaces emitted O(leaves * users)
separate quantize/channel/dequantize op chains, each drawing `bits`
Bernoulli masks — O(leaves * users * bits) RNG calls per round. The
packed wire does the whole tree in ONE jitted pass.

Manifest layout (`WirePlan`)
----------------------------
Each leaf is flattened row-major to float32 and padded up to a whole
number of `cols`-wide rows (cols = WIRE_COLS = 256, a lane multiple).
Leaf rows are concatenated into one [R, cols] buffer; R is padded to a
multiple of 8 (the float32 sublane tile). The plan records, per packet
(= leaf, or (user, leaf) for stacked transmits):

    row_start[i], rows[i], sizes[i], shapes[i], dtypes[i]

plus the treedef and the padded row count. The plan is a frozen,
hashable dataclass, so the jitted transmit specializes once per tree
layout, not once per leaf. Row alignment means per-packet metadata
(quantization scale, bit-error probability) is a per-ROW vector, which
the kernel reads as a [block_m, 1] tile beside the data tile.

RNG scheme
----------
One `split` of the caller's key: `kf` drives the per-packet Rayleigh
fades (a single batched uniform draw for all N*P packets), `kb` drives
ONE `jax.random.bits` draw of a uint32 word per packed element. Bit
plane b of a codeword flips iff

    fmix32(rand ^ ((b + 1) * GOLDEN)) < p * 2^32

i.e. each plane derives an independent uniform from the same word via
the Murmur3 finalizer (integer VPU ops only) — RNG cost no longer
scales with the bit width. The per-leaf reference path (`impl=
"per_leaf"`) consumes the SAME rand buffer and fades, so packed and
per-leaf outputs are bit-identical for identical keys (tested in
tests/test_wire.py).

Kernel grid mapping
-------------------
`impl="kernel"` routes the packed buffer through the Pallas kernel
(kernels/quant_channel/packed_wire_2d): grid = (R // bm, cols // bn)
over the packed 2D view, with the per-row scale and bit-error vectors
delivered as [bm, 1] blocks. A stacked N-user transmit reshapes
[N, R, cols] -> [N*R, cols], so FL's whole multi-user upload is one
kernel launch with per-user fading via the broadcast p vector. The jnp
path (`impl="packed"`, the CPU default) is the exact reference: same
scales, same hash, same flips.
"""
from __future__ import annotations

import dataclasses
import functools
import math
from typing import Any

import jax
import jax.numpy as jnp
import numpy as np

from repro.core import quantization as Q
from repro.kernels import resolve_interpret

WIRE_COLS = 256     # packed row width (lane-size multiple)
_ROW_ALIGN = 8      # float32 sublane tile: R padded to a multiple of this
GOLDEN = 0x9E3779B9  # per-bit-plane salt stride (python int, static)
_GE_FOLD = 77       # fold of kf for the Gilbert-Elliott state chain —
                    # disjoint from kf's own fade uniforms, so turning
                    # the outage process on never perturbs the fades
_SR_SALT = (33 * GOLDEN) & 0xFFFFFFFF  # stochastic-rounding hash salt:
                    # bit planes use (b+1)*GOLDEN for b < 32, so plane
                    # 33 is free for the rounding uniform


# ------------------------------------------------------------- bit-plane RNG
def fmix32(x: jax.Array) -> jax.Array:
    """Murmur3 fmix32: a high-quality 32-bit integer hash (VPU-only)."""
    x = x ^ (x >> 16)
    x = x * jnp.uint32(0x7FEB352D)
    x = x ^ (x >> 15)
    x = x * jnp.uint32(0x846CA68B)
    x = x ^ (x >> 16)
    return x


def flip_threshold(p) -> jax.Array:
    """uint32 threshold of a float32 bit-error probability `p`: a bit
    plane flips iff its hashed word is below p * 2^32. Computed outside
    the Pallas kernel bodies, because Mosaic has no float32 -> uint32
    cast."""
    return (jnp.asarray(p, jnp.float32) * 4294967296.0).astype(jnp.uint32)


def bit_flip_mask(rand: jax.Array, n_bits: int, thresh) -> jax.Array:
    """XOR mask with each of the low `n_bits` planes set iid w.p. p,
    derived from ONE uint32 word per element. `thresh` is
    `flip_threshold(p)` and broadcasts against `rand` (e.g. a per-row
    [R, 1] vector). Shared by the jnp paths and the Pallas kernel
    bodies (identical ops)."""
    flips = jnp.zeros_like(rand)
    for b in range(n_bits):
        salt = ((b + 1) * GOLDEN) & 0xFFFFFFFF
        r = fmix32(rand ^ jnp.uint32(salt))
        flips = flips | (jnp.where(r < thresh, jnp.uint32(1),
                                   jnp.uint32(0)) << b)
    return flips


# ---------------------------------------------------------------- manifest
@dataclasses.dataclass(frozen=True)
class WirePlan:
    """Static packed-buffer layout for one pytree (hashable: jit key)."""
    treedef: Any
    shapes: tuple              # per-packet logical shapes
    dtypes: tuple              # per-packet np.dtype
    sizes: tuple               # per-packet element counts
    rows: tuple                # per-packet row counts
    row_start: tuple           # per-packet first row
    cols: int
    n_rows: int                # R, padded to a multiple of _ROW_ALIGN

    @property
    def n_packets(self) -> int:
        return len(self.shapes)


def _plan_from_shapes(treedef, shapes, dtypes, cols: int) -> WirePlan:
    sizes = tuple(int(np.prod(s)) if s else 1 for s in shapes)
    rows = tuple(-(-s // cols) for s in sizes)
    starts, acc = [], 0
    for r in rows:
        starts.append(acc)
        acc += r
    n_rows = max(_ROW_ALIGN, -(-acc // _ROW_ALIGN) * _ROW_ALIGN)
    return WirePlan(treedef, tuple(tuple(s) for s in shapes), dtypes,
                    sizes, rows, tuple(starts), cols, n_rows)


def plan_for(tree, cols: int = WIRE_COLS) -> WirePlan:
    """Layout plan treating every leaf of `tree` as one packet."""
    leaves, treedef = jax.tree.flatten(tree)
    return _plan_from_shapes(treedef,
                             tuple(tuple(l.shape) for l in leaves),
                             tuple(np.dtype(l.dtype) for l in leaves), cols)


def _row_ids(plan: WirePlan) -> np.ndarray:
    """Static row -> packet-id map (final padding rows alias packet 0;
    they hold zeros, which cannot perturb a max|.| scale, and their
    output is discarded at unpack)."""
    ids = np.zeros(plan.n_rows, np.int32)
    for i, (r0, r) in enumerate(zip(plan.row_start, plan.rows)):
        ids[r0:r0 + r] = i
    return ids


# ------------------------------------------------------------- pack/unpack
def _pack_leaves(leaves, plan: WirePlan) -> jax.Array:
    parts = []
    for leaf, size, r in zip(leaves, plan.sizes, plan.rows):
        v = jnp.ravel(leaf).astype(jnp.float32)
        parts.append(jnp.pad(v, (0, r * plan.cols - size)))
    flat = jnp.concatenate(parts) if parts else jnp.zeros((0,), jnp.float32)
    flat = jnp.pad(flat, (0, plan.n_rows * plan.cols - flat.shape[0]))
    return flat.reshape(plan.n_rows, plan.cols)


def _unpack_leaves(buf: jax.Array, plan: WirePlan):
    flat = buf.reshape(-1)
    out = []
    for shape, dt, size, r0 in zip(plan.shapes, plan.dtypes, plan.sizes,
                                   plan.row_start):
        off = r0 * plan.cols
        out.append(flat[off:off + size].reshape(shape).astype(dt))
    return out


def pack_tree(tree, cols: int = WIRE_COLS):
    """-> (packed [R, cols] float32 buffer, WirePlan)."""
    plan = plan_for(tree, cols)
    return _pack_leaves(jax.tree.leaves(tree), plan), plan


def unpack_tree(buf: jax.Array, plan: WirePlan):
    """Inverse of pack_tree (padding discarded, dtypes restored)."""
    return jax.tree.unflatten(plan.treedef, _unpack_leaves(buf, plan))


# ----------------------------------------------------------------- faults
def fault_free(fading: bool = True, perfect: bool = False,
               arq_attempts: int = 1, arq_min_f2: float = 0.25,
               arq_max_tx: int = 0, ge_p_gb: float = 0.0) -> bool:
    """True iff this knob combination can neither retransmit nor erase —
    i.e. every packet costs exactly ONE transmission and always arrives.
    The replay helpers (`drawn_*`) use this to skip the draw entirely,
    and the schemes use it to keep the legacy billing paths bitwise."""
    if perfect:
        return True
    if ge_p_gb > 0.0:
        return False
    if arq_max_tx > 0:
        # bounded ARQ without fading: one clean tx, erasure impossible
        # unless the outage threshold exceeds the unit gain
        return (not fading) and arq_min_f2 <= 1.0
    return (not fading) or arq_attempts <= 1


def _ge_bad_states(kge, n: int, n_packets: int, p_gb: float, p_bg: float):
    """[n, n_packets] bool bad-link states of the two-state
    Gilbert-Elliott chain, one state per packet slot (every ARQ attempt
    of a packet shares its slot's state — that is what makes the outage
    BURSTY: a bad slot kills the whole retry window, unlike the iid
    per-attempt Rayleigh deep fades). The initial state is drawn from
    the stationary distribution pi_bad = p_gb / (p_gb + p_bg), so the
    marginal outage probability is cycle-position independent."""
    pi_bad = p_gb / max(p_gb + p_bg, 1e-12)
    k0, kc = jax.random.split(kge)
    b0 = jax.random.uniform(k0, (n,), jnp.float32) < pi_bad
    us = jax.random.uniform(kc, (n_packets, n), jnp.float32)

    def step(bad, u):
        nxt = jnp.where(bad, u >= p_bg, u < p_gb)
        return nxt, nxt

    _, bads = jax.lax.scan(step, b0, us)
    return bads.T


def backoff_s(n_tx, base_s: float):
    """Exponential-backoff wait billed to packets that took `n_tx`
    transmissions: retry j sleeps base * 2^(j-1), so a packet with k
    transmissions (k-1 retries) waited base * (2^(k-1) - 1) seconds
    total. Host-side accounting (np), returns a float scalar sum."""
    if base_s <= 0.0:
        return 0.0
    k = np.asarray(n_tx, np.float64)
    return float(base_s) * float(np.sum(np.exp2(k - 1.0) - 1.0))


# --------------------------------------------------------------- accounting
def expected_arq_tx(attempts: int = 1, min_f2: float = 0.25,
                    fading: bool = True, perfect: bool = False) -> float:
    """Analytic expected transmissions per packet under outage-ARQ:
    E[tx] = (1 - p_out^A) / (1 - p_out), p_out = P(|f|^2 < min_f2)
    = 1 - exp(-min_f2) for the unit-mean Rayleigh gain. Deterministic
    (the drawn n_tx is a traced value), so payload accounting stays a
    plain python float."""
    if attempts <= 1 or not fading or perfect:
        return 1.0
    p_out = 1.0 - math.exp(-min_f2)
    return (1.0 - p_out ** attempts) / (1.0 - p_out)


def drawn_tree_tx(key, n_packets: int = 1, fading: bool = True,
                  perfect: bool = False, arq_attempts: int = 1,
                  arq_min_f2: float = 0.25, arq_max_tx: int = 0,
                  ge_p_gb: float = 0.0, ge_p_bg: float = 0.5):
    """Total DRAWN transmissions of a `transmit_tree(key, tree, ...)`
    call whose tree has `n_packets` leaves, WITHOUT transmitting: the
    per-packet fade/ARQ redraw is a pure function of the key (same
    `split`, same uniform stream as `_packet_fades`), so a crossing
    that happened inside a jitted train step — where the diagnostics
    cannot escape — can still be billed at its actual retransmission
    cost by replaying the draw outside. Returns an int32 scalar
    (vmap-friendly); equals `n_packets` without ARQ/fading."""
    if fault_free(fading, perfect, arq_attempts, arq_min_f2, arq_max_tx,
                  ge_p_gb):
        return jnp.int32(n_packets)
    kf, _ = jax.random.split(key)
    _, n_tx, _ = _packet_fades(kf, 1, n_packets, fading, arq_attempts,
                               arq_min_f2, arq_max_tx, ge_p_gb, ge_p_bg)
    return n_tx.sum().astype(jnp.int32)


def drawn_tree_diag(key, n_packets: int = 1, fading: bool = True,
                    perfect: bool = False, arq_attempts: int = 1,
                    arq_min_f2: float = 0.25, arq_max_tx: int = 0,
                    ge_p_gb: float = 0.0, ge_p_bg: float = 0.5):
    """(n_tx_sum, n_erased, backoff_units) of a `transmit_tree` draw,
    without transmitting — the fault-aware superset of `drawn_tree_tx`.
    All three are traced scalars (vmap-friendly): total transmissions
    (int32), erased-packet count (int32), and backoff units (float32,
    sum over packets of 2^(n_tx-1) - 1 — multiply by `arq_backoff_s`
    for seconds). (n_packets, 0, 0) when `fault_free`."""
    if fault_free(fading, perfect, arq_attempts, arq_min_f2, arq_max_tx,
                  ge_p_gb):
        return jnp.int32(n_packets), jnp.int32(0), jnp.float32(0.0)
    kf, _ = jax.random.split(key)
    _, n_tx, erased = _packet_fades(kf, 1, n_packets, fading, arq_attempts,
                                    arq_min_f2, arq_max_tx, ge_p_gb,
                                    ge_p_bg)
    bo = jnp.exp2((n_tx - 1).astype(jnp.float32)) - 1.0
    return n_tx.sum().astype(jnp.int32), erased.sum().astype(jnp.int32), \
        bo.sum()


def drawn_stacked_tx(key, n: int, n_packets: int, fading: bool = True,
                     perfect: bool = False, arq_attempts: int = 1,
                     arq_min_f2: float = 0.25, arq_max_tx: int = 0,
                     ge_p_gb: float = 0.0, ge_p_bg: float = 0.5,
                     with_erased: bool = False):
    """Per-(user, packet) DRAWN transmission counts of a
    `transmit_stacked(key, tree, ...)` call with `n` users and
    `n_packets` leaves, WITHOUT transmitting — the stacked-send analogue
    of `drawn_tree_tx` (same `split`, same uniform stream as
    `_packet_fades`). Returns a host [n, n_packets] int array, so a
    scheme can bill a sync that happened INSIDE a jitted train step
    (the pod-mesh FL step) at its actual per-packet retransmission
    cost. All-ones without ARQ/fading. `with_erased=True` additionally
    returns the [n, n_packets] bool erasure mask (all-False when
    `fault_free`)."""
    if fault_free(fading, perfect, arq_attempts, arq_min_f2, arq_max_tx,
                  ge_p_gb):
        n_tx = np.ones((n, n_packets), np.int64)
        return (n_tx, np.zeros((n, n_packets), bool)) if with_erased \
            else n_tx
    kf, _ = jax.random.split(key)
    _, n_tx, erased = _packet_fades(kf, n, n_packets, fading, arq_attempts,
                                    arq_min_f2, arq_max_tx, ge_p_gb,
                                    ge_p_bg)
    n_tx = np.asarray(n_tx)
    return (n_tx, np.asarray(erased)) if with_erased else n_tx


def wire_width(wire_dtype: str, bits: int) -> int:
    """Billed on-air bits PER CODEWORD for a wire dtype. The float32
    wire transports abstract b-bit symbols, so it bills the quantizer
    width; the byte-packed dtypes bill their physical container width —
    int8 is one byte per codeword regardless of Q, int4 packs two
    codewords per byte. THE one width rule every bill shares (Radio
    delivery, scheme key-replay billing, payload_bits)."""
    if wire_dtype == "int8":
        return 8
    if wire_dtype == "int4":
        return 4
    return int(bits)


def payload_bits(tree, bits: int, expected_tx: float = 1.0,
                 wire_dtype: str = "float32") -> float:
    """On-air payload of transmitting every leaf of `tree` at b-bit
    quantization, scaled by the expected (ARQ) transmission count.
    The ONE accounting helper for FL uploads and SL legs — always a
    float, so int/float mixing between call sites is gone. With a
    packed `wire_dtype` the billed width is the container's
    (`wire_width`): int4 at Q<=4 bills half the bits of int8."""
    n = sum(int(l.size) for l in jax.tree.leaves(tree))
    return float(n) * float(wire_width(wire_dtype, bits)) \
        * float(expected_tx)


# ------------------------------------------------------------ fused channel
def wire_transform(buf: jax.Array, rand: jax.Array, scale, p, bits: int,
                   code_dtype=jnp.uint32, stochastic: bool = False,
                   nibble_packed: bool = False) -> jax.Array:
    """The fused quantize -> BPSK/Rayleigh bit-flip -> dequantize math on
    a packed buffer. `scale`/`p` broadcast against `buf` (per-row
    [..., R, 1] vectors). Identical ops to the Pallas kernel body — this
    IS the reference.

    `code_dtype=jnp.uint8` is the ON-WIRE int8 mode (quant_bits <= 8):
    the codewords live as one byte per element between quantize and
    dequantize instead of staying float32 end-to-end — 4x less HBM
    traffic for the buffer that actually crosses the link. The codes,
    the flip mask (low `bits` planes of the same Murmur3 stream, which
    fit a byte), and the dequantized output are bit-identical to the
    uint32 path (tested in tests/test_wire.py).

    `stochastic=True` (opt-in, wcfg.rounding="stochastic") rounds the
    codewords stochastically instead of to nearest, with the uniform
    derived from the SAME per-element rand word through one extra
    fmix32 salt (_SR_SALT, disjoint from every bit plane) — unbiased
    quantization at zero extra RNG draws.

    `nibble_packed=True` is the ON-WIRE int4 mode (quant_bits <= 4):
    adjacent codeword pairs along the last axis share one byte between
    quantize and dequantize (Q.pack_nibbles). Flips are still derived
    per-codeword from each element's OWN rand word — the flip-mask
    bytes are packed the same way and XORed against the packed buffer —
    so the output is bit-identical to the float32/uint32 path at the
    same Q (tested in tests/test_wire.py)."""
    qm = float(2 ** (bits - 1) - 1)
    x = buf / scale
    if stochastic:
        u = fmix32(rand ^ jnp.uint32(_SR_SALT)).astype(jnp.float32) \
            * jnp.float32(2.0 ** -32)
        r = Q.stochastic_round(x.astype(jnp.float32), u)
    else:
        r = jnp.round(x)
    q = jnp.clip(r, -qm, qm).astype(jnp.int32)
    flips = bit_flip_mask(rand, bits, flip_threshold(p))
    if nibble_packed:
        # bits <= 4 -> codes and flip masks both fit one nibble
        byte = Q.pack_nibbles((q + jnp.int32(qm)).astype(jnp.uint32))
        byte = byte ^ Q.pack_nibbles(flips)
        q_hat = jnp.clip(Q.unpack_nibbles(byte) - jnp.int32(qm), -qm, qm)
        return (q_hat.astype(jnp.float32) * scale).astype(buf.dtype)
    code = (q + jnp.int32(qm)).astype(code_dtype)
    code = code ^ flips.astype(code_dtype)
    q_hat = jnp.clip(code.astype(jnp.int32) - jnp.int32(qm), -qm, qm)
    return (q_hat.astype(jnp.float32) * scale).astype(buf.dtype)


def _packet_fades(kf, n: int, n_packets: int, fading: bool,
                  arq_attempts: int, arq_min_f2: float,
                  arq_max_tx: int = 0, ge_p_gb: float = 0.0,
                  ge_p_bg: float = 0.5):
    """(|f|^2, n_tx, erased) per (user, packet) — ONE batched uniform
    draw. With ARQ, deep fades are redrawn up to `arq_attempts` times
    (vectorized rayleigh_gain_arq); n_tx is the DRAWN per-packet
    transmission count (1 everywhere without ARQ), surfaced so
    accounting can report actual rather than expected retransmissions.

    Fault extensions (both off by default, legacy draws untouched):
    `arq_max_tx > 0` caps the link at that many transmissions — a
    packet whose every attempt fails is ERASED (erased=True; the
    transmit paths zero its payload). `ge_p_gb > 0` switches on the
    two-state Gilbert-Elliott burst process (states drawn off
    fold_in(kf, _GE_FOLD), a stream disjoint from the fade uniforms):
    an attempt in the bad state always fails, and a packet that never
    escapes the bad window delivers |f|^2 = 0 (pure noise) when
    unbounded, or an erasure when bounded."""
    ones = jnp.ones((n, n_packets), jnp.int32)
    no_erase = jnp.zeros((n, n_packets), bool)
    if arq_max_tx <= 0 and ge_p_gb <= 0.0:        # legacy, byte-identical
        if not fading:
            return jnp.ones((n, n_packets), jnp.float32), ones, no_erase
        if arq_attempts > 1:
            u = jax.random.uniform(kf, (n, n_packets, arq_attempts),
                                   jnp.float32, 1e-12, 1.0)
            f2s = -jnp.log(u)
            ok = f2s >= arq_min_f2
            any_ok = ok.any(axis=-1)
            first = jnp.argmax(ok, axis=-1)
            idx = jnp.where(any_ok, first, arq_attempts - 1)
            n_tx = jnp.where(any_ok, first + 1,
                             arq_attempts).astype(jnp.int32)
            return jnp.take_along_axis(f2s, idx[..., None],
                                       axis=-1)[..., 0], n_tx, no_erase
        u = jax.random.uniform(kf, (n, n_packets), jnp.float32, 1e-12, 1.0)
        return -jnp.log(u), ones, no_erase

    attempts = arq_max_tx if arq_max_tx > 0 else max(int(arq_attempts), 1)
    if fading:
        u = jax.random.uniform(kf, (n, n_packets, attempts),
                               jnp.float32, 1e-12, 1.0)
        f2s = -jnp.log(u)
    else:
        f2s = jnp.ones((n, n_packets, attempts), jnp.float32)
    ok = f2s >= arq_min_f2
    bad = no_erase
    if ge_p_gb > 0.0:
        bad = _ge_bad_states(jax.random.fold_in(kf, _GE_FOLD), n,
                             n_packets, ge_p_gb, ge_p_bg)
        ok = ok & ~bad[..., None]
    any_ok = ok.any(axis=-1)
    first = jnp.argmax(ok, axis=-1)
    idx = jnp.where(any_ok, first, attempts - 1)
    n_tx = jnp.where(any_ok, first + 1, attempts).astype(jnp.int32)
    f2 = jnp.take_along_axis(f2s, idx[..., None], axis=-1)[..., 0]
    # a packet that never left the bad state has NO received signal —
    # |f|^2 = 0 makes every bit a coin flip, not a deep-but-live fade
    f2 = jnp.where(bad & ~any_ok, 0.0, f2)
    erased = (~any_ok) if arq_max_tx > 0 else no_erase
    return f2, n_tx, erased


def _transmit_per_leaf(leaves, plan: WirePlan, rand, p, bits: int):
    """Per-leaf reference loop: per-tensor scale (Q.quantize), shared
    hash flips on the SAME rand words the packed path uses. Bit-exactly
    equal to the packed output — and the shape of the per-round cost the
    packed wire removes (O(packets) separate op chains)."""
    n = rand.shape[0]
    outs = []
    for ui in range(n):
        row = []
        for i, leaf in enumerate(leaves):
            x = leaf[ui].astype(jnp.float32)
            q, s = Q.quantize(x, bits)
            code = Q.quantize_offset(q, bits)
            r0, nr, size = plan.row_start[i], plan.rows[i], plan.sizes[i]
            rs = rand[ui, r0:r0 + nr].reshape(-1)[:size].reshape(x.shape)
            code = code ^ bit_flip_mask(rs, bits, flip_threshold(p[ui, i]))
            q_hat = Q.unquantize_offset(code, bits)
            row.append(Q.dequantize(q_hat, s).astype(plan.dtypes[i]))
        outs.append(row)
    return tuple(jnp.stack([outs[ui][i] for ui in range(n)])
                 for i in range(len(leaves)))


@functools.partial(jax.jit, static_argnames=(
    "plan", "bits", "fading", "perfect", "arq_attempts", "arq_min_f2",
    "arq_max_tx", "ge_p_gb", "ge_p_bg", "rounding", "impl", "interpret",
    "wire_dtype"))
def _transmit_stacked_planned(key, leaves, plan: WirePlan, bits: int,
                              snr_db, fading: bool, perfect: bool,
                              arq_attempts: int, arq_min_f2: float,
                              impl: str, interpret: bool,
                              wire_dtype: str = "float32",
                              arq_max_tx: int = 0, ge_p_gb: float = 0.0,
                              ge_p_bg: float = 0.5,
                              rounding: str = "nearest"):
    """One fused pass over a stacked tuple of leaves ([N, *shape_i]).
    Returns (received leaves (same stacked shapes), n_tx [N, P] drawn
    per-packet transmission counts, erased [N, P] bool erasure mask).
    Erased packets (bounded ARQ exhausted, see _packet_fades) arrive
    as ZEROS — the receiver knows the CRC failed and substitutes the
    additive identity, which is what lets quorum aggregation weight
    them out without a second pass."""
    from repro.core import channel as CH  # lazy: channel imports wire

    n = leaves[0].shape[0] if leaves else 1
    npk = plan.n_packets
    kf, kb = jax.random.split(key)
    if perfect:
        p = jnp.zeros((n, npk), jnp.float32)
        n_tx = jnp.ones((n, npk), jnp.int32)
        erased = jnp.zeros((n, npk), bool)
    else:
        f2, n_tx, erased = _packet_fades(kf, n, npk, fading, arq_attempts,
                                         arq_min_f2, arq_max_tx, ge_p_gb,
                                         ge_p_bg)
        p = CH.bpsk_bit_error_prob(snr_db, f2)
    rand = jax.random.bits(kb, (n, plan.n_rows, plan.cols), jnp.uint32)
    can_erase = (not perfect) and arq_max_tx > 0

    if impl == "per_leaf":
        out = _transmit_per_leaf(leaves, plan, rand, p, bits)
        if can_erase:
            out = tuple(
                jnp.where(erased[:, i].reshape((n,) + (1,) * (o.ndim - 1)),
                          jnp.zeros((), o.dtype), o)
                for i, o in enumerate(out))
        return out, n_tx, erased

    buf = jax.vmap(lambda *ls: _pack_leaves(ls, plan))(*leaves)  # [n, R, C]
    row_id = jnp.asarray(_row_ids(plan))
    # Per-packet amax from the LEAVES (plain max reductions), not a
    # segment_max over the packed buffer: bit-identical (padding rows
    # are zero), and SPMD-safe — the scatter-max lowering miscombined
    # per-shard partials when XLA sharded the buffer rows on the pod
    # mesh, scaling the dequantize by the replica count (caught by the
    # scaled-FL pod-mesh parity check, tests/dist_checks.py).
    amax = jnp.stack(
        [jnp.max(jnp.abs(l.reshape(l.shape[0], -1).astype(jnp.float32)),
                 axis=1) for l in leaves], axis=1)                # [n, P]
    scale = jnp.maximum(amax, 1e-12) / Q.qmax(bits)
    scale_row = jnp.take(scale, row_id, axis=1)[..., None]        # [n, R, 1]
    p_row = jnp.take(p, row_id, axis=1)[..., None]                # [n, R, 1]

    if impl == "kernel":
        from repro.kernels.quant_channel import kernel as K
        r, c = plan.n_rows, plan.cols
        # Opt-in TPU in-kernel PRNG (K.TPU_KERNEL_RNG): compiled-TPU
        # runs draw the rand words inside the kernel from a seed folded
        # off kb — a DIFFERENT stream than the host jax.random.bits
        # words, which is why it hides behind the flag (host-vs-kernel
        # bitwise parity only holds with it off).
        tpu_rng = K.TPU_KERNEL_RNG and not interpret
        seed = jax.random.bits(kb, (1, 1), jnp.uint32).astype(jnp.int32) \
            if tpu_rng else None
        y = K.packed_wire_2d(buf.reshape(n * r, c), rand.reshape(n * r, c),
                             scale_row.reshape(n * r, 1),
                             p_row.reshape(n * r, 1), bits,
                             interpret=interpret,
                             wire_dtype=wire_dtype,
                             rng_mode=("tpu" if tpu_rng else "host"),
                             seed=seed).reshape(n, r, c)
    else:
        y = wire_transform(buf, rand, scale_row, p_row, bits,
                           code_dtype=(jnp.uint8 if wire_dtype == "int8"
                                       else jnp.uint32),
                           stochastic=(rounding == "stochastic"),
                           nibble_packed=(wire_dtype == "int4"))
    if can_erase:
        erased_row = jnp.take(erased, row_id, axis=1)[..., None]  # [n, R, 1]
        y = jnp.where(erased_row, jnp.zeros((), y.dtype), y)
    return jax.vmap(lambda b: tuple(_unpack_leaves(b, plan)))(y), n_tx, \
        erased


def _check_wire_dtype(wire_dtype: str, bits: int, impl: str) -> str:
    if wire_dtype not in ("float32", "int8", "int4"):
        raise ValueError(f"unknown wire_dtype {wire_dtype!r}")
    if wire_dtype != "float32":
        width = 8 if wire_dtype == "int8" else 4
        if bits > width:
            raise ValueError(
                f"{wire_dtype} on-wire dtype holds at most {width}-bit "
                f"codewords, got quant_bits={bits}")
        if impl not in ("packed", "kernel"):
            raise ValueError(
                f"wire_dtype={wire_dtype!r} is only implemented for the "
                f"packed jnp and Pallas kernel paths, not impl={impl!r}")
    return wire_dtype


def _check_rounding(rounding: str, impl: str) -> str:
    if rounding not in ("nearest", "stochastic"):
        raise ValueError(f"unknown rounding {rounding!r}")
    if rounding == "stochastic" and impl != "packed":
        raise ValueError(
            "rounding='stochastic' is only implemented for the packed "
            f"jnp path, not impl={impl!r} (the Pallas kernel body and "
            "the per-leaf reference still round to nearest)")
    return rounding


def transmit_stacked(key, tree, bits: int, snr_db, fading: bool = True,
                     perfect: bool = False, arq_attempts: int = 1,
                     arq_min_f2: float = 0.25, impl: str = "packed",
                     interpret: bool | None = None,
                     return_diag: bool = False,
                     wire_dtype: str = "float32", arq_max_tx: int = 0,
                     ge_p_gb: float = 0.0, ge_p_bg: float = 0.5,
                     rounding: str = "nearest"):
    """Fused transmit of a tree whose leaves carry a leading user axis
    [N, ...]: each (user, leaf) pair is one packet with its own fade and
    per-tensor quantization scale — FL's whole N-user upload in one
    jitted call (one kernel launch under impl="kernel").

    With return_diag=True also returns {"n_tx": [N, P] int32,
    "erased": [N, P] bool}: the DRAWN per-(user, packet) ARQ
    transmission counts (all-ones without ARQ) — the actual on-air
    cost, vs the analytic `expected_arq_tx` — and the bounded-ARQ
    erasure mask (all-False unless arq_max_tx > 0; erased packets
    arrive zeroed).

    Fault knobs: `arq_max_tx` bounds the ARQ (exhaustion = erasure),
    `ge_p_gb`/`ge_p_bg` drive the Gilbert-Elliott burst-outage chain,
    `rounding="stochastic"` opts into unbiased codeword rounding
    (packed impl only). All default off, leaving every legacy draw and
    output bitwise intact.

    `wire_dtype="int8"` (quant_bits <= 8, packed impl) carries the
    codeword buffer as one byte per element across the channel instead
    of float32 — bit-identical output, 4x less on-wire HBM traffic.
    `wire_dtype="int4"` (quant_bits <= 4) packs TWO codewords per byte
    (Q.pack_nibbles) — still bit-identical to the float path at the
    same Q, and `payload_bits`/Radio bill the halved container width
    (`wire_width`)."""
    leaves, treedef = jax.tree.flatten(tree)
    if not leaves:
        return (tree, {"n_tx": jnp.zeros((1, 0), jnp.int32),
                       "erased": jnp.zeros((1, 0), bool)}) \
            if return_diag else tree
    plan = _plan_from_shapes(treedef,
                             tuple(tuple(l.shape[1:]) for l in leaves),
                             tuple(np.dtype(l.dtype) for l in leaves),
                             WIRE_COLS)
    out, n_tx, erased = _transmit_stacked_planned(
        key, tuple(leaves), plan, int(bits), snr_db, bool(fading),
        bool(perfect), int(arq_attempts), float(arq_min_f2), impl,
        resolve_interpret(interpret),
        wire_dtype=_check_wire_dtype(wire_dtype, int(bits), impl),
        arq_max_tx=int(arq_max_tx), ge_p_gb=float(ge_p_gb),
        ge_p_bg=float(ge_p_bg),
        rounding=_check_rounding(rounding, impl))
    rx = jax.tree.unflatten(treedef, list(out))
    return (rx, {"n_tx": n_tx, "erased": erased}) if return_diag else rx


@functools.partial(jax.jit, static_argnames=(
    "plan", "bits", "fading", "perfect", "arq_attempts", "arq_min_f2",
    "arq_max_tx", "ge_p_gb", "ge_p_bg", "impl", "interpret", "wire_dtype"))
def _transmit_stacked_mean_planned(key, leaves, plan: WirePlan, bits: int,
                                   snr_db, fading: bool, perfect: bool,
                                   arq_attempts: int, arq_min_f2: float,
                                   impl: str, interpret: bool,
                                   wire_dtype: str = "float32",
                                   arq_max_tx: int = 0,
                                   ge_p_gb: float = 0.0,
                                   ge_p_bg: float = 0.5):
    """The fused quantize -> channel -> dequantize -> WEIGHTED-MEAN pass
    over a stacked N-user upload: the dequantized [N, R, C] buffer is
    never materialized — each user's received rows are scaled by the
    alive-weight and accumulated straight into the [R, C] aggregate
    (one kernel launch under impl="kernel", with the user axis as the
    innermost accumulation grid dim). Returns (mean leaves (UNstacked),
    n_tx, erased, n_alive). Weights are uniform over alive users
    (1/n_alive; a user with ANY erased packet counts dead); when every
    user is erased the aggregate is all-zeros and n_alive == 0 — the
    caller picks its own fallback. The jnp path accumulates users in
    the same ascending order, so packed and kernel outputs are
    bit-identical in interpret mode; NOTE the ordered weighted sum is
    NOT bitwise-equal to dequant-then-`jnp.mean` (different reduction
    order), which is why the FL step only takes this path under
    `use_kernel`."""
    from repro.core import channel as CH  # lazy: channel imports wire

    n = leaves[0].shape[0] if leaves else 1
    npk = plan.n_packets
    kf, kb = jax.random.split(key)
    if perfect:
        p = jnp.zeros((n, npk), jnp.float32)
        n_tx = jnp.ones((n, npk), jnp.int32)
        erased = jnp.zeros((n, npk), bool)
    else:
        f2, n_tx, erased = _packet_fades(kf, n, npk, fading, arq_attempts,
                                         arq_min_f2, arq_max_tx, ge_p_gb,
                                         ge_p_bg)
        p = CH.bpsk_bit_error_prob(snr_db, f2)
    rand = jax.random.bits(kb, (n, plan.n_rows, plan.cols), jnp.uint32)
    can_erase = (not perfect) and arq_max_tx > 0

    alive = ~erased.any(axis=1) if can_erase \
        else jnp.ones((n,), bool)                                  # [N]
    n_alive = alive.sum().astype(jnp.int32)
    w = alive.astype(jnp.float32) / jnp.maximum(n_alive, 1)        # [N]

    buf = jax.vmap(lambda *ls: _pack_leaves(ls, plan))(*leaves)    # [n, R, C]
    row_id = jnp.asarray(_row_ids(plan))
    amax = jnp.stack(
        [jnp.max(jnp.abs(l.reshape(l.shape[0], -1).astype(jnp.float32)),
                 axis=1) for l in leaves], axis=1)                 # [n, P]
    scale = jnp.maximum(amax, 1e-12) / Q.qmax(bits)
    scale_row = jnp.take(scale, row_id, axis=1)[..., None]         # [n, R, 1]
    p_row = jnp.take(p, row_id, axis=1)[..., None]                 # [n, R, 1]

    r, c = plan.n_rows, plan.cols
    if impl == "kernel":
        from repro.kernels.quant_channel.kernel import packed_wire_mean_2d
        w_row = jnp.broadcast_to(w[:, None, None], (n, r, 1))
        acc = packed_wire_mean_2d(
            buf.reshape(n * r, c), rand.reshape(n * r, c),
            scale_row.reshape(n * r, 1), p_row.reshape(n * r, 1),
            w_row.reshape(n * r, 1), bits, n, interpret=interpret,
            wire_dtype=wire_dtype)
    else:
        y = wire_transform(buf, rand, scale_row, p_row, bits,
                           code_dtype=(jnp.uint8 if wire_dtype == "int8"
                                       else jnp.uint32),
                           nibble_packed=(wire_dtype == "int4"))
        # Ascending-user accumulation of the MATERIALIZED products, via
        # scan: the loop boundary stops XLA contracting w*y + acc into
        # an FMA, so each product is rounded to float32 before the add —
        # exactly what the kernel's store-then-accumulate does (bitwise
        # parity in interpret mode, pinned in tests/test_wire.py).
        prods = w[:, None, None] * y                       # [n, R, C]
        acc = jax.lax.scan(lambda a, pr: (a + pr, None),
                           jnp.zeros((r, c), jnp.float32), prods)[0]
    return tuple(_unpack_leaves(acc, plan)), n_tx, erased, n_alive


def transmit_stacked_mean(key, tree, bits: int, snr_db,
                          fading: bool = True, perfect: bool = False,
                          arq_attempts: int = 1, arq_min_f2: float = 0.25,
                          impl: str = "kernel",
                          interpret: bool | None = None,
                          wire_dtype: str = "float32", arq_max_tx: int = 0,
                          ge_p_gb: float = 0.0, ge_p_bg: float = 0.5):
    """Fused transmit-and-aggregate of a stacked [N, ...] upload: one
    pass computes what `transmit_stacked` + dequantized alive-weighted
    mean would, without materializing the received [N, ...] tree.
    Returns (mean_tree with UNstacked leaves, {"n_tx", "erased",
    "n_alive"}). Same key contract, fades, rand stream and billing
    draws as `transmit_stacked` — `drawn_stacked_tx` replays this
    call's costs identically. The aggregation itself is an ordered
    weighted sum, allclose-but-not-bitwise to the legacy
    dequant-then-mean (see _transmit_stacked_mean_planned)."""
    leaves, treedef = jax.tree.flatten(tree)
    if not leaves:
        return tree, {"n_tx": jnp.zeros((1, 0), jnp.int32),
                      "erased": jnp.zeros((1, 0), bool),
                      "n_alive": jnp.int32(0)}
    plan = _plan_from_shapes(treedef,
                             tuple(tuple(l.shape[1:]) for l in leaves),
                             tuple(np.dtype(l.dtype) for l in leaves),
                             WIRE_COLS)
    out, n_tx, erased, n_alive = _transmit_stacked_mean_planned(
        key, tuple(leaves), plan, int(bits), snr_db, bool(fading),
        bool(perfect), int(arq_attempts), float(arq_min_f2), impl,
        resolve_interpret(interpret),
        wire_dtype=_check_wire_dtype(wire_dtype, int(bits), impl),
        arq_max_tx=int(arq_max_tx), ge_p_gb=float(ge_p_gb),
        ge_p_bg=float(ge_p_bg))
    rx = jax.tree.unflatten(treedef, list(out))
    return rx, {"n_tx": n_tx, "erased": erased, "n_alive": n_alive}


def transmit_tree(key, tree, bits: int, snr_db, fading: bool = True,
                  perfect: bool = False, arq_attempts: int = 1,
                  arq_min_f2: float = 0.25, impl: str = "packed",
                  interpret: bool | None = None,
                  return_diag: bool = False,
                  wire_dtype: str = "float32", arq_max_tx: int = 0,
                  ge_p_gb: float = 0.0, ge_p_bg: float = 0.5,
                  rounding: str = "nearest"):
    """Fused transmit of an arbitrary pytree: one fade + one per-tensor
    scale per leaf, one RNG draw and one quantize/channel/dequantize
    pass for the whole tree. Drop-in replacement for the per-leaf
    transmit loop; `impl` selects packed-jnp (default), the Pallas
    kernel, or the bit-identical per-leaf reference.

    With return_diag=True also returns {"n_tx": [P] int32,
    "erased": [P] bool} drawn per-packet transmission counts and
    erasure mask (see transmit_stacked). Fault knobs and
    `wire_dtype="int8"`: see transmit_stacked."""
    leaves, treedef = jax.tree.flatten(tree)
    if not leaves:
        return (tree, {"n_tx": jnp.zeros((0,), jnp.int32),
                       "erased": jnp.zeros((0,), bool)}) \
            if return_diag else tree
    plan = _plan_from_shapes(treedef,
                             tuple(tuple(l.shape) for l in leaves),
                             tuple(np.dtype(l.dtype) for l in leaves),
                             WIRE_COLS)
    stacked = tuple(l[None] for l in leaves)
    out, n_tx, erased = _transmit_stacked_planned(
        key, stacked, plan, int(bits), snr_db, bool(fading), bool(perfect),
        int(arq_attempts), float(arq_min_f2), impl,
        resolve_interpret(interpret),
        wire_dtype=_check_wire_dtype(wire_dtype, int(bits), impl),
        arq_max_tx=int(arq_max_tx), ge_p_gb=float(ge_p_gb),
        ge_p_bg=float(ge_p_bg),
        rounding=_check_rounding(rounding, impl))
    rx = jax.tree.unflatten(treedef, [o[0] for o in out])
    return (rx, {"n_tx": n_tx[0], "erased": erased[0]}) \
        if return_diag else rx
