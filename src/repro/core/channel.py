"""Wireless channel: Rayleigh fading + AWGN over BPSK (paper Eq. 10).

Physical chain (Alg. 1/2): quantize -> encode bits -> BPSK modulate ->
z_hat = f*z + n -> coherent demod -> decode bits -> dequantize.

TPU adaptation (DESIGN.md §5): with BPSK, coherent detection, and a known
fading coefficient f, each *bit* is flipped independently with probability

    p = Q( sqrt(2 |f|^2 SNR) ),   Q(x) = 0.5 erfc(x / sqrt 2)

so the whole modulate/fade/demodulate chain is *exactly* equivalent to
XOR-ing the quantized codewords with Bernoulli(p) bit noise — a fully
vectorized VPU-friendly formulation (no per-bit Python loop). The Pallas
kernel `kernels/quant_channel` fuses this with blockwise quantization.

Rayleigh fading: f = sqrt(e/2)*(g1 + i g2) with g ~ N(0,1) => |f|^2 ~
Exp(1) (unit mean). The paper draws one f per transmission ("uniformly
affects all transmitted signals").

RNG scheme: Bernoulli(p) bit noise is derived from ONE uint32 random
word per element — bit plane b flips iff fmix32(word ^ (b+1)*GOLDEN)
< p * 2^32 (core/wire.py, shared with the Pallas kernel) — so RNG cost
does not scale with the bit width. Whole-pytree transmissions
(transmit_pytree) route through the packed wire (core/wire.py): one
fused quantize/channel/dequantize pass per tree instead of a per-leaf
Python loop.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.scipy.special import erfc

from repro.core import quantization as Q
from repro.core import wire as W


def snr_linear(snr_db) -> jax.Array:
    return 10.0 ** (jnp.asarray(snr_db, jnp.float32) / 10.0)


def rayleigh_gain(key) -> jax.Array:
    """|f|^2 with E[|f|^2] = 1 (one draw per transmission)."""
    u = jax.random.uniform(key, (), jnp.float32, 1e-12, 1.0)
    return -jnp.log(u)


def rayleigh_gain_arq(key, attempts: int, min_f2: float):
    """Outage-aware ARQ (beyond-paper): redraw the fade up to `attempts`
    times until |f|^2 >= min_f2 (the receiver NACKs deep fades — what a
    real link-layer does). Returns (|f|^2 used, transmissions used).
    Under per-tensor Rayleigh draws, the occasional |f|^2 << 1 deep fade
    flips weight MSBs and is what collapses FL below ~15 dB
    (EXPERIMENTS.md §Repro fig3c note)."""
    u = jax.random.uniform(key, (attempts,), jnp.float32, 1e-12, 1.0)
    f2s = -jnp.log(u)
    ok = f2s >= min_f2
    first = jnp.argmax(ok)                       # first passing draw
    idx = jnp.where(ok.any(), first, attempts - 1)
    n_tx = jnp.where(ok.any(), first + 1, attempts)
    return f2s[idx], n_tx


def bpsk_bit_error_prob(snr_db, f2) -> jax.Array:
    """p = Q(sqrt(2 |f|^2 SNR)) for coherent BPSK."""
    arg = jnp.sqrt(2.0 * f2 * snr_linear(snr_db))
    return 0.5 * erfc(arg / jnp.sqrt(2.0))


def flip_bits(key, codewords: jax.Array, n_bits: int, p) -> jax.Array:
    """XOR codewords (uint32, values < 2^n_bits) with iid Bernoulli(p)
    bits. One `jax.random.bits` draw + the Murmur3 bit-plane finalizer
    (shared with the Pallas wire kernel) — constant RNG cost in n_bits,
    where the old path paid `n_bits` separate bernoulli draws. `p`
    broadcasts against `codewords` (per-row fading)."""
    rand = jax.random.bits(key, codewords.shape, jnp.uint32)
    return codewords ^ W.bit_flip_mask(rand, n_bits, W.flip_threshold(p))


def transmit_quantized(key, x: jax.Array, bits: int, snr_db: float,
                       fading: bool = True, perfect: bool = False,
                       arq_attempts: int = 1, arq_min_f2: float = 0.25):
    """Full chain on one tensor. Returns (x_hat, diag dict). With
    arq_attempts > 1, deep fades are re-drawn (link-layer ARQ) and the
    diag carries the transmission count for energy accounting."""
    q, s = Q.quantize(x, bits)
    if perfect:
        return Q.dequantize(q, s, x.dtype), {"f2": jnp.float32(1.0),
                                             "ber": jnp.float32(0.0),
                                             "n_tx": jnp.int32(1)}
    kf, kb = jax.random.split(key)
    if not fading:
        f2, n_tx = jnp.float32(1.0), jnp.int32(1)
    elif arq_attempts > 1:
        f2, n_tx = rayleigh_gain_arq(kf, arq_attempts, arq_min_f2)
    else:
        f2, n_tx = rayleigh_gain(kf), jnp.int32(1)
    p = bpsk_bit_error_prob(snr_db, f2)
    code = Q.quantize_offset(q, bits)
    code = flip_bits(kb, code, bits, p)
    q_hat = Q.unquantize_offset(code, bits)
    return Q.dequantize(q_hat, s, x.dtype), {"f2": f2, "ber": p,
                                             "n_tx": n_tx}


def transmit_tokens(key, tokens: jax.Array, vocab_size: int, snr_db: float,
                    fading: bool = True) -> jax.Array:
    """CL uplink: raw token ids cross the channel as fixed-width codewords
    (the paper's CL transmits raw data; bit errors corrupt tokens).

    One Rayleigh draw per ROW (= one packet per tweet): a bulk upload far
    exceeds the channel coherence time, so a single fade for the whole
    dataset would make the corruption all-or-nothing."""
    n_bits = max(1, (int(vocab_size) - 1).bit_length())
    kf, kb = jax.random.split(key)
    if fading:
        n_rows = tokens.shape[0] if tokens.ndim > 1 else 1
        u = jax.random.uniform(kf, (n_rows,), jnp.float32, 1e-12, 1.0)
        f2 = -jnp.log(u)
        if tokens.ndim > 1:
            f2 = f2.reshape((n_rows,) + (1,) * (tokens.ndim - 1))
    else:
        f2 = jnp.float32(1.0)
    p = bpsk_bit_error_prob(snr_db, f2)
    code = flip_bits(kb, tokens.astype(jnp.uint32), n_bits, p)
    return jnp.minimum(code, vocab_size - 1).astype(tokens.dtype)


# --------------------------------------------------------------- SL link
@functools.partial(jax.custom_vjp,
                   nondiff_argnums=(2, 3, 4, 5, 6, 7, 8, 9, 10, 11))
def channel_crossing(x, key, bits, snr_db, fading, grad_clip, perfect,
                     arq_attempts=1, arq_min_f2=0.25, arq_max_tx=0,
                     ge_p_gb=0.0, ge_p_bg=0.5):
    """The SL radio boundary (Alg. 2): the forward activation AND the
    backward gradient both traverse quantize->BPSK->Rayleigh+AWGN.
    The gradient is norm-clipped to `grad_clip` (tau) before transmission.

    Both legs go through the packed wire (core/wire.py), so the jitted
    SL train step and the two-party `SLSession` share ONE wire
    implementation: same per-tensor scale, same Murmur3 bit-plane RNG,
    same fused quantize/bit-flip/dequantize pass — including the
    link-layer ARQ redraw of deep fades (`arq_attempts`/`arq_min_f2`)
    and the fault extensions (bounded ARQ `arq_max_tx`, Gilbert-Elliott
    burst outages `ge_p_gb`/`ge_p_bg`) — so the fused path runs the
    SAME link the two-party protocol does. An ERASED leg arrives as
    zeros: a zero forward activation lets the server step on a null
    feature batch and a zero backward gradient makes the user step a
    no-op — graceful degradation, not a crash. The drawn counts cannot
    escape the jitted step; accounting replays them outside via
    `wire.drawn_tree_tx`/`drawn_tree_diag` (see schemes/split.py
    `sl_cycle_drawn_tx`).
    """
    return W.transmit_tree(key, x, bits=bits, snr_db=snr_db, fading=fading,
                           perfect=perfect, arq_attempts=arq_attempts,
                           arq_min_f2=arq_min_f2, arq_max_tx=arq_max_tx,
                           ge_p_gb=ge_p_gb, ge_p_bg=ge_p_bg)


def _cc_fwd(x, key, bits, snr_db, fading, grad_clip, perfect,
            arq_attempts, arq_min_f2, arq_max_tx, ge_p_gb, ge_p_bg):
    return channel_crossing(x, key, bits, snr_db, fading, grad_clip,
                            perfect, arq_attempts, arq_min_f2, arq_max_tx,
                            ge_p_gb, ge_p_bg), key


def _cc_bwd(bits, snr_db, fading, grad_clip, perfect, arq_attempts,
            arq_min_f2, arq_max_tx, ge_p_gb, ge_p_bg, key, g):
    from repro.optim.clip import clip_array_by_norm
    g = clip_array_by_norm(g, grad_clip)
    g_hat = W.transmit_tree(jax.random.fold_in(key, 1), g, bits=bits,
                            snr_db=snr_db, fading=fading, perfect=perfect,
                            arq_attempts=arq_attempts,
                            arq_min_f2=arq_min_f2, arq_max_tx=arq_max_tx,
                            ge_p_gb=ge_p_gb, ge_p_bg=ge_p_bg)
    # receiver-side re-clip: a deep Rayleigh fade flips high-order bits
    # and can blow the received norm to tau*sqrt(N); the receiver knows
    # tau, so clipping again on arrival bounds the impulse (without it,
    # LR-scaled training destabilizes — EXPERIMENTS.md §Repro)
    return clip_array_by_norm(g_hat, grad_clip), None


channel_crossing.defvjp(_cc_fwd, _cc_bwd)


def transmit_pytree(key, tree, bits, snr_db, fading=True, perfect=False,
                    use_kernel: bool = False):
    """Quantize+channel every leaf (FL weight upload, Alg. 1). One fading
    draw per leaf (one packet per tensor), per-tensor scales. Returns
    (tree_hat, payload bits as float — wire.payload_bits accounting).

    The whole tree goes through the packed wire (core/wire.py) as ONE
    fused jitted pass; use_kernel=True selects the Pallas kernel for the
    packed buffer (the TPU deploy path; interpret mode on CPU)."""
    impl = "kernel" if (use_kernel and not perfect) else "packed"
    out = W.transmit_tree(key, tree, bits=bits, snr_db=snr_db, fading=fading,
                          perfect=perfect, impl=impl)
    return out, W.payload_bits(tree, bits)
