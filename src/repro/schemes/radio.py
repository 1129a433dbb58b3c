"""`Radio` — the ONE owner of the channel knobs.

Every transmission in the unified scheme API goes through a `Radio`
built once from the run's `WirelessConfig`; call sites say
`radio.send_tree(key, tree)` instead of threading
`(quant_bits, snr_db, fading, perfect)` positionally through every
`transmit_*` call. Each send returns a `Delivery` carrying the received
payload plus the on-air accounting (payload bits, comm energy, drawn
ARQ transmission counts), so payload/energy bookkeeping happens in
exactly one place.

Bits accounting uses the DRAWN per-packet transmission counts surfaced
by the packed wire (`core/wire.py`, `return_diag=True`): without ARQ the
drawn count is identically 1 and `Delivery.bits` equals the analytic
`wire.payload_bits`; with ARQ it is the actual retransmission cost of
this delivery (the analytic expectation stays available via
`Radio.expected_tx`).
"""
from __future__ import annotations

import dataclasses
import functools
from typing import Any, Optional

import jax
import numpy as np

from repro.core import channel as CH
from repro.core import energy as EN
from repro.core import wire as W


@functools.lru_cache(maxsize=64)
def _expected_capacity(bandwidth_hz: float, snr_db: float,
                       fading: bool) -> float:
    """Cached E_f[C] (Monte-Carlo over Rayleigh |f|^2, energy.py)."""
    return EN.channel_capacity(bandwidth_hz, snr_db, fading)


@dataclasses.dataclass(frozen=True)
class Delivery:
    """One radio transmission, received side + accounting."""
    payload: Any                # dequantized-at-receiver tree / tensor
    bits: float                 # on-air bits, incl. drawn retransmissions
    energy_j: float             # comm energy of this delivery (Eq. 11)
    n_tx: float                 # total transmissions drawn across packets
    # stacked sends only: per-user slice of the accounting above, in the
    # leading-axis order of the transmitted tree (None for flat sends).
    # Lets a population scheme bill ONE fused N-user pass back to the
    # individual clients that rode it.
    user_bits: Optional[tuple] = None
    user_n_tx: Optional[tuple] = None
    # bounded-ARQ fault accounting (zero / None on a fault-free link).
    # erased_bits: the slice of `bits` spent on packets that were
    # ultimately ERASED (every transmission of an exhausted packet) —
    # always <= bits; bits - erased_bits is the payload-delivered air
    # time. outage_s: total exponential-backoff wait billed in TIME
    # (docs/ACCOUNTING.md §Faults). user_erased: per-user "any packet
    # erased" flags for stacked sends (the quorum input).
    erased_bits: float = 0.0
    outage_s: float = 0.0
    user_erased: Optional[tuple] = None
    user_erased_bits: Optional[tuple] = None


@dataclasses.dataclass(frozen=True)
class Radio:
    """Channel knobs, held once per run (paper Table I + beyond-paper
    ARQ). Frozen + hashable so jitted paths can key on it."""
    quant_bits: int = 8
    snr_db: float = 20.0
    fading: bool = True
    perfect: bool = False
    arq_attempts: int = 1
    arq_min_f2: float = 0.25
    bandwidth_hz: float = 100e3
    tx_power_w: float = 1e-3
    use_kernel: bool = False     # Pallas packed kernel for float sends
    wire_dtype: str = "float32"  # "int8": byte codewords on-wire (Q<=8)
    # fault model (all off by default — legacy deliveries bitwise):
    arq_max_tx: int = 0          # >0: bounded ARQ, exhaustion = erasure
    ge_p_gb: float = 0.0         # Gilbert-Elliott good->bad (0 = off)
    ge_p_bg: float = 0.5         # Gilbert-Elliott bad->good
    arq_backoff_s: float = 0.0   # exp backoff base, billed as outage_s
    rounding: str = "nearest"    # "stochastic": unbiased codewords

    @classmethod
    def from_wcfg(cls, wcfg, quant_bits: Optional[int] = None,
                  use_kernel: bool = False, **overrides) -> "Radio":
        """Build from a WirelessConfig; None means an ideal (perfect,
        non-fading) link — the no-radio baseline. Extra keyword
        `overrides` replace individual Radio fields on top of the base
        config (``Radio.from_wcfg(wcfg, snr_db=5.0, fading=False)``) —
        the one-liner a heterogeneous client population uses to give
        every client its own link budget."""
        if wcfg is None:
            base = cls(perfect=True, fading=False)
        else:
            base = cls(quant_bits=int(quant_bits or wcfg.quant_bits),
                       snr_db=float(wcfg.snr_db), fading=bool(wcfg.fading),
                       perfect=bool(wcfg.perfect_channel),
                       arq_attempts=int(getattr(wcfg, "arq_attempts", 1)),
                       arq_min_f2=float(getattr(wcfg, "arq_min_f2", 0.25)),
                       bandwidth_hz=float(wcfg.bandwidth_hz),
                       tx_power_w=float(wcfg.tx_power_w),
                       use_kernel=bool(use_kernel or
                                       getattr(wcfg, "use_kernel", False)),
                       wire_dtype=str(getattr(wcfg, "wire_dtype",
                                              "float32")),
                       arq_max_tx=int(getattr(wcfg, "arq_max_tx", 0)),
                       ge_p_gb=float(getattr(wcfg, "ge_p_gb", 0.0)),
                       ge_p_bg=float(getattr(wcfg, "ge_p_bg", 0.5)),
                       arq_backoff_s=float(getattr(wcfg, "arq_backoff_s",
                                                   0.0)),
                       rounding=str(getattr(wcfg, "rounding", "nearest")))
        return dataclasses.replace(base, **overrides) if overrides else base

    # ----------------------------------------------------------- account
    def expected_tx(self) -> float:
        """Analytic expected transmissions per packet under outage-ARQ.
        With bounded ARQ the cap replaces `arq_attempts` (the legacy
        truncated-geometric formula already IS the bounded expectation);
        under Gilbert-Elliott outages a stationary-bad packet burns the
        whole window, so the expectation mixes the two link states."""
        a = self.arq_max_tx if self.arq_max_tx > 0 else self.arq_attempts
        base = W.expected_arq_tx(a, self.arq_min_f2, self.fading,
                                 self.perfect)
        if self.ge_p_gb > 0.0 and not self.perfect:
            pi_bad = self.ge_p_gb / (self.ge_p_gb + self.ge_p_bg)
            return pi_bad * float(a) + (1.0 - pi_bad) * base
        return base

    def wire_width(self) -> int:
        """Billed on-air bits per codeword: the quantizer width on the
        float32 wire, the physical container width on the packed dtypes
        (int8 -> 8, int4 -> 4; wire.wire_width)."""
        return W.wire_width(self.wire_dtype, self.quant_bits)

    def payload_bits(self, tree) -> float:
        """Analytic one-transmission payload of `tree` at this radio's
        quantization (wire.payload_bits — the one accounting helper),
        billed at the wire container width (`wire_width`)."""
        return W.payload_bits(tree, self.quant_bits,
                              wire_dtype=self.wire_dtype)

    def rate_bps(self) -> float:
        """Expected link rate E_f[C] in bits/s (Monte-Carlo ergodic
        capacity over the Rayleigh fade, cached per link budget) — the
        denominator of both the comm-energy rule (Eq. 11) and the fleet
        deadline model's transfer-time estimate
        (population.PopulationScheme, docs/ACCOUNTING.md §Fleet)."""
        return _expected_capacity(self.bandwidth_hz, self.snr_db,
                                  self.fading)

    def energy_j(self, bits: float) -> float:
        """Comm energy of `bits` on this link: bits * P / E[C]."""
        return float(bits) * self.tx_power_w / self.rate_bps()

    def bill_counts(self, n_tx, sizes, erased=None) -> Delivery:
        """Batched `Delivery` reduction WITHOUT a payload: bill a
        (stacked) send from its drawn per-(user, packet) transmission
        counts and erasure mask — the exact reduction `send_stacked`
        applies to its own diagnostics, exposed so a replay engine
        (`schemes/fleet.py`) or a test can turn `wire.drawn_stacked_tx`
        counts into the identical per-user bits / n_tx / energy /
        erased_bits split a real transmission would have billed."""
        return self._deliver(None, n_tx, sizes, erased)

    def _impl(self) -> str:
        return "kernel" if (self.use_kernel and not self.perfect) \
            else "packed"

    def wire_kwargs(self) -> dict:
        """The link knobs a float send passes to the packed wire
        (`wire.transmit_tree` / `wire.transmit_stacked`) after the key,
        tree, quant_bits and snr_db."""
        return dict(fading=self.fading, perfect=self.perfect,
                    arq_attempts=self.arq_attempts,
                    arq_min_f2=self.arq_min_f2, impl=self._impl(),
                    wire_dtype=self.wire_dtype, arq_max_tx=self.arq_max_tx,
                    ge_p_gb=self.ge_p_gb, ge_p_bg=self.ge_p_bg,
                    rounding=self.rounding)

    def _deliver(self, payload, n_tx, sizes, erased=None) -> Delivery:
        n_tx = np.asarray(n_tx, np.float64)
        sizes = np.asarray(sizes, np.float64)
        width = float(self.wire_width())
        bits = width * float((sizes * n_tx).sum())
        user_bits = user_n_tx = user_erased = None
        if n_tx.ndim == 2:      # stacked send: keep the per-user split
            user_bits = tuple(float(b) for b in
                              width * (sizes * n_tx).sum(axis=1))
            user_n_tx = tuple(float(t) for t in n_tx.sum(axis=1))
        erased_bits = 0.0
        user_erased_bits = None
        if erased is not None and self.arq_max_tx > 0:
            # every transmission of an exhausted packet was wasted air
            # time: bill its whole attempted slice as erased
            e = np.asarray(erased, bool)
            erased_bits = width * float((sizes * n_tx * e).sum())
            if n_tx.ndim == 2:
                user_erased = tuple(bool(x) for x in e.any(axis=1))
                user_erased_bits = tuple(
                    float(b) for b in
                    width * (sizes * n_tx * e).sum(axis=1))
        outage_s = W.backoff_s(n_tx, self.arq_backoff_s)
        return Delivery(payload, bits, self.energy_j(bits),
                        float(n_tx.sum()), user_bits, user_n_tx,
                        erased_bits, float(outage_s), user_erased,
                        user_erased_bits)

    # -------------------------------------------------------------- send
    def send_tree(self, key, tree) -> Delivery:
        """Transmit every leaf of a pytree (one packet per tensor) via
        the fused packed wire. SL legs, single-user weight uploads."""
        payload, diag = W.transmit_tree(
            key, tree, self.quant_bits, self.snr_db, return_diag=True,
            **self.wire_kwargs())
        sizes = [int(l.size) for l in jax.tree.leaves(tree)]
        return self._deliver(payload, diag["n_tx"], sizes, diag["erased"])

    def send_stacked(self, key, tree) -> Delivery:
        """Transmit a tree whose leaves carry a leading user axis
        [N, ...] — FL's whole N-user upload in one fused pass, one
        packet (fade + scale) per (user, tensor). The payload keeps the
        user axis; aggregation is the caller's (scheme's) job."""
        leaves = jax.tree.leaves(tree)
        payload, diag = W.transmit_stacked(
            key, tree, self.quant_bits, self.snr_db, return_diag=True,
            **self.wire_kwargs())
        sizes = [int(l.size) // int(l.shape[0]) for l in leaves]
        return self._deliver(payload, diag["n_tx"], sizes, diag["erased"])

    # disjoint key fold for the per-row token ARQ/erasure draw — never
    # collides with transmit_tokens' own split of the same key, so
    # turning the fault model on does not perturb the channel noise
    _TOKEN_ARQ_FOLD = 4242

    def send_tokens(self, key, tokens, vocab_size: int,
                    labels=None) -> Delivery:
        """CL / serving uplink: raw token ids as fixed-width codewords,
        one packet (fade) per row. Labels ride a 1-bit control channel.
        Bits — and one transmission per row in `n_tx` — are charged
        perfect or not: a perfect link is noiseless, not free, so the
        dataset crossing is billed either way (the one CL convention).

        Under bounded ARQ (`arq_max_tx > 0`) each row additionally
        draws its own retransmission count on a disjoint key fold
        (`wire.drawn_stacked_tx`, same convention as the fused paths):
        an exhausted row is ERASED — delivered as pad/zero ids, its
        whole attempted slice billed into `erased_bits`, and flagged in
        `user_erased` — so a serving request's prompt uplink can fail
        without crashing the batch (docs/ACCOUNTING.md §Serving)."""
        import jax.numpy as jnp

        from repro.core.centralized import token_bits
        n_bits = token_bits(vocab_size)
        if self.perfect:
            payload = tokens
        else:
            payload = CH.transmit_tokens(key, tokens, vocab_size,
                                         snr_db=self.snr_db,
                                         fading=self.fading)
        base_bits = W.payload_bits(tokens, n_bits)
        if labels is not None:
            base_bits += W.payload_bits(labels, 1)
        n_rows = tokens.shape[0] if getattr(tokens, "ndim", 1) > 1 else 1
        if self.arq_max_tx <= 0 or W.fault_free(
                self.fading, self.perfect, self.arq_attempts,
                self.arq_min_f2, self.arq_max_tx, self.ge_p_gb):
            # legacy billing, bitwise: one transmission per row
            return Delivery(payload, base_bits, self.energy_j(base_bits),
                            float(n_rows))
        n_tx, erased = W.drawn_stacked_tx(
            jax.random.fold_in(key, self._TOKEN_ARQ_FOLD), n_rows, 1,
            self.fading, self.perfect, self.arq_attempts, self.arq_min_f2,
            self.arq_max_tx, self.ge_p_gb, self.ge_p_bg, with_erased=True)
        n_tx = np.asarray(n_tx, np.float64)[:, 0]
        erased = np.asarray(erased, bool)[:, 0]
        row_bits = base_bits / n_rows
        bits = float(row_bits * n_tx.sum())
        erased_bits = float(row_bits * (n_tx * erased).sum())
        if erased.any():
            # an erased row's CRC failed: the receiver substitutes pad
            # ids (0), mirroring the zeroed erased packets of the wire
            er = jnp.asarray(erased)
            payload = jnp.where(er[:, None] if getattr(tokens, "ndim", 1)
                                > 1 else er[0], 0, payload)
        return Delivery(
            payload, bits, self.energy_j(bits), float(n_tx.sum()),
            tuple(float(row_bits * t) for t in n_tx),
            tuple(float(t) for t in n_tx), erased_bits,
            float(W.backoff_s(n_tx, self.arq_backoff_s)),
            tuple(bool(e) for e in erased),
            tuple(float(row_bits * t * e) for t, e in zip(n_tx, erased)))
