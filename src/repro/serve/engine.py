"""Continuous-batching semantic serving engine over per-user Radios.

Many concurrent users stream prompts up through their OWN `Radio`
(per-user SNR, bounded-ARQ erasures) and receive generated tokens back
down it; the server runs ONE jitted batched decode step over a
fixed-capacity slot axis every cycle. Requests occupy a slot from
admission to completion; a completed (or abandoned) slot re-admits
from the arrival queue on the very next cycle — no global barrier
between requests (`mode="continuous"`). `mode="static"` is the
classical baseline: a batch is admitted only when EVERY slot is free,
so the whole batch drains at the pace of its slowest member.

Every admitted prompt enters through bucketed prefill chunks: up to
`chunk_size` prompt tokens per cycle in ONE launch, chunk shapes
bucketed to powers of two (serve/paging.prefill_buckets) so distinct
prompt lengths share executables. Time-to-first-token is
ceil(P/chunk_size) cycles. The family decides the cache kind; no caller
sets it:

* Paged (attention families: every family with
  `ModelApi.paged_cache_shapes`) — slot KV lives in fixed-size pages of
  one shared pool (serve/paging.PagePool; models/transformer paged
  cache). A request reserves ceil((P+N-1)/page_size) pages at admission
  and frees them at completion, so memory is bounded by TOKENS IN
  FLIGHT, not n_slots * max_len. `page_budget` caps the pool (default
  n_slots * ceil(S/page_size) pages). Prompts admit through the
  family's fused `prefill_step` (runtime/serve_step
  .make_paged_prefill_step): a bulk cache insert and one chunk
  attention launch per layer.
* Dense (the paper classifier's O(1) recurrent state: conv taps,
  pending pool, LSTM h,c) — per-slot state with nothing to page.
  Prompts admit through the exact scan of the family's own decode step
  (runtime/serve_step.make_prefill_step).

Billing is independent of the cache kind by construction: prompt
tokens ride the user's uplink via `Radio.send_tokens` on the same
fold-4242 ARQ stream before the first chunk runs, every radio draw is
keyed only by (rid, leg, attempt), and sampling keys only by (rid, 9,
t) (docs/ACCOUNTING.md §Serving).

Engine invariants (pinned by tests/test_serve.py):

* Deterministic replay — same (trace.seed, trace) => same generated
  tokens and same billing, cycle for cycle.
* Exact billing — every crossing is a `Delivery` from the user's own
  Radio; per request and in total, erased_bits + delivered == bits.
* Graceful erasure — an exhausted prompt uplink retries up to
  `max_link_tries` sends and is then ABANDONED (billed, never served);
  the batch and every other slot are untouched.
* Slot hygiene — a freed slot's cache is zeroed before the next
  admission (dense: batch-row zero; paged: its pages are zeroed when
  reallocated), so no stale KV / recurrent state leaks across users.

Host spans: each pass of the serve loop is a `serve.cycle` span; inside
it `serve.admit` (one request into a slot, holding `serve.prompt`, the
prompt draw, and `serve.uplink`), `serve.keys` (filling one launch's
`(rid, t)` rows for the slots it samples), `serve.prefill.wait` /
`serve.decode.wait` (the host waiting on a step's tokens) and
`serve.downlink`; per-request spans carry `rid`. They are
`jax.profiler.TraceAnnotation`s, on the device trace's timeline while a
profiler records, and their host seconds and counts add up in
`ServeReport.spans` either way, beside `ServeReport.host_syncs`, the
blocking device-to-host reads the engine makes (prompt draws, delivered
payloads, step tokens). A paged MoE model's steps append their
held-expert counters to the sampled tokens, so they ride the same read;
the engine sums them into `ServeReport.expert_rows`, `expert_rows_max`
and `expert_groups`.

RNG streams (all under `PRNGKey(trace.seed + 13)`, disjoint from every
training stream — docs/ACCOUNTING.md §RNG): per request rid,
`kreq = fold_in(base, rid)`; prompt content `fold_in(kreq, 3)`; uplink
attempt a `fold_in(fold_in(kreq, 1), a)`; downlink attempt a
`fold_in(fold_in(kreq, 2), a)`; sampling for generated token t
`fold_in(fold_in(kreq, 9), t)`. The sampling keys are derived inside the
step programs (`sample_keys`) from `base` and each row's `(rid, t)`, so
the host sends ids, not keys, and waits on no key.
"""
from __future__ import annotations

import contextlib
import dataclasses
import time
from functools import partial
from typing import Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.configs.base import ShapeConfig
from repro.models import api as M
from repro.models.moe import N_STATS
from repro.runtime.serve_step import (init_paged_cache, logit_width,
                                      make_decode_step,
                                      make_paged_decode_step,
                                      make_paged_prefill_step,
                                      make_prefill_step)
from repro.schemes.radio import Radio
from repro.serve.paging import (PagePool, bucket_for, pages_needed,
                                prefill_buckets)
from repro.serve.trace import RequestTrace

#: families whose decode path accepts a per-slot [B] index vector
SLOT_FAMILIES = ("dense", "moe", "vlm", "tiny")
#: families whose KV cache can live in the shared page pool
PAGED_FAMILIES = M.paged_families()
#: the serving RNG stream offset (docs/ACCOUNTING.md §RNG)
SERVE_STREAM = 13


def sample_keys(base, ids):
    """[B, 2] sampling keys of rows `ids` = [B, 2] int32 `(rid, t)`
    under the serving stream's `base` key:
    `fold_in(fold_in(fold_in(base, rid), 9), t)`, the schedule in the
    module docstring."""
    return jax.vmap(lambda r, t: jax.random.fold_in(jax.random.fold_in(
        jax.random.fold_in(base, r), 9), t))(ids[:, 0], ids[:, 1])


@dataclasses.dataclass
class RequestResult:
    """One request's outcome + its exact radio bill."""
    rid: int
    status: str = "queued"       # ok | downlink_erased | uplink_erased
    tokens: Tuple[int, ...] = ()
    prompt_len: int = 0
    snr_db: float = 0.0
    latency_cycles: int = -1     # completion - arrival + 1 (queue incl.)
    first_token_cycle: int = -1
    ttft_cycles: int = -1        # first token - arrival + 1 (queue incl.)
    ttft_s: float = -1.0         # admission -> first token, seconds
    uplink_bits: float = 0.0
    downlink_bits: float = 0.0
    bits: float = 0.0
    erased_bits: float = 0.0
    energy_j: float = 0.0
    n_tx: float = 0.0
    outage_s: float = 0.0


@dataclasses.dataclass
class ServeReport:
    """Whole-run outcome: per-request results + engine aggregates."""
    mode: str
    n_slots: int
    results: Tuple[RequestResult, ...]
    cycles: int
    wall_s: float
    kv: str = "dense"            # the cache kind that served: paged | dense
    n_pages: int = 0             # paged: pool size (0 for dense)
    peak_pages: int = 0          # paged: high-water pages in use
    #: blocking device-to-host reads the engine made (not the Radio's)
    host_syncs: int = 0
    #: span name -> (host seconds, count) of each `serve.*` span
    spans: dict = dataclasses.field(default_factory=dict)
    #: paged MoE steps' held-expert counters, summed over steps and
    #: layers: rows the held experts computed, the busiest held
    #: expert's rows (per step and layer), held experts that had a row
    expert_rows: int = 0
    expert_rows_max: int = 0
    expert_groups: int = 0

    @property
    def generated_tokens(self) -> int:
        return sum(len(r.tokens) for r in self.results)

    @property
    def bits(self) -> float:
        return sum(r.bits for r in self.results)

    @property
    def erased_bits(self) -> float:
        return sum(r.erased_bits for r in self.results)

    @property
    def delivered_bits(self) -> float:
        return self.bits - self.erased_bits

    @property
    def energy_j(self) -> float:
        return sum(r.energy_j for r in self.results)

    def latencies(self):
        return sorted(r.latency_cycles for r in self.results
                      if r.latency_cycles >= 0)

    def latency_quantile(self, q: float) -> float:
        lat = self.latencies()
        if not lat:
            return float("nan")
        return float(lat[min(len(lat) - 1, int(q * len(lat)))])

    def ttfts_cycles(self):
        return sorted(r.ttft_cycles for r in self.results
                      if r.ttft_cycles >= 0)

    def ttfts_s(self):
        return sorted(r.ttft_s for r in self.results if r.ttft_s >= 0)

    def ttft_quantile(self, q: float, unit: str = "cycles") -> float:
        vals = self.ttfts_cycles() if unit == "cycles" else self.ttfts_s()
        if not vals:
            return float("nan")
        return float(vals[min(len(vals) - 1, int(q * len(vals)))])

    def tokens_per_s(self) -> float:
        return self.generated_tokens / max(self.wall_s, 1e-9)

    def to_dict(self) -> dict:
        return {
            "mode": self.mode, "n_slots": self.n_slots,
            "kv": self.kv,
            "n_pages": self.n_pages, "peak_pages": self.peak_pages,
            "cycles": self.cycles, "wall_s": self.wall_s,
            "generated_tokens": self.generated_tokens,
            "tokens_per_s": self.tokens_per_s(),
            "bits": self.bits, "erased_bits": self.erased_bits,
            "delivered_bits": self.delivered_bits,
            "energy_j": self.energy_j,
            "p50_latency_cycles": self.latency_quantile(0.50),
            "p99_latency_cycles": self.latency_quantile(0.99),
            "p50_ttft_cycles": self.ttft_quantile(0.50),
            "p99_ttft_cycles": self.ttft_quantile(0.99),
            "p50_ttft_s": self.ttft_quantile(0.50, "s"),
            "p99_ttft_s": self.ttft_quantile(0.99, "s"),
            "statuses": {s: sum(1 for r in self.results if r.status == s)
                         for s in sorted({r.status for r in self.results})},
        }


class ServeEngine:
    """Slot-based inference server for one model over one base Radio.

    `radio` carries the shared link knobs (quantizer, fading, ARQ /
    fault model, bandwidth, power); each request's own `snr_db`
    overrides the budget per user, exactly like training fleets
    (`Radio.from_wcfg(..., snr_db=...)`). `None` = ideal noiseless
    links — still billed (a perfect link is noiseless, not free).

    The family picks the cache kind (module docstring); `chunk_size`
    bounds prompt tokens absorbed per cycle, `page_size` is the paged-KV
    page length in tokens, `page_budget` caps the shared pool (0 =
    n_slots * ceil(S/page_size) pages).

    `prefill` and `kv` accept only their one value, "chunked" and
    "paged", and raise on any other: they exist for callers that still
    pass them (`benchmarks/chip/drivers/serve_waves.py`)."""

    def __init__(self, cfg, params, *, n_slots: int = 8,
                 radio: Optional[Radio] = None, temperature: float = 1.0,
                 greedy: bool = False, max_link_tries: int = 2,
                 prefill: str = "chunked", kv: str = "paged",
                 chunk_size: int = 32, page_size: int = 16,
                 page_budget: int = 0):
        if cfg.family not in SLOT_FAMILIES:
            raise ValueError(
                f"family {cfg.family!r} has no per-slot decode path; "
                f"serving supports {SLOT_FAMILIES}")
        if n_slots < 1:
            raise ValueError("n_slots must be >= 1")
        if prefill != "chunked":
            raise ValueError(f"prefill={prefill!r}: prompts are admitted "
                             f"only in chunks ('chunked')")
        if kv != "paged":
            raise ValueError(f"kv={kv!r}: the family picks the cache kind; "
                             f"'paged' is the only value accepted")
        if chunk_size < 1:
            raise ValueError("chunk_size must be >= 1")
        if page_size < 1:
            raise ValueError("page_size must be >= 1")
        self.cfg = cfg
        self.params = params
        self.n_slots = int(n_slots)
        self.radio = radio if radio is not None \
            else Radio(perfect=True, fading=False)
        self.temperature = float(temperature)
        self.greedy = bool(greedy)
        self.max_link_tries = max(1, int(max_link_tries))
        # recurrent O(1) caches have nothing to page: they stay dense
        self.kv = "paged" if cfg.family in PAGED_FAMILIES else "dense"
        self.chunk_size = int(chunk_size)
        self.page_size = int(page_size)
        self.page_budget = int(page_budget)
        self.out_vocab = logit_width(cfg)
        self._model = M.get_model(cfg)
        self._compiled = {}      # max_len -> dict of jitted entry points

    # ------------------------------------------------------------ jitted
    def _build(self, S: int):
        if S in self._compiled:
            return self._compiled[S]
        cfg, B = self.cfg, self.n_slots
        sc = ShapeConfig("serve", S, B, "decode")
        paged = self.kv == "paged"
        out = {"buckets": prefill_buckets(self.chunk_size)}

        def sample(lg, base, ids, temperature, greedy):
            if greedy:
                return jnp.argmax(lg, axis=-1)
            return jax.vmap(jax.random.categorical)(
                sample_keys(base, ids), lg / jnp.maximum(temperature, 1e-6))

        def with_counters(nxt, st):
            """The sampled tokens [B], then the step's counters (none
            for a model without held experts): one int32 array, so the
            counters ride the tokens' read-back."""
            nxt = nxt.astype(jnp.int32)
            return jnp.concatenate([nxt, st]) if st.shape[0] else nxt

        if paged:
            n_lp = -(-S // self.page_size)
            n_pages = self.page_budget or B * n_lp
            out["n_lp"], out["n_pages"] = n_lp, int(n_pages)
            pstep = make_paged_decode_step(cfg, sc, self.page_size,
                                           stats=True)

            @partial(jax.jit, static_argnames=("greedy",))
            def step_sample(params, cache, tokens, idx, base, ids, tables,
                            active, temperature, greedy):
                logits, cache, st = pstep(params, cache, tokens, idx, tables,
                                          active)
                lg = logits[:, 0].astype(jnp.float32)
                nxt = sample(lg, base, ids, temperature, greedy)
                return with_counters(nxt, st), cache

            @jax.jit
            def zero_pages(cache, pids):
                return {k: v.at[:, pids].set(jnp.zeros((), v.dtype),
                                             mode="drop")
                        for k, v in cache.items()}

            out["decode"] = step_sample
            out["zero_pages"] = zero_pages

            pf = make_paged_prefill_step(cfg, sc, self.page_size,
                                         stats=True)

            @partial(jax.jit, static_argnames=("greedy",))
            def prefill_sample(params, cache, tokens, start, n_valid,
                               tables, base, ids, temperature, greedy):
                lg, cache, st = pf(params, cache, tokens, start, n_valid,
                                   tables)
                nxt = sample(lg, base, ids, temperature, greedy)
                return with_counters(nxt, st), cache

            out["prefill_sample"] = prefill_sample
        else:
            step = make_decode_step(cfg, sc)
            axes = {k: ax for k, (sh, ax, dt) in
                    self._model.cache_shapes(cfg, B, S).items()}

            def batch_select(mask, new, old, ax):
                i = list(ax).index("batch")
                m = mask.reshape([-1 if d == i else 1
                                  for d in range(new.ndim)])
                return jnp.where(m, new, old)

            @partial(jax.jit, static_argnames=("greedy",))
            def step_sample(params, cache, tokens, idx, base, ids, active,
                            temperature, greedy):
                logits, new_cache = step(params, cache, tokens, idx)
                cache = {k: batch_select(active, new_cache[k], cache[k],
                                         axes[k]) for k in new_cache}
                lg = logits[:, 0].astype(jnp.float32)
                nxt = sample(lg, base, ids, temperature, greedy)
                return nxt.astype(jnp.int32), cache

            @jax.jit
            def reset_slot(cache, b):
                def zero(leaf, ax):
                    i = list(ax).index("batch")
                    mask = (jnp.arange(leaf.shape[i]) == b).reshape(
                        [leaf.shape[i] if d == i else 1
                         for d in range(leaf.ndim)])
                    return jnp.where(mask, jnp.zeros((), leaf.dtype), leaf)
                return {k: zero(v, axes[k]) for k, v in cache.items()}

            out["decode"] = step_sample
            out["reset"] = reset_slot

            pf = make_prefill_step(cfg, sc)

            @partial(jax.jit, static_argnames=("greedy",))
            def prefill_sample(params, cache, tokens, start, n_valid,
                               base, ids, temperature, greedy):
                lg, cache = pf(params, cache, tokens, start, n_valid)
                nxt = sample(lg, base, ids, temperature, greedy)
                return nxt.astype(jnp.int32), cache

            out["prefill_sample"] = prefill_sample

        self._compiled[S] = out
        return out

    def warmup_compile(self, max_seq_len: int) -> float:
        """AOT-compile every jitted entry point the serve loop will hit
        for `max_seq_len` (`lower`). Returns the COMPILE wall seconds —
        tracing/lowering is done first and excluded, because it is paid
        by every process while the persistent compile cache
        (launch/compile_cache.py) only short-circuits XLA compilation:
        on a warm cache the returned wall collapses to deserialization
        time."""
        lowered = self.lower(max_seq_len)
        t0 = time.perf_counter()
        for low in lowered.values():
            low.compile()
        return time.perf_counter() - t0

    def lower(self, max_seq_len: int) -> dict:
        """Lower, on abstract inputs, every jitted entry point the serve
        loop will hit for `max_seq_len`: {"decode": the batched
        decode-sample step, "zero_pages" (paged KV), and "prefill_<C>":
        one prefill-sample program per power-of-two bucket C}."""
        S = max(8, int(max_seq_len))
        built = self._build(S)
        cfg, B = self.cfg, self.n_slots
        paged = self.kv == "paged"
        params_sds = jax.tree.map(
            lambda a: jax.ShapeDtypeStruct(jnp.shape(a), a.dtype),
            self.params)
        if paged:
            cache_sds = jax.eval_shape(
                lambda: init_paged_cache(cfg, built["n_pages"],
                                         self.page_size))
        else:
            cache_sds = jax.eval_shape(
                lambda: self._model.init_cache(cfg, B, S))
        i32 = jnp.int32
        tok = jax.ShapeDtypeStruct((B, 1), i32)
        idx = jax.ShapeDtypeStruct((B,), i32)
        base = jax.ShapeDtypeStruct((2,), jnp.uint32)
        ids = jax.ShapeDtypeStruct((B, 2), i32)
        act = jax.ShapeDtypeStruct((B,), jnp.bool_)
        temp = jax.ShapeDtypeStruct((), jnp.float32)
        lowered = {}
        if paged:
            tbl = jax.ShapeDtypeStruct((B, built["n_lp"]), i32)
            lowered["decode"] = built["decode"].lower(
                params_sds, cache_sds, tok, idx, base, ids, tbl, act, temp,
                greedy=self.greedy)
            lowered["zero_pages"] = built["zero_pages"].lower(
                cache_sds, jax.ShapeDtypeStruct((built["n_lp"],), i32))
        else:
            lowered["decode"] = built["decode"].lower(
                params_sds, cache_sds, tok, idx, base, ids, act, temp,
                greedy=self.greedy)
        for C in built["buckets"]:
            toks = jax.ShapeDtypeStruct((B, C), i32)
            nv = jax.ShapeDtypeStruct((B,), i32)
            if paged:
                lowered[f"prefill_{C}"] = built["prefill_sample"].lower(
                    params_sds, cache_sds, toks, idx, nv, tbl, base, ids,
                    temp, greedy=self.greedy)
            else:
                lowered[f"prefill_{C}"] = built["prefill_sample"].lower(
                    params_sds, cache_sds, toks, idx, nv, base, ids, temp,
                    greedy=self.greedy)
        return lowered

    # ------------------------------------------------------------- radio
    def _bill(self, res: RequestResult, d, leg: str) -> None:
        res.bits += d.bits
        res.erased_bits += d.erased_bits
        res.energy_j += d.energy_j
        res.n_tx += d.n_tx
        res.outage_s += d.outage_s
        if leg == "up":
            res.uplink_bits += d.bits
        else:
            res.downlink_bits += d.bits

    def _send_row(self, radio: Radio, kleg, row: np.ndarray, vocab: int,
                  res: RequestResult, leg: str):
        """One row of token ids through `radio`, retried up to
        `max_link_tries` sends under bounded ARQ. Returns (received row
        | None if every try was erased, erased_last_try)."""
        payload, erased = None, False
        for attempt in range(self.max_link_tries):
            d = radio.send_tokens(jax.random.fold_in(kleg, attempt),
                                  jnp.asarray(row)[None, :], vocab)
            self._bill(res, d, leg)
            erased = bool(d.user_erased[0]) if d.user_erased else False
            if not erased:
                payload = np.asarray(d.payload[0])
                break
        return payload, erased

    # ------------------------------------------------------------- serve
    def serve(self, trace: RequestTrace, mode: str = "continuous"
              ) -> ServeReport:
        if mode not in ("continuous", "static"):
            raise ValueError(f"unknown mode {mode!r}")
        barrier = mode == "static"
        cfg, B = self.cfg, self.n_slots
        reqs = trace.sorted()
        if not reqs:
            return ServeReport(mode, B, (), 0, 0.0, kv=self.kv)
        S = max(8, trace.max_seq_len())
        built = self._build(S)
        paged = self.kv == "paged"
        base = jax.random.PRNGKey(trace.seed + SERVE_STREAM)

        results = {}
        slots = [None] * B
        if paged:
            n_lp, n_pages = built["n_lp"], built["n_pages"]
            pool = PagePool(n_pages)
            cache = init_paged_cache(cfg, n_pages, self.page_size)
            tables = np.zeros((B, n_lp), np.int32)
        else:
            pool = None
            cache = self._model.init_cache(cfg, B, S)
            tables = None
        qi, cycle = 0, 0
        syncs = 0
        counters = np.zeros(N_STATS, np.int64)
        spans = {}
        t0 = time.perf_counter()

        @contextlib.contextmanager
        def span(name: str, **meta):
            """A profiler span (`TraceAnnotation`, recorded only while a
            profiler traces) whose host seconds and count also add up
            in the report."""
            ts = time.perf_counter()
            with jax.profiler.TraceAnnotation(name, **meta):
                yield
            tot = spans.setdefault(name, [0.0, 0])
            tot[0] += time.perf_counter() - ts
            tot[1] += 1

        def sample_ids(rows) -> jax.Array:
            """[B, 2] `(rid, t)` of a launch's sampled `rows`, given as
            (slot, t) pairs; a slot left out is not sampled and its draw
            is discarded."""
            ids = np.zeros((B, 2), np.int32)
            if rows and not self.greedy:
                with span("serve.keys"):
                    for b, t in rows:
                        ids[b] = (slots[b]["r"].rid, t)
            return jnp.asarray(ids)

        def admit(b: int, r, need: int) -> bool:
            """Request `r` into slot `b`: its prompt drawn and sent up,
            then the slot's pages (paged) or row (dense) zeroed. False
            when every uplink try was erased: the request is abandoned
            and its bill stands."""
            nonlocal cache, syncs
            kreq = jax.random.fold_in(base, r.rid)
            res = RequestResult(r.rid, prompt_len=r.prompt_len,
                                snr_db=r.snr_db)
            results[r.rid] = res
            with span("serve.prompt", rid=r.rid):
                syncs += 1
                prompt = np.asarray(jax.random.randint(
                    jax.random.fold_in(kreq, 3), (r.prompt_len,), 1,
                    cfg.vocab_size, jnp.int32))
            radio = dataclasses.replace(self.radio, snr_db=r.snr_db)
            with span("serve.uplink", rid=r.rid):
                rx, erased = self._send_row(
                    radio, jax.random.fold_in(kreq, 1), prompt,
                    cfg.vocab_size, res, "up")
            if erased:
                res.status = "uplink_erased"
                return False
            syncs += 1
            res.status = "serving"
            slots[b] = {"r": r, "res": res, "kreq": kreq, "radio": radio,
                        "prompt": rx, "pos": 0, "last": 0, "new": [],
                        "admit_wall": time.perf_counter()}
            if paged:
                pids = pool.alloc(need)
                slots[b]["pgs"] = pids
                tables[b, :] = 0
                tables[b, :len(pids)] = pids
                cache = built["zero_pages"](cache, jnp.asarray(
                    np.pad(pids, (0, n_lp - len(pids)),
                           constant_values=n_pages), jnp.int32))
            else:
                cache = built["reset"](cache, jnp.int32(b))
            return True

        def push_token(st, tok: int) -> None:
            st["new"].append(tok)
            st["last"] = tok
            if len(st["new"]) == 1:
                res = st["res"]
                res.first_token_cycle = cycle
                res.ttft_cycles = cycle - st["r"].arrival_cycle + 1
                res.ttft_s = time.perf_counter() - st["admit_wall"]

        def complete(st) -> None:
            nonlocal syncs
            r, res = st["r"], st["res"]
            gen = np.asarray(st["new"], np.int32)
            with span("serve.downlink", rid=r.rid):
                _, erased = self._send_row(
                    st["radio"], jax.random.fold_in(st["kreq"], 2), gen,
                    self.out_vocab, res, "down")
            syncs += not erased
            res.status = "downlink_erased" if erased else "ok"
            res.tokens = tuple(int(t) for t in gen)
            res.latency_cycles = cycle - r.arrival_cycle + 1
            if paged:
                pool.free(st.pop("pgs"))

        while qi < len(reqs) or any(s is not None for s in slots):
            with span("serve.cycle"):
                # ---- admission (continuous: any free slot; static: barrier)
                if not barrier or all(s is None for s in slots):
                    blocked = False      # paged: FIFO head-of-line wait
                    for b in range(B):
                        if blocked or slots[b] is not None:
                            continue
                        while qi < len(reqs) \
                                and reqs[qi].arrival_cycle <= cycle:
                            r = reqs[qi]
                            need = 0
                            if paged:
                                need = pages_needed(r.prompt_len,
                                                    r.max_new_tokens,
                                                    self.page_size)
                                if need > n_pages:
                                    raise ValueError(
                                        f"request {r.rid} needs {need} "
                                        f"pages but the pool has "
                                        f"{n_pages}; raise page_budget")
                                if not pool.can_alloc(need):
                                    blocked = True
                                    break
                            with span("serve.admit", rid=r.rid):
                                seated = admit(b, r, need)
                            qi += 1
                            if seated:
                                break
                if not any(s is not None for s in slots):
                    if qi < len(reqs):   # idle: jump to the next arrival
                        cycle = max(cycle + 1, reqs[qi].arrival_cycle)
                        continue
                    break

                tables_j = jnp.asarray(tables) if paged else None
                pre = [b for b, st in enumerate(slots)
                       if st is not None and st["pos"] < st["r"].prompt_len]
                dec = [b for b, st in enumerate(slots)
                       if st is not None and st["pos"] >= st["r"].prompt_len]

                # ---- bucketed prefill chunks over the prefilling slots
                if pre:
                    chunk = {b: min(slots[b]["r"].prompt_len
                                    - slots[b]["pos"], self.chunk_size)
                             for b in pre}
                    C = bucket_for(max(chunk.values()), built["buckets"])
                    ptoks = np.zeros((B, C), np.int32)
                    pstart = np.zeros(B, np.int32)
                    pnv = np.zeros(B, np.int32)
                    rows = []
                    for b in pre:
                        st, c = slots[b], chunk[b]
                        ptoks[b, :c] = st["prompt"][st["pos"]:st["pos"] + c]
                        pstart[b] = st["pos"]
                        pnv[b] = c
                        if st["pos"] + c >= st["r"].prompt_len:
                            rows.append((b, 0))
                    pids = sample_ids(rows)
                    if paged:
                        nxtp, cache = built["prefill_sample"](
                            self.params, cache, jnp.asarray(ptoks),
                            jnp.asarray(pstart), jnp.asarray(pnv),
                            tables_j, base, pids,
                            jnp.float32(self.temperature), self.greedy)
                    else:
                        nxtp, cache = built["prefill_sample"](
                            self.params, cache, jnp.asarray(ptoks),
                            jnp.asarray(pstart), jnp.asarray(pnv),
                            base, pids,
                            jnp.float32(self.temperature), self.greedy)
                    with span("serve.prefill.wait"):
                        syncs += 1
                        nxtp = np.asarray(nxtp)
                    counters[:len(nxtp) - B] += nxtp[B:]
                    for b in pre:
                        st = slots[b]
                        st["pos"] += chunk[b]
                        if st["pos"] >= st["r"].prompt_len:
                            push_token(st, int(nxtp[b]))
                            if len(st["new"]) >= st["r"].max_new_tokens:
                                complete(st)
                                slots[b] = None

                # ---- one batched decode cycle over the decoding slots
                if dec:
                    toks = np.zeros((B, 1), np.int32)
                    idx = np.zeros(B, np.int32)
                    active = np.zeros(B, bool)
                    rows = []
                    for b in dec:
                        st = slots[b]
                        toks[b, 0] = st["last"]
                        idx[b] = st["pos"]
                        active[b] = True
                        rows.append((b, len(st["new"])))
                    ids = sample_ids(rows)
                    if paged:
                        nxt, cache = built["decode"](
                            self.params, cache, jnp.asarray(toks),
                            jnp.asarray(idx), base, ids, tables_j,
                            jnp.asarray(active),
                            jnp.float32(self.temperature), self.greedy)
                    else:
                        nxt, cache = built["decode"](
                            self.params, cache, jnp.asarray(toks),
                            jnp.asarray(idx), base, ids,
                            jnp.asarray(active),
                            jnp.float32(self.temperature), self.greedy)
                    with span("serve.decode.wait"):
                        syncs += 1
                        nxt = np.asarray(nxt)
                    counters[:len(nxt) - B] += nxt[B:]
                    for b in dec:
                        st = slots[b]
                        push_token(st, int(nxt[b]))
                        st["pos"] += 1
                        if len(st["new"]) >= st["r"].max_new_tokens:
                            complete(st)
                            slots[b] = None
                cycle += 1

        wall = time.perf_counter() - t0
        ordered = tuple(results[r.rid] for r in reqs)
        return ServeReport(mode, B, ordered, cycle, wall,
                           kv=self.kv,
                           n_pages=built.get("n_pages", 0) if paged else 0,
                           peak_pages=pool.peak_pages if paged else 0,
                           host_syncs=syncs,
                           spans={k: tuple(v) for k, v in spans.items()},
                           expert_rows=int(counters[0]),
                           expert_rows_max=int(counters[1]),
                           expert_groups=int(counters[2]))
