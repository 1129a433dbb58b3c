"""repro.serve — KV/state-cache correctness, slot hygiene, exact
billing, deterministic replay, continuous-vs-static throughput."""
import dataclasses
import json

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import get_arch
from repro.models import api as M
from repro.nn import init_params
from repro.schemes.radio import Radio
from repro.serve import (Request, RequestTrace, ServeEngine, make_trace,
                         uniform_trace)

TINY = get_arch("paper-tinylstm")
QWEN = get_arch("qwen1.5-0.5b").reduced()


def params_for(cfg, seed=0):
    return init_params(jax.random.PRNGKey(seed), M.param_specs(cfg))


# a link harsh enough that bounded ARQ regularly erases whole rows
HARSH = Radio(snr_db=5.0, fading=True, arq_max_tx=1, arq_attempts=1,
              arq_min_f2=1.5)


# ------------------------------------------------ KV-cache correctness
@pytest.mark.parametrize("cfg,tol", [(TINY, 1e-6), (QWEN, 2e-4)],
                         ids=["paper-tinylstm", "qwen1.5-0.5b-reduced"])
def test_decode_matches_teacher_forced_prefill(cfg, tol):
    """Per-slot decode over the serving cache (the classifier's dense
    state; the transformer's paged pool, identity page tables)
    reproduces the batch forward pass: every decode-step logit equals
    the teacher-forced logit at that position (the cache holds exactly
    the right keys/values). Slots run at DIFFERENT depths via the
    vector index."""
    from repro.runtime.serve_step import init_paged_cache
    model = M.get_model(cfg)
    params = params_for(cfg)
    B, S = 4, 12
    tokens = jax.random.randint(jax.random.PRNGKey(1), (B, S), 1,
                                cfg.vocab_size, jnp.int32)
    ref, _ = model.forward(params, {"tokens": tokens}, cfg, 0)
    kw = {}
    if cfg.family == "tiny":
        cache = model.init_cache(cfg, B, S)
    else:
        page = 4
        n_lp = S // page
        cache = init_paged_cache(cfg, B * n_lp, page)
        kw["pages"] = {"tables": jnp.arange(B * n_lp, dtype=jnp.int32)
                       .reshape(B, n_lp), "page_size": page,
                       "active": None, "kernel": None}
    # stagger the slots: slot b starts b steps late, so the batched
    # step always carries a genuine per-slot index vector
    offs = np.arange(B) % 3
    got = np.zeros((B, S), np.float32) if cfg.family == "tiny" \
        else np.zeros((B, S, cfg.vocab_size), np.float32)
    pos = -offs.copy()
    for step in range(S + offs.max()):
        idx = np.maximum(pos, 0).astype(np.int32)
        tk = np.array([tokens[b, min(max(pos[b], 0), S - 1)]
                       for b in range(B)], np.int32)[:, None]
        logits, cache = model.decode_step(params, cache, jnp.asarray(tk),
                                          jnp.asarray(idx), cfg, 0, **kw)
        lg = np.asarray(logits, np.float32)
        for b in range(B):
            if 0 <= pos[b] < S:
                got[b, pos[b]] = lg[b, 0, 1] if cfg.family == "tiny" \
                    else lg[b, 0]
        pos += 1
    if cfg.family == "tiny":
        # classifier: streaming logit must match forward() wherever the
        # batch model emits one (the final position)
        np.testing.assert_allclose(got[:, -1], np.asarray(ref)[:, 0],
                                   rtol=tol, atol=tol)
    else:
        np.testing.assert_allclose(got, np.asarray(ref, np.float32),
                                   rtol=tol, atol=tol)


def test_slot_reuse_no_stale_cache():
    """A request served in a REUSED slot generates the same tokens as
    the same request served alone in a fresh engine — slot zeroing
    leaves nothing of the previous occupant behind."""
    params = params_for(TINY)
    eng = ServeEngine(TINY, params, n_slots=2)
    reqs = tuple(Request(rid, 0, 4 + rid % 5, 2 + rid % 3)
                 for rid in range(6))
    crowded = eng.serve(RequestTrace(11, reqs), "continuous")
    assert len({r.rid for r in crowded.results}) == 6
    for req in reqs:
        alone = eng.serve(RequestTrace(11, (req,)), "continuous")
        got = next(r for r in crowded.results if r.rid == req.rid)
        assert got.tokens == alone.results[0].tokens, req


# ------------------------------------------------ determinism + billing
def test_replay_is_deterministic():
    """Same (seed, trace) => same tokens AND same bill, both modes."""
    params = params_for(TINY)
    eng = ServeEngine(TINY, params, n_slots=4, radio=HARSH,
                      max_link_tries=2)
    tr = make_trace(3, 12, prompt_lens=(3, 8), new_tokens=(2, 4),
                    snr_dbs=(5.0,))
    for mode in ("continuous", "static"):
        a, b = eng.serve(tr, mode), eng.serve(tr, mode)
        assert [r.tokens for r in a.results] == \
               [r.tokens for r in b.results]
        assert [r.status for r in a.results] == \
               [r.status for r in b.results]
        assert (a.bits, a.erased_bits, a.energy_j) == \
               (b.bits, b.erased_bits, b.energy_j)
        assert a.cycles == b.cycles
    # a different trace seed actually changes the run
    c = eng.serve(dataclasses.replace(tr, seed=4), "continuous")
    assert [r.tokens for r in c.results] != \
           [r.tokens for r in eng.serve(tr, "continuous").results]


def test_billing_exact_under_erasures():
    """erased_bits + delivered == bits EXACTLY, per request and in
    total; abandoned uplinks are billed but never served; the batch
    survives every erasure."""
    params = params_for(TINY)
    eng = ServeEngine(TINY, params, n_slots=4, radio=HARSH,
                      max_link_tries=2)
    rep = eng.serve(make_trace(3, 16, prompt_lens=(3, 8),
                               new_tokens=(2, 4), snr_dbs=(5.0,)),
                    "continuous")
    statuses = {r.status for r in rep.results}
    assert "uplink_erased" in statuses          # the harsh link bites
    assert "ok" in statuses                     # ...but not every time
    for r in rep.results:
        assert r.bits > 0                       # every request billed
        assert 0.0 <= r.erased_bits <= r.bits
        assert (r.bits - r.erased_bits) + r.erased_bits == r.bits
        if r.status == "uplink_erased":         # abandoned: billed only
            assert r.tokens == () and r.latency_cycles == -1
            assert r.erased_bits > 0
        else:
            assert len(r.tokens) > 0 and r.latency_cycles >= 1
    assert rep.delivered_bits + rep.erased_bits == rep.bits
    assert rep.bits == sum(r.bits for r in rep.results)


def test_eight_concurrent_users_end_to_end():
    """>=8 users genuinely in flight at once on CPU, each billed on its
    own per-SNR Radio; per-user bills sum exactly to the run total."""
    params = params_for(TINY)
    eng = ServeEngine(TINY, params, n_slots=8,
                      radio=Radio(snr_db=10.0, fading=True))
    reqs = tuple(Request(rid, 0, 6 + rid % 4, 3 + rid % 3,
                         snr_db=float(5 + 3 * (rid % 4)))
                 for rid in range(12))
    rep = eng.serve(RequestTrace(21, reqs), "continuous")
    assert all(r.status == "ok" for r in rep.results)
    assert len(rep.results) == 12
    # all 8 slots were actually occupied at cycle 0 (12 arrivals, 8
    # slots): the run needs more cycles than any single request alone
    # (a request takes ceil(P/chunk) prefill cycles + N decode cycles
    # under the default chunked admission)
    alone = max(-(-r.prompt_len // eng.chunk_size) + r.max_new_tokens
                for r in reqs)
    assert rep.cycles > alone
    for req, r in zip(reqs, rep.results):
        assert r.snr_db == req.snr_db
        assert len(r.tokens) == req.max_new_tokens
        assert r.uplink_bits > 0 and r.downlink_bits > 0
        assert r.uplink_bits + r.downlink_bits == r.bits
    assert rep.bits == sum(r.bits for r in rep.results)
    assert rep.energy_j == sum(r.energy_j for r in rep.results)


# ------------------------------------------------ scheduling / formats
def test_continuous_beats_static_on_mixed_lengths():
    """With mixed output lengths, continuous admission finishes the
    same trace in strictly fewer decode cycles than the static barrier
    (a static batch drains at the pace of its slowest member)."""
    params = params_for(TINY)
    eng = ServeEngine(TINY, params, n_slots=4)
    tr = make_trace(7, 12, prompt_lens=(3, 10), new_tokens=(1, 8),
                    mean_gap=0.0)
    cont = eng.serve(tr, "continuous")
    stat = eng.serve(tr, "static")
    assert cont.generated_tokens == stat.generated_tokens
    assert cont.cycles < stat.cycles
    # same requests, same per-request radio bill in either schedule
    assert cont.bits == stat.bits


def test_trace_json_roundtrip(tmp_path):
    tr = make_trace(5, 9)
    p = tmp_path / "trace.json"
    tr.save(str(p))
    back = RequestTrace.load(str(p))
    assert back == tr
    obj = json.loads(tr.to_json())
    assert obj["format"] == "repro.serve/RequestTrace/v1"
    assert obj["seed"] == 5 and len(obj["requests"]) == 9
    # replay order is (arrival_cycle, rid) regardless of storage order
    shuffled = RequestTrace(5, tuple(reversed(tr.requests)))
    assert shuffled.sorted() == tr.sorted()
    assert tr.max_seq_len() == max(r.prompt_len + r.max_new_tokens
                                   for r in tr.requests)


def test_uniform_trace_matches_legacy_demo_shape():
    tr = uniform_trace(0, 4, 16, 16)
    assert tr.n_requests == 4
    assert all(r.arrival_cycle == 0 and r.prompt_len == 16 and
               r.max_new_tokens == 16 for r in tr.requests)


def test_engine_rejects_scalar_families():
    cfg = get_arch("xlstm-350m").reduced()
    with pytest.raises(ValueError, match="per-slot"):
        ServeEngine(cfg, {}, n_slots=2)


def test_transformer_engine_e2e():
    """The reduced transformer serves a mixed trace end-to-end through
    the SAME engine loop (paged KV pool, fused chunk prefill)."""
    params = params_for(QWEN)
    eng = ServeEngine(QWEN, params, n_slots=4,
                      radio=Radio(snr_db=10.0, fading=True))
    rep = eng.serve(make_trace(9, 6, prompt_lens=(3, 6),
                               new_tokens=(2, 4)), "continuous")
    assert all(r.status == "ok" for r in rep.results)
    assert rep.generated_tokens == sum(len(r.tokens) for r in rep.results)
    rep2 = eng.serve(make_trace(9, 6, prompt_lens=(3, 6),
                                new_tokens=(2, 4)), "continuous")
    assert [r.tokens for r in rep.results] == \
           [r.tokens for r in rep2.results]


# --------------------------------------- chunked prefill + paged KV
def _staggered_trace():
    """Mixed trace exercising every prefill bucket: prompts shorter than
    the bucket floor, longer than one chunk, arrivals staggered so
    prefills and decodes share cycles."""
    reqs = tuple(Request(rid=i, arrival_cycle=[0, 0, 1, 3, 7, 9][i],
                         prompt_len=[40, 3, 17, 64, 5, 33][i],
                         max_new_tokens=[6, 9, 4, 5, 8, 3][i],
                         snr_db=[18.0, 6.0, 12.0, 25.0, 9.0, 15.0][i])
                 for i in range(6))
    return RequestTrace(seed=7, requests=reqs)


def _bill_rows(rep):
    return [(r.rid, r.status, r.bits, r.erased_bits, r.energy_j, r.n_tx,
             r.uplink_bits, r.downlink_bits) for r in rep.results]


#: fused chunk prefill vs the same chunk fed through decode steps, on
#: the reduced transformer in float32: one bulk insert and one chunk
#: attention sum in another order than C single-token steps, so the two
#: agree to float32 rounding, not bitwise
FUSED_TOL = 1e-5


@pytest.mark.parametrize("cfg", [TINY, QWEN],
                         ids=["paper-tinylstm", "qwen1.5-0.5b-reduced"])
def test_prefill_scan_bitwise_matches_token_steps(cfg):
    """Runtime-level pin of each cache kind's prefill: the same chunk
    fed through the family's decode step one position at a time, with
    the engine's per-row active masking (staggered starts and ragged
    n_valid, so the masking genuinely matters), gives the same cache and
    last-valid-token logits. The classifier's dense scan is BITWISE the
    token steps (same primitive sequence); the transformer's paged fused
    prefill agrees with paged decode steps to FUSED_TOL, and a row with
    n_valid=0 writes nothing."""
    from repro.configs.base import ShapeConfig
    from repro.runtime import serve_step as SS
    model = M.get_model(cfg)
    params = params_for(cfg)
    B, S, C, page = 4, 32, 8, 8
    sc = ShapeConfig("serve", S, B, "decode")
    tokens = jax.random.randint(jax.random.PRNGKey(5), (B, C), 1,
                                cfg.vocab_size, jnp.int32)
    start = jnp.array([0, 3, 9, 17], jnp.int32)
    n_valid = jnp.array([8, 1, 0, 5], jnp.int32)
    V = SS.logit_width(cfg)

    if cfg.family == "tiny":
        shapes = model.cache_shapes(cfg, B, S)
        axes = {k: ax for k, (sh, ax, dt) in shapes.items()}
        cache0 = model.init_cache(cfg, B, S)
        lg_pf, cache_pf = jax.jit(SS.make_prefill_step(cfg, sc))(
            params, cache0, tokens, start, n_valid)

        # the token path exactly as a batched masked decode runs it:
        # ONE jitted step (same primitive sequence as the scan body)
        @jax.jit
        def token_step(cache, tok, idx, sel):
            logits, new_cache = model.decode_step(params, cache, tok, idx,
                                                  cfg, 0)

            def pick(new, old, ax):
                j = list(ax).index("batch")
                m = sel.reshape([-1 if d == j else 1
                                 for d in range(new.ndim)])
                return jnp.where(m, new, old)
            cache = {k: pick(new_cache[k], cache[k], axes[k])
                     for k in new_cache}
            return logits[:, 0].astype(jnp.float32), cache
    else:
        n_lp = S // page
        tables = jnp.arange(B * n_lp, dtype=jnp.int32).reshape(B, n_lp)
        cache0 = SS.init_paged_cache(cfg, B * n_lp, page)
        lg_pf, cache_pf = jax.jit(SS.make_paged_prefill_step(cfg, sc, page))(
            params, cache0, tokens, start, n_valid, tables)
        decode = jax.jit(SS.make_paged_decode_step(cfg, sc, page))

        def token_step(cache, tok, idx, sel):
            logits, cache = decode(params, cache, tok, idx, tables, sel)
            return logits[:, 0].astype(jnp.float32), cache

    cache = cache0
    lg = np.zeros((B, V), np.float32)
    for i in range(C):
        sel = jnp.asarray(i < np.asarray(n_valid))
        row, cache = token_step(cache, tokens[:, i:i + 1],
                                start + jnp.int32(i), sel)
        take = i == np.asarray(n_valid) - 1
        lg[take] = np.asarray(row)[take]
    if cfg.family == "tiny":
        for k in cache:
            np.testing.assert_array_equal(np.asarray(cache_pf[k]),
                                          np.asarray(cache[k]), err_msg=k)
        np.testing.assert_array_equal(np.asarray(lg_pf), lg)
        return
    for k in cache:
        np.testing.assert_allclose(np.asarray(cache_pf[k]),
                                   np.asarray(cache[k]), rtol=FUSED_TOL,
                                   atol=FUSED_TOL, err_msg=k)
        idle = np.asarray(cache_pf[k])[:, 2 * n_lp:3 * n_lp]  # row 2's pages
        assert not idle.any(), k
    live = np.asarray(n_valid) > 0
    np.testing.assert_allclose(np.asarray(lg_pf)[live], lg[live],
                               rtol=FUSED_TOL, atol=FUSED_TOL)


def test_paged_page_reuse_no_stale_cache():
    """A tight page budget forces physical pages to be freed and handed
    to later requests; a request served on RECYCLED pages generates the
    same tokens as the same request served alone — zero-on-alloc leaves
    nothing of the previous tenant behind."""
    params = params_for(QWEN)
    eng = ServeEngine(QWEN, params, n_slots=2, kv="paged", page_size=4,
                      page_budget=6, chunk_size=8)
    reqs = tuple(Request(rid, 0, 5 + rid % 4, 2 + rid % 3)
                 for rid in range(6))
    crowded = eng.serve(RequestTrace(11, reqs))
    assert crowded.peak_pages <= 6          # the budget actually binds
    assert len({r.rid for r in crowded.results}) == 6
    for req in reqs:
        alone = eng.serve(RequestTrace(11, (req,)))
        got = next(r for r in crowded.results if r.rid == req.rid)
        assert got.tokens == alone.results[0].tokens, req


def test_paged_capacity_bounded_by_tokens_not_slots():
    """The pool admits by TOKENS IN FLIGHT: a budget far below
    n_slots * ceil(S/page) still serves the whole trace (admission
    blocks FIFO until completions free pages), and a long request never
    deadlocks the queue. Tokens stay bit-identical to the run on the
    default-budget pool, where nothing waits for pages."""
    params = params_for(QWEN)
    reqs = (Request(0, 0, 40, 8),) + tuple(
        Request(rid, 0, 4, 3) for rid in range(1, 7))
    trace = RequestTrace(13, reqs)
    roomy = ServeEngine(QWEN, params, n_slots=4, page_size=4,
                        chunk_size=8).serve(trace)
    # the default budget is 4 * ceil(47/4) = 48 pages; 16 is enough for
    # the long request (12 pages) plus one short at a time
    paged = ServeEngine(QWEN, params, n_slots=4, kv="paged", page_size=4,
                        page_budget=16, chunk_size=8).serve(trace)
    assert roomy.n_pages == 48
    assert [r.tokens for r in paged.results] == \
           [r.tokens for r in roomy.results]
    assert all(r.status == "ok" for r in paged.results)
    assert paged.peak_pages <= 16 < roomy.peak_pages
    assert paged.n_pages == 16


def test_paged_rejects_never_fitting_request():
    params = params_for(QWEN)
    eng = ServeEngine(QWEN, params, n_slots=2, kv="paged", page_size=4,
                      page_budget=3)
    with pytest.raises(ValueError, match="pages"):
        eng.serve(RequestTrace(1, (Request(0, 0, 30, 4),)))


def test_chunked_ttft_beats_token_and_is_recorded():
    """Long prompts: chunked admission reaches the first token in
    ceil(P/chunk) cycles, not P — TTFT is recorded per request at that
    level, and the report quantiles are populated."""
    params = params_for(TINY)
    trace = RequestTrace(3, tuple(Request(rid, 0, 64, 4)
                                  for rid in range(4)))
    chk = ServeEngine(TINY, params, n_slots=4,
                      chunk_size=16).serve(trace)
    for r in chk.results:
        assert r.first_token_cycle >= 0 and r.ttft_s >= 0.0
        assert 1 <= r.ttft_cycles <= -(-r.prompt_len // 16) + 1
    d = chk.to_dict()
    assert d["p50_ttft_cycles"] == chk.ttft_quantile(0.5)
    assert d["p99_ttft_s"] >= 0.0


@pytest.mark.parametrize("cfg", [TINY, QWEN],
                         ids=["paper-tinylstm", "qwen1.5-0.5b-reduced"])
def test_replay_deterministic_and_billing_exact_per_prefill(cfg):
    """Replay determinism and the exact-billing identity hold under
    BOTH cache kinds' prefills (the classifier's dense scan, the
    transformer's paged fused chunks), on a harsh ARQ link with real
    abandonments — and the static schedule's bills agree with the
    continuous one's request for request."""
    params = params_for(cfg)
    tr = make_trace(3, 12, prompt_lens=(3, 40), new_tokens=(2, 4),
                    snr_dbs=(5.0,))
    eng = ServeEngine(cfg, params, n_slots=4, radio=HARSH,
                      max_link_tries=2)
    a, b = eng.serve(tr), eng.serve(tr)
    assert [r.tokens for r in a.results] == [r.tokens for r in b.results]
    assert _bill_rows(a) == _bill_rows(b)
    assert any(r.status == "uplink_erased" for r in a.results)
    for r in a.results:
        assert (r.bits - r.erased_bits) + r.erased_bits == r.bits
        if r.status == "uplink_erased":
            assert r.tokens == () and r.erased_bits > 0
    assert _bill_rows(eng.serve(tr, "static")) == _bill_rows(a)


def test_engine_validates_prefill_kv_flags():
    """`prefill` and `kv` take only "chunked" and "paged"; the family,
    not the caller, decides the cache kind."""
    params = params_for(TINY)
    for bad in ("speculative", "token"):
        with pytest.raises(ValueError, match="prefill"):
            ServeEngine(TINY, params, prefill=bad)
    for bad in ("compressed", "dense"):
        with pytest.raises(ValueError, match="kv"):
            ServeEngine(TINY, params, kv=bad)
    assert ServeEngine(TINY, params, kv="paged").kv == "dense"
    assert ServeEngine(QWEN, params_for(QWEN)).kv == "paged"


def test_per_slot_attention_needs_pages():
    """Per-slot (vector-index) GQA decode and chunk prefill serve from
    the paged pool only: without `pages` they raise, as latent attention
    does; the scalar-index decode over the dense cache still runs."""
    model = M.get_model(QWEN)
    params = params_for(QWEN)
    B, S = 2, 8
    cache = model.init_cache(QWEN, B, S)
    tok = jnp.ones((B, 1), jnp.int32)
    with pytest.raises(NotImplementedError, match="paged"):
        model.decode_step(params, cache, tok, jnp.zeros(B, jnp.int32), QWEN)
    with pytest.raises(NotImplementedError, match="paged"):
        model.prefill_step(params, cache, jnp.ones((B, 4), jnp.int32),
                           jnp.zeros(B, jnp.int32),
                           jnp.full((B,), 4, jnp.int32), QWEN)
    logits, _ = model.decode_step(params, cache, tok, jnp.int32(0), QWEN)
    assert logits.shape == (B, 1, QWEN.vocab_size)


def test_page_pool_deterministic_alloc_and_guards():
    from repro.serve import PagePool, pages_needed, prefill_buckets, \
        bucket_for
    pool = PagePool(6)
    a = pool.alloc(3)
    assert a == [0, 1, 2] and pool.used_pages == 3
    pool.free([1])
    assert pool.alloc(2) == [1, 3]          # lowest free id first
    assert pool.peak_pages == 4             # 3 held, -1 freed, +2 held
    assert not pool.can_alloc(3)
    with pytest.raises(RuntimeError, match="exhausted"):
        pool.alloc(3)
    with pytest.raises(RuntimeError, match="double free"):
        pool.free([5])
    assert pages_needed(5, 3, 4) == 2       # cols 0..6 -> 2 pages
    assert pages_needed(1, 1, 4) == 1
    assert prefill_buckets(32) == (4, 8, 16, 32)
    assert prefill_buckets(20) == (4, 8, 16, 32)
    assert prefill_buckets(1) == (1,)
    assert bucket_for(5, (4, 8, 16)) == 8


# ------------------------------------------------ host spans and syncs
SPAN_TRACE = RequestTrace(5, (Request(0, 0, 3, 1), Request(1, 0, 5, 2),
                              Request(2, 0, 4, 4)))


@pytest.mark.parametrize("greedy", [True, False], ids=["greedy", "sampled"])
def test_host_syncs_and_spans_match_the_hand_count(greedy):
    """Three requests on four slots, every prompt in one chunk: cycle 0
    prefills all three (one read-back), cycles 1-3 decode the rest (one
    read-back each); each request costs a prompt draw, an uplink and a
    downlink payload. Sampling keys are derived in the step programs, so
    they cost no sync; under sampling each launch that samples a row
    (the prefill and the three decodes) fills its ids in one
    `serve.keys` span."""
    eng = ServeEngine(TINY, params_for(TINY), n_slots=4, chunk_size=8,
                      greedy=greedy)
    rep = eng.serve(SPAN_TRACE)
    keys = 0 if greedy else 1 + 3
    assert rep.cycles == 4
    assert rep.host_syncs == 3 * 3 + 4
    counts = {k: n for k, (_, n) in rep.spans.items()}
    want = {"serve.cycle": 4, "serve.admit": 3, "serve.prompt": 3,
            "serve.uplink": 3, "serve.downlink": 3,
            "serve.prefill.wait": 1, "serve.decode.wait": 3}
    if keys:
        want["serve.keys"] = keys
    assert counts == want
    assert all(s > 0 for s, _ in rep.spans.values())
    cycle_s = rep.spans["serve.cycle"][0]
    assert rep.spans["serve.admit"][0] <= cycle_s <= rep.wall_s


def test_erased_payloads_are_not_host_syncs():
    """On a link that erases whole rows, only delivered payloads are
    read back: syncs = prompt draws + delivered uplinks and downlinks +
    step read-backs (greedy: no keys)."""
    eng = ServeEngine(TINY, params_for(TINY), n_slots=4, radio=HARSH,
                      max_link_tries=2, greedy=True)
    rep = eng.serve(make_trace(3, 16, prompt_lens=(3, 8),
                               new_tokens=(2, 4), snr_dbs=(5.0,)))
    n = {k: c for k, (_, c) in rep.spans.items()}
    served = [r for r in rep.results if r.status != "uplink_erased"]
    assert len(served) < len(rep.results)
    assert rep.host_syncs == (len(rep.results) + len(served)
                              + sum(r.status == "ok" for r in served)
                              + n.get("serve.prefill.wait", 0)
                              + n.get("serve.decode.wait", 0))


def test_profiler_changes_no_token_or_bill(tmp_path):
    """Serving under `jax.profiler.trace` gives the same tokens and
    bills as without it, and the trace holds the engine's spans, as
    many of each as the report counts, with each request's id."""
    from jax.profiler import ProfileData
    eng = ServeEngine(TINY, params_for(TINY), n_slots=4, chunk_size=8)
    plain = eng.serve(SPAN_TRACE)
    with jax.profiler.trace(str(tmp_path)):
        traced = eng.serve(SPAN_TRACE)
    assert [r.tokens for r in traced.results] == \
        [r.tokens for r in plain.results]
    assert _bill_rows(traced) == _bill_rows(plain)
    assert traced.host_syncs == plain.host_syncs
    (path,) = tmp_path.glob("**/*.xplane.pb")
    seen, rids = {}, set()
    for plane in ProfileData.from_file(str(path)).planes:
        for line in plane.lines:
            for e in line.events:
                if e.name.startswith("serve."):
                    seen[e.name] = seen.get(e.name, 0) + 1
                    if e.name == "serve.admit":
                        rids.add(dict(e.stats)["rid"])
    assert seen == {k: n for k, (_, n) in traced.spans.items()}
    assert rids == {0, 1, 2}


# ------------------------------------------------ sampling keys in-step
def test_sample_keys_match_the_host_schedule():
    """The step programs' `sample_keys(base, ids)` gives, bit for bit,
    the documented schedule fold_in(fold_in(fold_in(base, rid), 9), t)
    computed eagerly on the host, over a grid of rids and positions."""
    from repro.serve.engine import SERVE_STREAM, sample_keys
    base = jax.random.PRNGKey(3700000111 + SERVE_STREAM)
    grid = [(r, t) for r in (0, 1, 2, 31, 255, 4097, 2 ** 30)
            for t in (0, 1, 2, 57, 100, 2 ** 20)]
    got = np.asarray(jax.jit(sample_keys)(base,
                                          jnp.asarray(grid, jnp.int32)))
    want = np.stack([np.asarray(jax.random.fold_in(jax.random.fold_in(
        jax.random.fold_in(base, r), 9), t)) for r, t in grid])
    np.testing.assert_array_equal(got, want)


#: tokens of `_staggered_trace()` at T = 0.8 (chunk 16, pages of 8, a
#: perfect link), served by the engine as it was when the host derived
#: each sampling key eagerly (`fold_in`, then a read-back) and passed
#: the keys into the step programs; on CPU.
SAMPLED_GOLDEN = {
    "qwen1.5-0.5b-reduced":
        [[512, 609, 907, 320, 832, 897],
         [405, 905, 231, 721, 578, 782, 757, 319, 902],
         [480, 550, 1015, 82], [735, 214, 1023, 386, 232],
         [407, 796, 134, 373, 991, 827, 591, 414], [762, 246, 1002]],
    "paper-tinylstm":
        [[1, 1, 0, 0, 1, 1], [0, 1, 0, 1, 1, 1, 1, 0, 1], [1, 0, 1, 0],
         [1, 1, 1, 1, 0], [1, 0, 0, 0, 1, 0, 1, 1], [1, 0, 0]],
}


@pytest.mark.parametrize("cfg,kv", [(QWEN, "paged"), (TINY, "dense")],
                         ids=["qwen1.5-0.5b-reduced-paged",
                              "paper-tinylstm-dense"])
def test_sampled_tokens_match_the_host_key_goldens(cfg, kv):
    """Keys derived inside the step programs sample the very tokens the
    host-derived keys sampled (`SAMPLED_GOLDEN`, produced by running the
    earlier engine on CPU); the family picks the cache kind `kv`."""
    eng = ServeEngine(cfg, params_for(cfg), n_slots=3, temperature=0.8,
                      chunk_size=16, page_size=8)
    rep = eng.serve(_staggered_trace())
    assert eng.kv == kv
    name = "paper-tinylstm" if cfg is TINY else "qwen1.5-0.5b-reduced"
    assert [list(r.tokens) for r in rep.results] == SAMPLED_GOLDEN[name]


@pytest.mark.parametrize("cfg", [QWEN, TINY],
                         ids=["qwen1.5-0.5b-reduced-paged",
                              "paper-tinylstm-dense"])
def test_new_trace_seed_does_not_recompile(cfg):
    """The trace's sampling base key is an argument of the step
    programs, not a constant: serving a second trace with another seed
    through one engine (either cache kind) compiles nothing more (one
    decode program, the same prefill buckets) and samples other
    tokens."""
    eng = ServeEngine(cfg, params_for(cfg), n_slots=3, chunk_size=8,
                      page_size=8)
    reqs = tuple(Request(rid, 0, 3 + 2 * rid, 4) for rid in range(3))
    a = eng.serve(RequestTrace(21, reqs))
    built = eng._compiled[max(8, RequestTrace(21, reqs).max_seq_len())]
    sizes = {k: built[k]._cache_size()
             for k in ("decode", "prefill_sample")}
    b = eng.serve(RequestTrace(22, reqs))
    assert sizes["decode"] == 1
    assert {k: built[k]._cache_size() for k in sizes} == sizes
    assert [r.tokens for r in a.results] != [r.tokens for r in b.results]


@pytest.mark.parametrize("page_size", [4, 16])
def test_page_size_is_layout_only(page_size):
    """The page length is a layout of the pool, not a result: greedy
    serving of the staggered trace on pages of 4 or 16 tokens gives the
    same tokens, statuses and bills as on pages of 8."""
    params = params_for(QWEN)
    trace = _staggered_trace()
    radio = Radio(snr_db=10.0, fading=True, arq_max_tx=6, arq_attempts=2)

    def serve(page):
        return ServeEngine(QWEN, params, n_slots=3, radio=radio,
                           greedy=True, chunk_size=16,
                           page_size=page).serve(trace)

    ref, rep = serve(8), serve(page_size)
    assert [(r.rid, r.status, r.tokens) for r in rep.results] == \
           [(r.rid, r.status, r.tokens) for r in ref.results]
    assert _bill_rows(rep) == _bill_rows(ref)
