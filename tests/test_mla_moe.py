"""Latent attention (MLA), YaRN RoPE and the dropless held-expert MoE layer
of DeepSeek-V2, on a reduced deepseek-v2-lite in float32 on the CPU: the
absorbed serving form against the published (naive) form, YaRN against
hand-computed values, the expert-parallel shares summing to the uncut
layer, routing that piles every token on one expert dropping nothing,
and the grouped-matmul kernel against its reference in interpret mode."""
import dataclasses
import math

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import get_arch
from repro.kernels.moe_gmm.ops import moe_gmm
from repro.kernels.moe_gmm.ref import gmm_ref
from repro.models import api as M
from repro.models import layers as L
from repro.models import mla
from repro.models import transformer as T
from repro.models.moe import moe_held, moe_specs
from repro.nn import init_params

#: float32 on both sides, the same weights: the forms differ only in
#: the order of their sums (the absorbed form multiplies the query by
#: W_UK where the naive one multiplies the keys), so they agree to a few
#: float32 ulps of the logits' size
F32_TOL = 2e-5


def small_cfg(**kw):
    """A dense layer and two MoE layers, 8 experts of which all are held,
    top-3, small latent and rope widths, float32."""
    base = dict(n_layers=3, d_model=64, n_heads=4, n_kv_heads=4, d_ff=128,
                moe_d_ff=32, vocab_size=256, n_experts=8, top_k=3,
                kv_lora_rank=32, qk_nope_dim=16, qk_rope_dim=8,
                v_head_dim=16, dtype=jnp.float32, attn_chunk=16,
                capacity_factor=16.0)
    base.update(kw)
    return dataclasses.replace(get_arch("deepseek-v2-lite"), **base)


def _params(cfg, seed=0):
    return init_params(jax.random.PRNGKey(seed), M.param_specs(cfg))


def _pages(B, n_lp, page, active=None, kernel=False):
    tables = jnp.arange(B * n_lp, dtype=jnp.int32).reshape(B, n_lp)
    return {"tables": tables, "page_size": page, "active": active,
            "kernel": kernel}


def _pool(cfg, n_pages, page):
    return {k: jnp.zeros(s, d) for k, (s, a, d) in
            T.paged_cache_shapes(cfg, n_pages, page).items()}


def test_published_config_keys():
    c = get_arch("deepseek-v2-lite")
    assert (c.n_layers, c.d_model, c.n_heads, c.vocab_size) == \
        (27, 2048, 16, 102400)
    assert (c.kv_lora_rank, c.qk_nope_dim, c.qk_rope_dim, c.v_head_dim) == \
        (512, 128, 64, 128)
    assert (c.first_dense, c.d_ff, c.moe_d_ff, c.n_experts, c.top_k,
            c.shared_experts) == (1, 10944, 1408, 64, 6, 2)
    assert not c.norm_topk_prob and not c.tie_embed and c.norm_eps == 1e-6
    assert c.held == (0, 64) and mla.pool_width(c) == 576
    # every default keeps today's models as they were
    q = get_arch("qwen1.5-0.5b")
    assert q.norm_eps == 1e-5 and q.tie_embed and q.norm_topk_prob
    assert q.rope_scaling is None and not q.is_mla


def test_yarn_inv_freq_and_scale_by_hand():
    """DeepSeek-V2-Lite's rope half: 64 dims, base 1e4, factor 40 over
    4096 positions, beta 32/1. Correction dims: 64 ln(4096 / (32 * 2 pi))
    / (2 ln 1e4) = 10.47 -> 10, and with one rotation 22.51 -> 23; the
    ramp runs over pair indices 10..23."""
    c = get_arch("deepseek-v2-lite")
    inv = np.asarray(L.rope_inv_freq(64, 1e4, c.rope_scaling), np.float64)
    base = 1e4 ** (np.arange(0, 64, 2) / 64)
    for i in (0, 5, 10):                      # original frequencies
        assert inv[i] == pytest.approx(1 / base[i], rel=1e-6)
    for i in (23, 31):                        # interpolated by 40
        assert inv[i] == pytest.approx(1 / (40 * base[i]), rel=1e-6)
    t = (16 - 10) / 13                        # inside the ramp
    assert inv[16] == pytest.approx(
        t / (40 * base[16]) + (1 - t) / base[16], rel=1e-6)
    # softmax scale 192^-1/2 * (0.1 * 0.707 * ln 40 + 1)^2
    m = 0.1 * 0.707 * math.log(40) + 1
    assert m == pytest.approx(1.260804, abs=1e-6)
    assert mla.softmax_scale(c) == pytest.approx(0.1147225, rel=1e-5)
    # mscale == mscale_all_dim: cos and sin keep their size
    sin, cos = L.rope_angles(jnp.arange(5)[None], 64, 1e4, c.rope_scaling)
    np.testing.assert_allclose(np.asarray(sin ** 2 + cos ** 2), 1.0,
                               rtol=1e-6)


@pytest.mark.parametrize("kernel", [False, True])
def test_absorbed_mla_matches_naive(kernel):
    """The served (absorbed) latent attention over the paged latent pool,
    a prompt chunk then token steps, against the published form over the
    whole sequence: the same attention outputs for every position. With
    `kernel` the shared paged Pallas kernels run in interpret mode."""
    cfg = small_cfg()
    p = _params(cfg)["layers"]["attn"]
    p = jax.tree.map(lambda a: a[0], p)
    B, S, C, page, n_lp = 2, 12, 8, 4, 4
    x = jax.random.normal(jax.random.PRNGKey(3), (B, S, cfg.d_model))
    want = mla.mla_train(p, x, cfg)
    pool = jnp.zeros((1, B * n_lp, 1, page, mla.pool_width(cfg)))
    pages = _pages(B, n_lp, page)
    got, pool = mla.mla_prefill_slots(p, x[:, :C], cfg, pool, 0,
                                      jnp.zeros(B, jnp.int32),
                                      jnp.full((B,), C, jnp.int32), pages,
                                      kernel)
    outs = [got]
    for i in range(C, S):
        o, pool = mla.mla_decode_slots(p, x[:, i:i + 1], cfg, pool, 0,
                                       jnp.full((B,), i, jnp.int32),
                                       pages, kernel)
        outs.append(o)
    got = jnp.concatenate(outs, 1)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               atol=F32_TOL, rtol=F32_TOL)


def _uncut_moe(p, x, cfg):
    """The whole layer in plain jnp: every expert on every token, each
    weighted by its gate where it is among the token's top-k."""
    xf = x.reshape(-1, cfg.d_model)
    probs = jax.nn.softmax(xf @ p["router"]["w"], -1)
    gate, idx = jax.lax.top_k(probs, cfg.top_k)
    if cfg.norm_topk_prob:
        gate = gate / gate.sum(-1, keepdims=True)
    y = L.apply_mlp(p["shared"], xf) if cfg.shared_experts else 0.0
    for e in range(cfg.n_experts):
        h = jax.nn.silu(xf @ p["wg"][e]) * (xf @ p["wi"][e])
        ge = jnp.sum(jnp.where(idx == e, gate, 0.0), -1)
        y = y + ge[:, None] * (h @ p["wo"][e])
    return y.reshape(x.shape)


def _stacked(p, lo=0, hi=None):
    """The layer's experts [lo, hi) as `moe_held` takes them: stacked
    over a stack of one layer."""
    return dict(p, **{w: p[w][None, lo:hi] for w in ("wi", "wg", "wo")})


@pytest.mark.parametrize("norm_topk", [False, True])
def test_expert_shares_sum_to_the_uncut_layer(norm_topk):
    """Four chips of 2 experts each: the routed parts of their outputs,
    with the shared expert (which every chip computes alike) counted
    once, add up to the uncut layer."""
    cfg = small_cfg(norm_topk_prob=norm_topk, shared_experts=2)
    p = init_params(jax.random.PRNGKey(1), moe_specs(cfg))
    x = jax.random.normal(jax.random.PRNGKey(2), (3, 5, cfg.d_model))
    shared = L.apply_mlp(p["shared"], x)
    total = shared
    rows = 0
    for lo in range(0, 8, 2):
        c = dataclasses.replace(cfg, experts_held=(lo, lo + 2))
        y, st = moe_held(_stacked(p, lo, lo + 2), x, c, 0)
        total = total + (y - shared)
        rows += int(st[0])
    assert rows == 3 * 5 * cfg.top_k              # every assignment, once
    np.testing.assert_allclose(np.asarray(total),
                               np.asarray(_uncut_moe(p, x, cfg)),
                               atol=F32_TOL, rtol=F32_TOL)
    whole, st = moe_held(_stacked(p), x, cfg, 0)
    np.testing.assert_allclose(np.asarray(whole),
                               np.asarray(_uncut_moe(p, x, cfg)),
                               atol=F32_TOL, rtol=F32_TOL)


@pytest.mark.parametrize("kernel", [False, True])
def test_every_token_on_one_expert_drops_nothing(kernel):
    """A router that sends every token to experts 0, 1, 2 (top-3): the
    capacity dispatch would keep 1.25x the mean load and drop the rest;
    the held-expert layer computes every row."""
    cfg = small_cfg(capacity_factor=1.25, shared_experts=0)
    p = init_params(jax.random.PRNGKey(4), moe_specs(cfg))
    bias = jnp.zeros((cfg.d_model, cfg.n_experts)).at[:, :3].set(
        jnp.array([3.0, 2.0, 1.0]))
    p["router"]["w"] = 0.01 * p["router"]["w"] + bias
    x = jnp.abs(jax.random.normal(jax.random.PRNGKey(5),
                                  (4, 16, cfg.d_model)))
    y, st = moe_held(_stacked(p), x, cfg, 0, kernel=kernel)
    T_ = 4 * 16
    assert [int(v) for v in st] == [3 * T_, T_, 3]
    np.testing.assert_allclose(np.asarray(y),
                               np.asarray(_uncut_moe(p, x, cfg)),
                               atol=F32_TOL, rtol=F32_TOL)
    from repro.models.moe import apply_moe
    assert float(apply_moe(p, x, cfg)[1]["dropped_frac"]) >= 0.5


def test_invalid_rows_are_not_dispatched():
    cfg = small_cfg(shared_experts=0)
    p = init_params(jax.random.PRNGKey(6), moe_specs(cfg))
    x = jax.random.normal(jax.random.PRNGKey(7), (2, 6, cfg.d_model))
    valid = jnp.arange(6)[None, :] < jnp.array([[4], [0]])
    y, st = moe_held(_stacked(p), x, cfg, 0, valid=valid)
    assert int(st[0]) == 4 * cfg.top_k
    np.testing.assert_allclose(np.asarray(y[0, :4]),
                               np.asarray(_uncut_moe(p, x, cfg)[0, :4]),
                               atol=F32_TOL, rtol=F32_TOL)
    assert float(jnp.abs(y[0, 4:]).max()) == 0.0
    assert float(jnp.abs(y[1]).max()) == 0.0


@pytest.mark.parametrize("sizes", [[5, 0, 17, 1], [0, 0, 0, 40], [0, 0, 0, 0],
                                   [64, 64, 0, 0], [3, 9, 27, 81]])
def test_gmm_kernel_matches_ref(sizes):
    """The Pallas grouped matmul in interpret mode against its reference,
    with empty, uneven and full groups and a row count off the tile; rows
    past the groups' sum are not compared (the kernel leaves them)."""
    m, k, n = 200, 256, 128
    kx, kw = jax.random.split(jax.random.PRNGKey(sum(sizes)))
    lhs = jax.random.normal(kx, (m, k), jnp.float32)
    rhs = jax.random.normal(kw, (4, k, n), jnp.float32)
    gs = jnp.asarray(sizes, jnp.int32)
    got = moe_gmm(lhs, rhs[None], gs, 0, interpret=True)
    want = gmm_ref(lhs, rhs, gs)
    live = sum(sizes)
    assert got.shape == (m, n) and got.dtype == jnp.float32
    np.testing.assert_allclose(np.asarray(got)[:live],
                               np.asarray(want)[:live], rtol=1e-5,
                               atol=1e-4)
    assert float(jnp.abs(want[live:]).max(initial=0.0)) == 0.0


def test_serve_steps_match_forward_and_count_rows():
    """Paged fused prefill of a chunk, then decode steps, against the
    full forward's logits at every position; the counters count each
    valid token's held-expert rows once per MoE layer."""
    cfg = small_cfg(experts_held=(2, 6))
    params = _params(cfg, seed=8)
    B, S, C, page, n_lp = 2, 11, 8, 4, 4
    toks = jax.random.randint(jax.random.PRNGKey(9), (B, S), 1, 256)
    want, _ = T.forward(params, {"tokens": toks}, cfg)
    cache = _pool(cfg, B * n_lp, page)
    pages = _pages(B, n_lp, page)
    nv = jnp.array([C, 5], jnp.int32)
    lg, cache, st = T.prefill_step(params, cache, toks[:, :C],
                                   jnp.zeros(B, jnp.int32), nv, cfg,
                                   pages=pages, stats=True)
    np.testing.assert_allclose(np.asarray(lg[0]), np.asarray(want[0, C - 1]),
                               atol=F32_TOL, rtol=F32_TOL)
    np.testing.assert_allclose(np.asarray(lg[1]), np.asarray(want[1, 4]),
                               atol=F32_TOL, rtol=F32_TOL)
    assert 0 < int(st[0]) <= (C + 5) * cfg.top_k * 2
    assert int(st[1]) <= int(st[0]) and 0 < int(st[2]) <= 2 * 4
    act = jnp.array([True, False])
    for i in range(C, S):
        lg, cache, st = T.decode_step(
            params, cache, toks[:, i:i + 1], jnp.full((B,), i, jnp.int32),
            cfg, pages=dict(pages, active=act), stats=True)
        np.testing.assert_allclose(np.asarray(lg[0, 0]),
                                   np.asarray(want[0, i]),
                                   atol=F32_TOL, rtol=F32_TOL)
        assert int(st[0]) <= cfg.top_k * 2          # one valid row
    # a dense model has no counters
    q = get_arch("qwen1.5-0.5b").reduced()
    qp = _params(q)
    qc = {k: jnp.zeros(s, d) for k, (s, a, d) in
          T.paged_cache_shapes(q, B * n_lp, page).items()}
    _, _, st = T.decode_step(qp, qc, toks[:, :1], jnp.zeros(B, jnp.int32), q,
                             pages=pages, stats=True)
    assert st.shape == (0,)


def test_latent_cache_is_one_pool_and_only_paged():
    cfg = small_cfg()
    shapes = T.paged_cache_shapes(cfg, 10, 4)
    assert list(shapes) == ["latent"]
    assert shapes["latent"][0] == (3, 10, 1, 4, 40)
    assert M.get_model(cfg).paged_cache_shapes is T.paged_cache_shapes
    with pytest.raises(NotImplementedError):
        T.init_cache_shapes(cfg, 2, 16)
    assert "moe" in M.paged_families() and "ssm" not in M.paged_families()


def test_untied_head_and_eps():
    cfg = small_cfg()
    params = _params(cfg)
    assert "lm_head" in params and params["dense_layers"]["attn"][
        "wkv_a"]["w"].shape == (1, 64, 40)
    toks = jnp.arange(8, dtype=jnp.int32)[None] + 1
    lg, _ = T.forward(params, {"tokens": toks}, cfg)
    lg2, _ = T.forward(dict(params, lm_head={"table": 2 * params[
        "lm_head"]["table"]}), {"tokens": toks}, cfg)
    np.testing.assert_allclose(np.asarray(lg2), 2 * np.asarray(lg),
                               rtol=1e-5, atol=1e-6)
    lg3, _ = T.forward(params, {"tokens": toks},
                       dataclasses.replace(cfg, norm_eps=1.0))
    assert float(jnp.abs(lg3 - lg).max()) > 1e-4


def test_engine_sums_the_counters_at_no_extra_sync():
    """A reduced deepseek-v2-lite served through `ServeEngine` (paged,
    chunked prefill): every request ok, the held-expert counters summed
    into the report, and exactly the host syncs a model without
    counters makes on the same trace (qwen's reduced preset)."""
    from repro.serve import Request, RequestTrace, ServeEngine
    trace = RequestTrace(7, tuple(Request(i, 0, p, n) for i, (p, n) in
                                  enumerate([(5, 3), (9, 4), (3, 2)])))
    reports = {}
    for name, cfg in [("ds", small_cfg(experts_held=(0, 4))),
                      ("qwen", get_arch("qwen1.5-0.5b").reduced())]:
        eng = ServeEngine(cfg, _params(cfg), n_slots=2, chunk_size=4,
                          page_size=4, greedy=True)
        reports[name] = eng.serve(trace)
    ds, qw = reports["ds"], reports["qwen"]
    assert all(r.status == "ok" for r in ds.results)
    # 3 requests, 17 prompt + 6 decoded tokens, 2 MoE layers, top-3 of
    # 8 with 4 held: at most 23 * 2 * 3 rows
    assert 0 < ds.expert_rows <= 23 * 2 * 3
    assert ds.expert_rows_max <= ds.expert_rows
    assert 0 < ds.expert_groups <= ds.cycles * 2 * 2 * 4
    assert qw.expert_rows == qw.expert_rows_max == qw.expert_groups == 0
    assert ds.cycles == qw.cycles and ds.host_syncs == qw.host_syncs
