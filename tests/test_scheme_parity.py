"""Fixed-seed parity: the unified Scheme API must reproduce the
pre-refactor `train_cl` / `train_fl` / `train_sl` trajectories.

Goldens in golden_scheme_parity.json were captured from the legacy
driver loops (scripts/capture_golden.py) at commit time on the
reference CPU backend: accuracy/loss per cycle and total payload bits
for a 3072/512 corpus. The schemes must match them exactly (same RNG
streams, same batch order, same channel keys).

Noisy-SL is pinned on payload accounting only: routing the fused
`channel_crossing` through the packed wire (a ROADMAP item shipped with
this API) re-derives the channel-noise RNG stream, so the noisy
trajectory is statistically — not bitwise — unchanged. The
perfect-channel SL trajectory (quantization active, noise off) IS
bitwise-pinned, which exercises the full split+codec+wire pipeline.
"""
import json
import os

import jax
import numpy as np
import pytest

from benchmarks.common import train_cl, train_fl, train_sl
from repro.configs.base import WirelessConfig
from repro.core import wire as W
from repro.schemes import (CentralizedScheme, ClientSpec, Delivery,
                           Experiment, FederatedScheme, PopulationScheme,
                           Radio, SplitScheme, build_scheme, evaluate_sl)

N_TRAIN, N_TEST = 3072, 512


@pytest.fixture(scope="module")
def golden():
    path = os.path.join(os.path.dirname(__file__),
                        "golden_scheme_parity.json")
    with open(path) as f:
        return json.load(f)


def _assert_matches(res, want):
    np.testing.assert_allclose(res.accuracy, want["accuracy"], rtol=1e-6)
    np.testing.assert_allclose(res.loss, want["loss"], rtol=1e-6)
    assert res.total_bits == pytest.approx(want["total_bits"])


def _reports_cover_bits(exp, res):
    """RoundReport accounting must reassemble RunResult.total_bits."""
    init_bits = exp.init_delivery.bits if exp.init_delivery else 0.0
    total = init_bits + sum(r.bits for r in exp.reports)
    assert total / exp.scheme.bits_normalizer == pytest.approx(
        res.total_bits)


# ----------------------------------------------------------------- CL
def test_cl_clean_parity(golden):
    exp = Experiment(build_scheme(None), cycles=2, seed=0,
                     n_train=N_TRAIN, n_test=N_TEST)
    res = exp.run()
    assert isinstance(exp.scheme, CentralizedScheme)
    _assert_matches(res, golden["cl_clean"])
    _reports_cover_bits(exp, res)
    # rounds are radio-silent for CL: the whole payload is the upload
    assert exp.init_delivery.bits == res.total_bits
    assert all(r.bits == 0.0 for r in exp.reports)


def test_cl_noisy_parity(golden):
    res = train_cl(cycles=2, wcfg=WirelessConfig(mode="cl", snr_db=10.0),
                   seed=0, n_train=N_TRAIN, n_test=N_TEST)
    _assert_matches(res, golden["cl_noisy"])


# ----------------------------------------------------------------- FL
def test_fl_q8_parity(golden):
    scheme = build_scheme(WirelessConfig(mode="fl", quant_bits=8))
    assert isinstance(scheme, FederatedScheme)
    exp = Experiment(scheme, cycles=2, seed=0, n_train=N_TRAIN,
                     n_test=N_TEST)
    res = exp.run()
    _assert_matches(res, golden["fl_q8"])
    _reports_cover_bits(exp, res)
    # without ARQ the drawn counts collapse to one tx per (user, packet)
    n_packets = scheme.n_users * len(jax.tree.leaves(
        exp.final_state.train.trainable["model"]))
    assert all(r.n_tx == n_packets for r in exp.reports)


def test_fl_wrapper_is_thin(golden):
    res = train_fl(cycles=2, wcfg=WirelessConfig(mode="fl", quant_bits=8),
                   seed=0, n_train=N_TRAIN, n_test=N_TEST)
    _assert_matches(res, golden["fl_q8"])


# ----------------------------------------------------------------- SL
def test_sl_perfect_parity(golden):
    scheme = build_scheme(WirelessConfig(mode="sl", quant_bits=16,
                                         perfect_channel=True))
    assert isinstance(scheme, SplitScheme)
    exp = Experiment(scheme, cycles=2, seed=0, n_train=N_TRAIN,
                     n_test=N_TEST)
    res = exp.run()
    _assert_matches(res, golden["sl_perfect"])
    _reports_cover_bits(exp, res)


def test_sl_noisy_bits_parity(golden):
    res = train_sl(cycles=1, wcfg=WirelessConfig(mode="sl", quant_bits=16),
                   seed=0, n_train=N_TRAIN, n_test=N_TEST)
    assert res.total_bits == pytest.approx(
        golden["sl_noisy_bits"]["total_bits"])


# ------------------------------------------- population degeneracy
def test_population_all_fl_matches_federated_golden(golden):
    """An all-FL population with one (radio, J) group runs the identical
    vmapped local phase + stacked upload on the identical RNG stream as
    FederatedScheme: payload bits bit-for-bit, accuracy exact (the
    aggregated params are bitwise equal), loss within float32
    reduction-order tolerance (per-client means vs one flat mean)."""
    wcfg = WirelessConfig(mode="fl", quant_bits=8)
    clients = [ClientSpec.fl(wcfg) for _ in range(wcfg.n_users)]
    scheme = build_scheme(wcfg, clients=clients)
    assert isinstance(scheme, PopulationScheme)
    exp = Experiment(scheme, cycles=2, seed=0, n_train=N_TRAIN,
                     n_test=N_TEST)
    res = exp.run()
    want = golden["fl_q8"]
    assert res.total_bits == want["total_bits"]          # bit-for-bit
    np.testing.assert_array_equal(res.accuracy, want["accuracy"])
    np.testing.assert_allclose(res.loss, want["loss"], rtol=1e-5)
    _reports_cover_bits(exp, res)
    for rep in exp.reports:
        assert len(rep.clients) == wcfg.n_users
        assert sum(c.bits for c in rep.clients) == rep.bits
        assert all(c.paradigm == "fl" for c in rep.clients)


def test_population_all_sl_matches_split_golden(golden):
    """A single-client all-SL population is SplitScheme's fused loop:
    the aggregation of one weight-1 client is the identity, so the whole
    trajectory is bitwise the golden one."""
    wcfg = WirelessConfig(mode="sl", quant_bits=16, perfect_channel=True)
    exp = Experiment(build_scheme(wcfg, clients=[ClientSpec.sl(wcfg)]),
                     cycles=2, seed=0, n_train=N_TRAIN, n_test=N_TEST)
    res = exp.run()
    want = golden["sl_perfect"]
    assert res.total_bits == want["total_bits"]          # bit-for-bit
    np.testing.assert_array_equal(res.accuracy, want["accuracy"])
    np.testing.assert_array_equal(res.loss, want["loss"])
    _reports_cover_bits(exp, res)
    rep = exp.reports[0]
    assert len(rep.clients) == 1 and rep.clients[0].paradigm == "sl"
    assert rep.clients[0].weight == 1.0


# -------------------------------------------------- Radio accounting
def test_radio_delivery_matches_wire_payload_bits():
    tree = {"w": jax.random.normal(jax.random.PRNGKey(0), (64, 32)),
            "b": jax.random.normal(jax.random.PRNGKey(1), (17,))}
    radio = Radio(quant_bits=8, snr_db=20.0)
    dlv = radio.send_tree(jax.random.PRNGKey(2), tree)
    assert isinstance(dlv, Delivery)
    assert dlv.bits == W.payload_bits(tree, 8)      # no ARQ: drawn == 1
    assert dlv.n_tx == 2.0                          # one tx per packet
    assert dlv.energy_j > 0.0
    for a, b in zip(jax.tree.leaves(tree), jax.tree.leaves(dlv.payload)):
        assert a.shape == b.shape


def test_radio_arq_surfaces_drawn_retransmissions():
    """With outage-ARQ on a fading link, the DRAWN per-packet counts in
    the Delivery exceed one transmission per packet and the billed bits
    grow accordingly (satellite: actual, not expectation-only)."""
    tree = {f"l{i}": jax.random.normal(jax.random.PRNGKey(i), (32,))
            for i in range(24)}
    radio = Radio(quant_bits=8, snr_db=5.0, arq_attempts=4)
    dlv = radio.send_tree(jax.random.PRNGKey(99), tree)
    n_packets = 24
    assert dlv.n_tx > n_packets            # some deep fades were redrawn
    assert dlv.bits > W.payload_bits(tree, 8)
    assert dlv.bits == pytest.approx(8 * 32 * dlv.n_tx)  # equal-size pkts
    # and the analytic expectation brackets sanity: 1 < E[tx] <= attempts
    assert 1.0 < radio.expected_tx() < 4.0


def test_radio_send_tokens_charges_bits_even_when_perfect():
    """Satellite: CL payload accounting is one convention — the dataset
    crossing is billed perfect or not (the old code charged 0 in
    upload_batch but full bits in train_cl)."""
    toks = np.ones((16, 30), np.int32)
    labs = np.ones((16,), np.int32)
    ideal = Radio.from_wcfg(None)
    dlv = ideal.send_tokens(jax.random.PRNGKey(0), toks, 10_000,
                            labels=labs)
    assert dlv.bits == 16 * 30 * 14 + 16
    assert np.array_equal(np.asarray(dlv.payload), toks)   # noiseless
    from repro.core import centralized
    wcfg = WirelessConfig(mode="cl", perfect_channel=True)
    _, bits = centralized.upload_batch(
        jax.random.PRNGKey(0), {"tokens": toks, "labels": labs},
        10_000, wcfg)
    assert bits == dlv.bits


def test_fl_scheme_derives_n_users_from_custom_shards():
    """A shards/wcfg.n_users mismatch must not train on uninitialized
    batch memory: the shard list defines the population."""
    from repro.schemes import corpus
    (xtr, ytr), _ = corpus(N_TRAIN, N_TEST, 0)
    shards = [(xtr[:1024], ytr[:1024]), (xtr[1024:2048], ytr[1024:2048])]
    wcfg = WirelessConfig(mode="fl", quant_bits=8)     # n_users=3 default
    scheme = FederatedScheme(wcfg, shards=shards)
    assert scheme.n_users == 2
    assert scheme.bits_normalizer == 2.0
    state, _ = scheme.init(0, xtr, ytr)
    batch = scheme.cycle_batches(state, np.random.default_rng(1), 0)
    assert batch["tokens"].shape[0] == 2


def test_fl_capture_with_dp_is_rejected():
    with pytest.raises(ValueError, match="capture"):
        FederatedScheme(WirelessConfig(mode="fl"), capture=True,
                        dp_sigma=0.5)


def test_fl_dp_round_reports_expected_transmissions():
    """The DP upload path exposes no per-packet diagnostics, but N users
    x P packets still crossed the channel: the report carries the
    analytic expectation, not 0."""
    from repro.schemes import corpus
    (xtr, ytr), _ = corpus(N_TRAIN, N_TEST, 0)
    scheme = FederatedScheme(WirelessConfig(mode="fl", quant_bits=8),
                             dp_sigma=0.5)
    state, _ = scheme.init(0, xtr, ytr)
    batch = scheme.cycle_batches(state, np.random.default_rng(1), 0)
    _, rep = scheme.round(state, batch, scheme.round_key(0, 0), 0.1)
    n_packets = scheme.n_users * len(jax.tree.leaves(
        state.train.trainable["model"]))
    assert rep.n_tx == n_packets * scheme.radio.expected_tx() > 0
    assert rep.bits > 0


# ------------------------------------------- fused-SL ARQ consistency
def test_drawn_tx_replay_matches_wire_diag():
    """`wire.drawn_tree_tx` replays the EXACT fade/ARQ stream the
    packed wire draws for the same key — the mechanism that lets the
    fused SL path bill drawn retransmissions for crossings buried
    inside the jitted train step."""
    import jax.numpy as jnp
    key = jax.random.PRNGKey(5)
    z = jax.random.normal(jax.random.PRNGKey(0), (16, 13, 8))
    _, diag = W.transmit_tree(key, z, bits=8, snr_db=5.0,
                              arq_attempts=4, return_diag=True)
    assert int(W.drawn_tree_tx(key, 1, arq_attempts=4)) \
        == int(diag["n_tx"].sum())
    # multi-leaf trees: one replayed count per packet
    tree = {"a": z, "b": jnp.ones((7,))}
    _, diag2 = W.transmit_tree(key, tree, bits=8, snr_db=5.0,
                               arq_attempts=4, return_diag=True)
    assert int(W.drawn_tree_tx(key, 2, arq_attempts=4)) \
        == int(diag2["n_tx"].sum())
    # and without ARQ the replay is the analytic one-per-packet count
    assert int(W.drawn_tree_tx(key, 3)) == 3


def test_fused_sl_arq_bills_drawn_retransmissions(golden):
    """ROADMAP fix: under ARQ the fused SL path now simulates the
    link-layer redraws inside the jitted step (`channel_crossing`
    carries arq_attempts/arq_min_f2) and bills bits/energy at the
    DRAWN n_tx replayed outside the jit — the two-party protocol's
    convention, instead of E[tx]-n_tx over unscaled bits."""
    wcfg = WirelessConfig(mode="sl", quant_bits=8, snr_db=5.0,
                          arq_attempts=4)
    scheme = build_scheme(wcfg)
    exp = Experiment(scheme, cycles=1, seed=0, n_train=1024, n_test=512)
    exp.run()
    (rep,) = exp.reports
    assert rep.n_tx > 2 * rep.steps              # deep fades were redrawn
    assert rep.n_tx <= 2 * rep.steps * wcfg.arq_attempts
    assert rep.bits == pytest.approx(rep.n_tx * scheme.bits_per_batch / 2)
    assert rep.energy_j == pytest.approx(scheme.radio.energy_j(rep.bits))
    # the analytic expectation brackets the drawn average
    assert 1.0 < scheme.radio.expected_tx() < wcfg.arq_attempts


# ------------------------------------------------- SL eval convention
def test_sl_eval_convention_is_real_channel_with_escape_hatch():
    """ONE SL eval convention (ROADMAP fix): the deployed function
    scores through the REAL channel on fixed eval keys for both
    protocols; `perfect_eval=True` is the noiseless escape hatch (the
    pre-unification fused behavior)."""
    import dataclasses
    from repro.schemes import corpus
    (xtr, ytr), (xte, yte) = corpus(1024, 512, 0)
    wcfg = WirelessConfig(mode="sl", quant_bits=16, snr_db=-5.0)
    scheme = SplitScheme(wcfg)
    state, _ = scheme.init(0, xtr, ytr)
    tr = state.train.trainable
    noisy = evaluate_sl(tr, wcfg, xte, yte)
    assert noisy == evaluate_sl(tr, wcfg, xte, yte)   # fixed eval keys
    perfect = evaluate_sl(tr, wcfg, xte, yte, perfect_eval=True)
    assert noisy != perfect            # at -5 dB the channel bites
    assert scheme.evaluate(state, xte, yte) == noisy  # scheme default
    assert SplitScheme(wcfg, perfect_eval=True).evaluate(
        state, xte, yte) == perfect                   # escape hatch
    # on an already-perfect link the two conventions coincide
    wp = dataclasses.replace(wcfg, perfect_channel=True)
    assert evaluate_sl(tr, wp, xte, yte) == \
        evaluate_sl(tr, wp, xte, yte, perfect_eval=True)


# ------------------------------------------- scaled-scheme parity
# The scaled schemes (schemes/scaled.py) must reproduce the legacy
# bespoke loops they replaced — launch/train.py's
# `fold_in(PRNGKey(seed), step)` stream over `make_train_step`, and a
# straight `make_fl_train_step` cycle loop on `fold_in(PRNGKey(seed+3),
# cycle)` — bit for bit, on the test mesh the dry-run degrades to.

def _scaled_cfg_shape():
    import dataclasses
    from repro.configs import get_arch
    from repro.configs.base import ShapeConfig
    cfg = dataclasses.replace(get_arch("qwen1.5-0.5b").reduced(),
                              remat=False)
    return cfg, ShapeConfig("t", 16, 4, "train", microbatch=4)


def _replay_batches(scheme, state, seed, cycles):
    """The exact per-cycle batch lists the Experiment rng produces."""
    rng = np.random.default_rng(seed + 1)
    return [scheme.cycle_batches(state, rng, c) for c in range(cycles)]


def _tree_equal(a, b):
    for x, y in zip(jax.tree.leaves(a), jax.tree.leaves(b)):
        np.testing.assert_array_equal(np.asarray(x), np.asarray(y))


def test_scaled_cl_parity_vs_legacy_loop():
    """ScaledCentralizedScheme through Experiment == the deleted
    launch/train.py loop (same step factory, same key folds, same
    batches): identical loss trajectory and bitwise-identical params."""
    from repro.launch.mesh import make_mesh
    from repro.nn import use_mesh
    from repro.runtime.train_step import init_train_state, make_train_step
    from repro.schemes import ScaledCentralizedScheme
    cfg, shape = _scaled_cfg_shape()
    seed, cycles, spc, lr = 0, 2, 2, 1e-3
    with use_mesh(make_mesh((1, 1), ("data", "model"))):
        scheme = build_scheme(None, cfg=cfg, shape=shape,
                              steps_per_cycle=spc)
        assert isinstance(scheme, ScaledCentralizedScheme)
        exp = Experiment(scheme, cycles=cycles, seed=seed, n_train=64,
                         n_test=16, lr_schedule=lambda e: lr)
        res = exp.run()
        # rounds are radio-silent; the whole payload is the init upload
        assert exp.init_delivery.bits == res.total_bits > 0
        assert all(r.bits == 0.0 for r in exp.reports)

        # ---- the legacy loop, inline (launch/train.py pre-refactor)
        (xtr, ytr), _ = scheme.default_data(64, 16, seed)
        twin = build_scheme(None, cfg=cfg, shape=shape,
                            steps_per_cycle=spc)
        tstate, _ = twin.init(seed, xtr, ytr)
        batches = _replay_batches(twin, tstate, seed, cycles)
        state = init_train_state(jax.random.PRNGKey(seed), cfg, None,
                                 "adamw")
        step = jax.jit(make_train_step(cfg, shape, None))
        key, i, losses = jax.random.PRNGKey(seed), 0, []
        for cyc_batches in batches:
            for b in cyc_batches:
                state, m = step(state, b, jax.random.fold_in(key, i), lr)
                i += 1
            losses.append(float(m["loss"]))
    assert losses == res.loss
    _tree_equal(state.trainable, exp.final_state.train.trainable)


def test_scaled_fl_parity_vs_legacy_loop():
    """ScaledFederatedScheme through Experiment == a straight
    make_fl_train_step cycle loop, with the sync billed at the paper's
    per-user convention (no ARQ: one tx per (user, leaf) packet)."""
    from repro.launch.mesh import make_mesh
    from repro.nn import use_mesh
    from repro.runtime.fl_runtime import make_fl_train_step
    from repro.runtime.train_step import init_train_state
    from repro.schemes import ScaledFederatedScheme
    import jax.numpy as jnp
    cfg, shape = _scaled_cfg_shape()
    seed, cycles, lr = 0, 2, 1e-3
    wcfg = WirelessConfig(mode="fl", quant_bits=8, local_steps=2,
                          n_users=2)
    with use_mesh(make_mesh((1, 1), ("data", "model"))):
        scheme = build_scheme(wcfg, cfg=cfg, shape=shape)
        assert isinstance(scheme, ScaledFederatedScheme)
        exp = Experiment(scheme, cycles=cycles, seed=seed, n_train=64,
                         n_test=16, lr_schedule=lambda e: lr)
        res = exp.run()

        # ---- the legacy loop, inline
        (xtr, ytr), _ = scheme.default_data(64, 16, seed)
        twin = build_scheme(wcfg, cfg=cfg, shape=shape)
        tstate, _ = twin.init(seed, xtr, ytr)
        batches = _replay_batches(twin, tstate, seed, cycles)
        state0 = init_train_state(jax.random.PRNGKey(seed), cfg, None,
                                  "sgd")
        state = jax.tree.map(
            lambda p: jnp.broadcast_to(p, (2,) + p.shape), state0)
        fl_step = jax.jit(make_fl_train_step(cfg, shape, wcfg, n_users=2))
        losses = []
        for cyc, b in enumerate(batches):
            key = jax.random.fold_in(jax.random.PRNGKey(seed + 3), cyc)
            state, m = fl_step(state, b, key, lr)
            losses.append(float(m["loss"]))
    assert losses == res.loss
    _tree_equal(state.trainable, exp.final_state.train.trainable)
    # billing: N users x model elems x Q8, one tx per packet (no ARQ)
    elems = sum(int(l.size) for l in
                jax.tree.leaves(state.trainable["model"])) // 2
    n_leaves = len(jax.tree.leaves(state.trainable["model"]))
    for rep in exp.reports:
        assert rep.bits == 2 * elems * 8
        assert rep.n_tx == 2 * n_leaves
    assert res.total_bits == pytest.approx(       # per-user convention
        sum(r.bits for r in exp.reports) / 2)


def test_scaled_sl_parity_and_drawn_arq_billing():
    """ScaledSplitScheme (fused split step) == the legacy loop over
    make_train_step with the SL wcfg; under ARQ the per-step legs bill
    DRAWN retransmissions replayed outside the jit, like the tiny
    fused path."""
    from repro.core.split import crossing_elems
    from repro.runtime.train_step import init_train_state, make_train_step
    from repro.schemes import ScaledSplitScheme
    cfg, shape = _scaled_cfg_shape()
    seed, cycles, spc, lr = 0, 2, 2, 1e-3
    wcfg = WirelessConfig(mode="sl", quant_bits=8, snr_db=5.0,
                          arq_attempts=4)
    scheme = build_scheme(wcfg, cfg=cfg, shape=shape, steps_per_cycle=spc)
    assert isinstance(scheme, ScaledSplitScheme)
    exp = Experiment(scheme, cycles=cycles, seed=seed, n_train=64,
                     n_test=16, lr_schedule=lambda e: lr)
    res = exp.run()

    # ---- the legacy loop, inline
    (xtr, ytr), _ = scheme.default_data(64, 16, seed)
    twin = build_scheme(wcfg, cfg=cfg, shape=shape, steps_per_cycle=spc)
    tstate, _ = twin.init(seed, xtr, ytr)
    batches = _replay_batches(twin, tstate, seed, cycles)
    state = init_train_state(jax.random.PRNGKey(seed), cfg, wcfg, "adamw")
    step = jax.jit(make_train_step(cfg, shape, wcfg))
    key, i, losses = jax.random.PRNGKey(seed), 0, []
    for cyc_batches in batches:
        for b in cyc_batches:
            state, m = step(state, b, jax.random.fold_in(key, i), lr)
            i += 1
        losses.append(float(m["loss"]))
    assert losses == res.loss
    _tree_equal(state.trainable, exp.final_state.train.trainable)
    # drawn-ARQ billing: more than one tx per leg, bits scale with n_tx
    leg = crossing_elems(cfg, shape, wcfg)
    for rep in exp.reports:
        assert 2 * spc < rep.n_tx <= 2 * spc * wcfg.arq_attempts
        assert rep.bits == pytest.approx(rep.n_tx * leg * 8)


@pytest.mark.parametrize("mode,sync", [("cl", "barrier"), ("sl", "barrier"),
                                       ("fl", "barrier"), ("fl", "delayed")])
def test_scaled_round_donates_train_state(mode, sync):
    """The scaled schemes' run program donates the train state, like the
    `lower_step` program the dry-run reads memory from: a round frees
    the state it was given, and two rounds in a row run (the delayed-FL
    carry holds no buffer twice)."""
    cfg, shape = _scaled_cfg_shape()
    wcfg = None if mode == "cl" else WirelessConfig(
        mode=mode, quant_bits=8, local_steps=2, n_users=2, sync=sync)
    scheme = build_scheme(wcfg, cfg=cfg, shape=shape, steps_per_cycle=2)
    (xtr, ytr), _ = scheme.default_data(64, 16, 0)
    state, _ = scheme.init(0, xtr, ytr)
    rng = np.random.default_rng(1)
    for cyc in range(2):
        before = jax.tree.leaves(state.train)
        state, rep = scheme.round(state, scheme.cycle_batches(state, rng, cyc),
                                  scheme.round_key(0, cyc), 1e-3)
        assert all(x.is_deleted() for x in before)
        assert np.isfinite(rep.loss)
    assert not any(x.is_deleted() for x in jax.tree.leaves(state.train))


def test_wire_diag_does_not_change_payload():
    """return_diag is accounting-only: same key -> same received tree."""
    tree = {"w": jax.random.normal(jax.random.PRNGKey(0), (33, 9))}
    key = jax.random.PRNGKey(5)
    plain = W.transmit_tree(key, tree, bits=8, snr_db=6.0)
    with_diag, diag = W.transmit_tree(key, tree, bits=8, snr_db=6.0,
                                      return_diag=True)
    np.testing.assert_array_equal(np.asarray(plain["w"]),
                                  np.asarray(with_diag["w"]))
    assert diag["n_tx"].shape == (1,)
    assert int(diag["n_tx"][0]) == 1
