"""Multi-device equivalence checks, run in a subprocess by
test_distributed.py (the main pytest process has already initialized JAX
with 1 CPU device; these need 8 fake host devices).

    python tests/dist_checks.py <check-name>
"""
import os
import sys

os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
sys.path.insert(0, os.path.join(os.path.dirname(__file__), "..", "src"))

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np

from repro.launch.mesh import make_mesh


def check_moe_ep():
    """Expert-parallel shard_map MoE == chunked single-device MoE."""
    from repro.configs import get_arch
    from repro.models.moe import _moe_chunked, _moe_ep, moe_specs
    from repro.nn import init_params, use_mesh
    mesh = make_mesh((2, 4), ("data", "model"))
    cfg = dataclasses.replace(get_arch("qwen3-moe-235b-a22b").reduced(),
                              capacity_factor=8.0)   # no drops -> exact
    p = init_params(jax.random.PRNGKey(0), moe_specs(cfg))
    x = jax.random.normal(jax.random.PRNGKey(1), (4, 32, cfg.d_model))
    y_ref, aux_ref = _moe_chunked(p, x, cfg)
    with use_mesh(mesh):
        y_ep, aux_ep = jax.jit(lambda p, x: _moe_ep(p, x, cfg, mesh))(p, x)
    np.testing.assert_allclose(np.asarray(y_ep), np.asarray(y_ref),
                               rtol=2e-4, atol=2e-4)
    # lb_loss averages per-(shard, chunk) estimates — a valid but not
    # bit-identical estimator of the global Switch loss
    np.testing.assert_allclose(float(aux_ep["lb_loss"]),
                               float(aux_ref["lb_loss"]), rtol=2e-2)
    print("OK moe_ep")


def check_train_step_sharded():
    """One sharded train step on the test mesh matches the unsharded
    step (same seed, same batch) for a reduced dense arch."""
    from repro.configs import get_arch
    from repro.configs.base import ShapeConfig
    from repro.nn import use_mesh
    from repro.runtime.train_step import init_train_state, make_train_step
    cfg = get_arch("qwen1.5-0.5b").reduced()
    shape = ShapeConfig("t", 32, 8, "train", microbatch=4)
    batch = {"tokens": jnp.ones((8, 32), jnp.int32) * 3,
             "labels": jnp.ones((8, 32), jnp.int32) * 3}
    key = jax.random.PRNGKey(0)

    state0 = init_train_state(key, cfg, None, "adamw")
    step = make_train_step(cfg, shape, None)
    _, m_ref = jax.jit(step)(state0, batch, jax.random.PRNGKey(1))

    mesh = make_mesh((2, 4), ("data", "model"))
    with use_mesh(mesh):
        state0 = init_train_state(key, cfg, None, "adamw")
        _, m_sh = jax.jit(step)(state0, batch, jax.random.PRNGKey(1))
    np.testing.assert_allclose(float(m_sh["loss"]), float(m_ref["loss"]),
                               rtol=2e-4)
    print("OK train_step_sharded")


def check_fl_pod_step():
    """Production FL step lowers and runs on the test mesh."""
    from repro.configs import get_arch
    from repro.configs.base import ShapeConfig, WirelessConfig
    from repro.nn import use_mesh
    from repro.runtime.fl_runtime import make_fl_train_step
    from repro.runtime.train_step import init_train_state
    cfg = get_arch("qwen1.5-0.5b").reduced()
    shape = ShapeConfig("t", 32, 4, "train", microbatch=4)
    wcfg = WirelessConfig(mode="fl", quant_bits=8, local_steps=2)
    mesh = make_mesh((2, 4), ("data", "model"))
    with use_mesh(mesh):
        state = init_train_state(jax.random.PRNGKey(0), cfg, None, "sgd")
        state = jax.tree.map(
            lambda p: jnp.broadcast_to(p, (2,) + p.shape), state)
        step = make_fl_train_step(cfg, shape, wcfg, n_users=2)
        batch = {"tokens": jnp.ones((2, 4, 32), jnp.int32),
                 "labels": jnp.ones((2, 4, 32), jnp.int32)}
        new_state, metrics = jax.jit(step)(state, batch,
                                           jax.random.PRNGKey(1))
    assert np.isfinite(float(metrics["loss"]))
    print("OK fl_pod_step")


def check_scaled_fl_scheme_pod():
    """The ported pod-mesh FL scheme (schemes/scaled.py) drives a whole
    Experiment on a (pod, data, model) mesh — the user axis sharded
    over `pod` via the "users" rule — and the trajectory matches the
    same scheme on no mesh (the sharding is a placement, not a math
    change). Billing: N users x model elems x Q8 per cycle, no ARQ."""
    from repro.configs import get_arch
    from repro.configs.base import ShapeConfig, WirelessConfig
    from repro.nn import use_mesh
    from repro.schemes import Experiment, build_scheme

    cfg = dataclasses.replace(get_arch("qwen1.5-0.5b").reduced(),
                              remat=False)
    shape = ShapeConfig("t", 16, 4, "train", microbatch=4)
    wcfg = WirelessConfig(mode="fl", quant_bits=8, local_steps=2,
                          n_users=2)

    def run(mesh):
        with use_mesh(mesh):
            scheme = build_scheme(wcfg, cfg=cfg, shape=shape)
            exp = Experiment(scheme, cycles=2, seed=0, n_train=64,
                             n_test=16, lr_schedule=lambda e: 1e-3)
            res = exp.run()
        return res, exp

    mesh = make_mesh((2, 2, 2), ("pod", "data", "model"))
    res_m, exp_m = run(mesh)
    res_0, _ = run(None)
    assert np.isfinite(res_m.loss).all()
    # cycle 1 (local phase + one sync) matches tightly; later cycles
    # drift more: the sync QUANTIZES weights, so a one-ulp sharded
    # reduction-order difference can flip a codeword boundary and jump
    # a weight by a whole quant step (this check still caught the
    # segment_max mis-partitioning, which scaled weights 4x)
    np.testing.assert_allclose(res_m.loss[0], res_0.loss[0], rtol=2e-4)
    np.testing.assert_allclose(res_m.loss, res_0.loss, rtol=0.15)
    elems = sum(int(l.size) for l in jax.tree.leaves(
        exp_m.final_state.train.trainable["model"])) // 2
    for rep in exp_m.reports:
        assert rep.bits == 2 * elems * 8 and rep.energy_j > 0
    print("OK scaled_fl_scheme_pod")


def check_fleet_pod():
    """The fleet engine's billing round is INVARIANT to the clients-axis
    device count: the same 16-client bounded-ARQ fleet billed on no
    mesh and on 1/2/4/8-way `pod` meshes (the "clients" logical axis
    shards over (pod, data)) produces bitwise-identical round totals
    and per-client detail arrays — the sharded fade/erasure draws are a
    placement, not a math change (cf. check_scaled_fl_scheme_pod)."""
    from repro.nn import use_mesh
    from repro.schemes import BATCH, ClientBatch, FleetScheme

    def bill(mesh):
        # one SNR class -> one 8-client FL group + one 8-client SL
        # cohort, so the [clients, ...] draws actually shard
        batch = ClientBatch.synthetic(16, seed=3, snr_classes=(6.0,),
                                      sl_frac=0.5, arq_max_tx=2,
                                      ge_p_gb=0.2, arq_backoff_s=0.01)
        scheme = FleetScheme(None, batch, train="off")
        dummy = jnp.zeros((BATCH, 4), jnp.int32)
        with use_mesh(mesh):
            state, _ = scheme.init(0, dummy, dummy[:, 0])
            rng = np.random.default_rng(1)
            reps = []
            for cyc in range(2):
                b = scheme.cycle_batches(state, rng, cyc)
                key = scheme.round_key(0, cyc)
                state, rep = scheme.round(state, b, key, 0.1)
                reps.append(rep)
        return reps, scheme.last_round_detail

    ref_reps, ref_det = bill(None)
    assert sum(r.erased_bits for r in ref_reps) > 0   # chaos fired
    for k in (1, 2, 4, 8):
        reps, det = bill(make_mesh((k,), ("pod",)))
        for c, (a, b) in enumerate(zip(ref_reps, reps)):
            for f in ("bits", "n_tx", "energy_j", "erased_bits",
                      "outage_s", "steps", "loss"):
                assert getattr(a, f) == getattr(b, f), \
                    f"{k}-shard cycle {c} {f}: {getattr(a, f)!r} " \
                    f"!= {getattr(b, f)!r}"
        for name in ("bits", "n_tx", "energy_j", "erased_bits",
                     "status", "est_round_s", "weight"):
            np.testing.assert_array_equal(
                np.asarray(ref_det[name]), np.asarray(det[name]),
                err_msg=f"{k}-shard detail {name}")
    print("OK fleet_pod")


def check_chip_smoke_pod():
    """chip_smoke.py's four-chip phase on four host devices: the pod-mesh
    FL round (one user per `pod` slot) against the same round on one
    device — cycle-1 loss within rtol 2e-4, bills equal."""
    import importlib.util
    from repro.configs import get_arch
    from repro.configs.base import ShapeConfig
    spec = importlib.util.spec_from_file_location(
        "chip_smoke", os.path.join(os.path.dirname(__file__), "..",
                                   "chip_smoke.py"))
    cs = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(cs)
    out = cs.phase_pod_fl(get_arch("qwen1.5-0.5b").reduced(),
                          ShapeConfig("t", 16, 2, "train"),
                          make_mesh((4, 1, 1), ("pod", "data", "model")))
    assert out["mesh"] == {"pod": 4, "data": 1, "model": 1}
    print("OK chip_smoke_pod")


CHECKS = {
    "moe_ep": check_moe_ep,
    "train_step_sharded": check_train_step_sharded,
    "fl_pod_step": check_fl_pod_step,
    "scaled_fl_scheme_pod": check_scaled_fl_scheme_pod,
    "fleet_pod": check_fleet_pod,
    "chip_smoke_pod": check_chip_smoke_pod,
}

if __name__ == "__main__":
    CHECKS[sys.argv[1]]()
