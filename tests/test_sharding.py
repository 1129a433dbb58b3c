"""Sharding-resolver property tests + optimizer math (Eq. 13-14) + the
pod-mesh FL scheme smoke (subprocess: needs 8 fake host devices)."""
import os
import subprocess
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from _hyp import given, settings, strategies as st
from jax.sharding import PartitionSpec as P

from repro.launch.mesh import make_mesh
from repro.nn.sharding import resolve_spec, use_mesh, constrain
from repro.optim import sgd_momentum, adamw, clip_by_global_norm, global_norm
from repro.optim.clip import clip_array_by_norm
from repro.optim.schedule import step_decay

HS = settings(max_examples=25, deadline=None)

pytestmark = pytest.mark.skipif(
    len(jax.devices()) < 4 and False, reason="needs >=4 devices")


@pytest.fixture(scope="module")
def mesh():
    if len(jax.devices()) >= 4:
        return make_mesh((2, 2), ("data", "model"))
    return make_mesh((1, 1), ("data", "model"))


# ------------------------------------------------------------- resolver
def test_resolver_basic(mesh):
    # "batch" resolves to the data axis (pod absent), "mlp" to model —
    # axis sizes of 1 still match (divisibility is trivial).
    spec = resolve_spec((64, 128), ("batch", "mlp"), mesh)
    assert spec == P("data", "model")


@HS
@given(d0=st.sampled_from([1, 2, 3, 4, 6, 64]),
       d1=st.sampled_from([1, 2, 5, 16, 128]))
def test_resolver_divisibility_invariant(d0, d1):
    """An axis is only assigned when the mesh-axis size divides the dim."""
    mesh = make_mesh((1, 1), ("data", "model")) \
        if len(jax.devices()) < 4 else \
        make_mesh((2, 2), ("data", "model"))
    spec = resolve_spec((d0, d1), ("batch", "mlp"), mesh)
    parts = tuple(spec) + (None,) * (2 - len(tuple(spec)))
    for dim, part in zip((d0, d1), parts):
        if part is None:
            continue
        axes = (part,) if isinstance(part, str) else part
        size = int(np.prod([mesh.shape[a] for a in axes]))
        assert dim % size == 0


def test_resolver_no_axis_reuse(mesh):
    """The same mesh axis never shards two dims of one tensor."""
    spec = resolve_spec((64, 64, 64), ("batch", "embed", "mlp"), mesh)
    used = []
    for part in tuple(spec):
        if part is None:
            continue
        used.extend((part,) if isinstance(part, str) else part)
    assert len(used) == len(set(used))


def test_resolver_unknown_axis_replicates(mesh):
    spec = resolve_spec((64,), ("no_such_rule",), mesh)
    assert spec == P()


def test_constrain_noop_without_mesh():
    x = jnp.ones((8, 8))
    y = constrain(x, "batch", "mlp")
    np.testing.assert_array_equal(np.asarray(x), np.asarray(y))


def test_constrain_under_mesh(mesh):
    with use_mesh(mesh):
        y = jax.jit(lambda x: constrain(x, "batch", "mlp"))(jnp.ones((8, 8)))
    np.testing.assert_array_equal(np.asarray(y), 1.0)


def test_users_axis_resolves_to_pod():
    """The FL user axis maps onto `pod` (and batch degrades to data,
    pod being taken) — the scaled FL scheme's pod-mesh layout."""
    shape = (2, 2, 2) if len(jax.devices()) >= 8 else (1, 1, 1)
    mesh = make_mesh(shape, ("pod", "data", "model"))
    spec = resolve_spec((2, 8, 16), ("users", "batch", None), mesh)
    assert spec == P("pod", "data")


def test_scaled_fl_scheme_on_pod_mesh():
    """Satellite (ISSUE 5): the ported pod-mesh FL scheme runs a whole
    Experiment under xla_force_host_platform_device_count=8 (subprocess
    — the in-process backend is pinned to 1 device; dist_checks.py sets
    the flag) and matches the unsharded trajectory."""
    env = dict(os.environ)
    env.pop("XLA_FLAGS", None)
    script = os.path.join(os.path.dirname(__file__), "dist_checks.py")
    res = subprocess.run([sys.executable, script, "scaled_fl_scheme_pod"],
                         capture_output=True, text=True, timeout=600,
                         env=env)
    assert res.returncode == 0, \
        f"scaled_fl_scheme_pod failed:\n{res.stdout}\n{res.stderr}"
    assert "OK scaled_fl_scheme_pod" in res.stdout


# ------------------------------------------------------------- optimizer
def test_sgd_momentum_matches_eq_13_14():
    """v <- mu v + lr g ; w <- w - v (paper Eq. 13-14)."""
    init, update = sgd_momentum(mu := 0.9)
    params = {"w": jnp.asarray([1.0, 2.0])}
    state = init(params)
    g = {"w": jnp.asarray([0.5, -1.0])}
    lr = 0.1
    p1, s1 = update(g, state, params, lr)
    np.testing.assert_allclose(np.asarray(p1["w"]),
                               [1.0 - 0.05, 2.0 + 0.1], rtol=1e-6)
    p2, s2 = update(g, s1, p1, lr)
    v2 = mu * 0.05 + lr * 0.5
    np.testing.assert_allclose(float(p2["w"][0]), 0.95 - v2, rtol=1e-6)


def test_adamw_decreases_quadratic():
    init, update = adamw()
    params = {"w": jnp.asarray([5.0])}
    state = init(params)
    for _ in range(200):
        g = {"w": 2 * params["w"]}
        params, state = update(g, state, params, 0.1)
    assert abs(float(params["w"][0])) < 0.5


@HS
@given(seed=st.integers(0, 2 ** 16), clip=st.floats(0.1, 10.0))
def test_global_norm_clip(seed, clip):
    tree = {"a": jax.random.normal(jax.random.PRNGKey(seed), (17,)),
            "b": jax.random.normal(jax.random.PRNGKey(seed + 1), (3, 5))}
    clipped, pre_norm = clip_by_global_norm(tree, clip)
    gn = float(global_norm(clipped))
    assert gn <= clip * 1.001
    assert float(pre_norm) == pytest.approx(float(global_norm(tree)))
    if float(global_norm(tree)) <= clip:      # no-op when under threshold
        np.testing.assert_allclose(np.asarray(clipped["a"]),
                                   np.asarray(tree["a"]), rtol=1e-6)


def test_clip_array_by_norm_direction_preserved():
    x = jnp.asarray([3.0, 4.0])              # norm 5
    y = clip_array_by_norm(x, 0.5)
    np.testing.assert_allclose(np.asarray(y), [0.3, 0.4], rtol=1e-6)


def test_step_decay_schedule():
    """Paper: reduce by 10% every 5 epochs."""
    sched = step_decay(0.01, 0.9, 5)
    assert sched(0) == pytest.approx(0.01)
    assert sched(4) == pytest.approx(0.01)
    assert sched(5) == pytest.approx(0.009)
    assert sched(14) == pytest.approx(0.01 * 0.9 ** 2)
