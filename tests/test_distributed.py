"""Multi-device (8 fake host devices) equivalence tests, each in a
subprocess because the in-process JAX backend is pinned to 1 device."""
import os
import subprocess
import sys

import pytest

SCRIPT = os.path.join(os.path.dirname(__file__), "dist_checks.py")


def run_check(name: str):
    env = dict(os.environ)
    env.pop("XLA_FLAGS", None)
    res = subprocess.run([sys.executable, SCRIPT, name],
                         capture_output=True, text=True, timeout=600,
                         env=env)
    assert res.returncode == 0, f"{name} failed:\n{res.stdout}\n{res.stderr}"
    assert f"OK {name}" in res.stdout


@pytest.mark.parametrize("name", ["moe_ep", "train_step_sharded",
                                  "fl_pod_step", "fleet_pod",
                                  "chip_smoke_pod"])
def test_distributed(name):
    run_check(name)
