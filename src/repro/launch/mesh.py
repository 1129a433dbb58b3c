"""Production mesh factory. A FUNCTION (not module-level constant) so that
importing this module never touches jax device state — the dry-run sets
XLA_FLAGS before first jax init, everything else sees 1 CPU device."""
from __future__ import annotations

import math

import jax
from jax.sharding import AxisType

# TPU v5e hardware constants (roofline §EXPERIMENTS.md)
PEAK_FLOPS_BF16 = 197e12          # per chip
HBM_BW = 819e9                    # bytes/s per chip
ICI_BW = 50e9                     # bytes/s per link


def make_mesh(shape, axes):
    """A mesh of `shape` over the first devices, every axis Auto — the
    only kind `nn.sharding.constrain` (with_sharding_constraint) can
    name. Raises when there are fewer devices than the shape needs."""
    n = math.prod(shape)
    devices = jax.devices()
    if len(devices) < n:
        raise RuntimeError(
            f"mesh {tuple(shape)} needs {n} devices, have {len(devices)}")
    return jax.make_mesh(tuple(shape), tuple(axes),
                         axis_types=(AxisType.Auto,) * len(axes),
                         devices=devices[:n])


def make_production_mesh(*, multi_pod: bool = False):
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return make_mesh(shape, axes)
