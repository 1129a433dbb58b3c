"""jit'd wrapper of the grouped matmul kernel (`kernel.py`) for the
held-expert MoE layer: rows are padded to the m tile, and the k and n
tiles are picked so that one weight block stays near 1.5 MB of VMEM.

The kernel visits only the m tiles that hold rows of some group, so an
expert with no row is never read and rows past sum(group_sizes) are
left unwritten: callers mask them. On the device trace its calls are
the ops named `moe_gmm` (KERNEL_NAME)."""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from repro.kernels.moe_gmm.kernel import grouped_matmul

#: the name of the kernel's ops on the device trace
KERNEL_NAME = "moe_gmm"


def _tile(dim: int, cap: int) -> int:
    """The whole dim when it fits under `cap`, else the largest of 512,
    256, 128 that divides it, else the whole dim."""
    if dim <= cap:
        return dim
    for t in (512, 256, 128):
        if dim % t == 0:
            return t
    return dim


def tiling(m: int, k: int, n: int) -> tuple:
    """(tm, tk, tn): 128-row tiles for decode-sized batches, 512 above
    4096 rows."""
    tm = 128 if m <= 4096 else 512
    tk = _tile(k, 1536)
    tn = _tile(n, 1536 if tk <= 512 else 512)
    return tm, tk, tn


@functools.partial(jax.jit, static_argnames=("interpret",))
def moe_gmm(lhs: jax.Array, rhs: jax.Array, group_sizes: jax.Array,
            layer: jax.Array | int,
            interpret: bool | None = None) -> jax.Array:
    """lhs [m, k] rows sorted by group; rhs [L, G, k, n] stacked over
    layers, `layer` picking one; group_sizes [G] int32 with sum <= m.
    Returns [m, n] fp32; rows past the sum are not written."""
    m, k = lhs.shape
    tm, tk, tn = tiling(m, k, rhs.shape[3])
    pad = (-m) % tm
    if pad:
        lhs = jnp.pad(lhs, ((0, pad), (0, 0)))
    out = grouped_matmul(lhs, rhs.astype(lhs.dtype), group_sizes,
                         jnp.asarray(layer, jnp.int32), (tm, tk, tn),
                         interpret=interpret)
    return out[:m]
