"""Grouped matmul over one layer of stacked expert weights: rows of `lhs`
sorted by group, rows [offsets[g], offsets[g+1]) times rhs[layer, g].

The expert weights stay one stacked array [L, G, k, n] in HBM and the
layer index is scalar-prefetched into the weight BlockSpec's index map,
so no per-layer slice of the weights is materialised: a slice taken in
the scan over layers is a copy, which XLA may even place in VMEM, and
then the copy, not the kernel, pays the weights' HBM read.

Grid (n tiles, visits, k tiles). A visit is one (m tile, group) pair;
`megablox.make_group_metadata` lists them, a tile once per group that
has rows in it and only tiles holding rows, so a group with no row is
never read and the visit count is dynamic. Visits of one m tile are
consecutive, so its output block stays in VMEM while each group writes
its own rows (masked store); rows past the groups' sum are never
written.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu
from jax.experimental.pallas.ops.tpu.megablox.gmm import make_group_metadata

from repro.kernels import resolve_interpret


def _gmm_kernel(meta, layer, lhs_ref, rhs_ref, out_ref, acc_ref, *,
                tm: int):
    offsets, group_ids, m_tile_ids = meta
    del layer                             # consumed by the index maps
    visit, k_i = pl.program_id(1), pl.program_id(2)

    @pl.when(k_i == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    acc_ref[...] += jnp.dot(lhs_ref[...], rhs_ref[...],
                            preferred_element_type=jnp.float32)

    @pl.when(k_i == pl.num_programs(2) - 1)
    def _store():
        g = group_ids[visit]
        rows = m_tile_ids[visit] * tm + jax.lax.broadcasted_iota(
            jnp.int32, (tm, 1), 0)
        mine = (rows >= offsets[g]) & (rows < offsets[g + 1])
        out_ref[...] = jnp.where(mine, acc_ref[...], out_ref[...])


def grouped_matmul(lhs: jax.Array, rhs: jax.Array, group_sizes: jax.Array,
                   layer: jax.Array, tiling: tuple,
                   interpret: bool | None = None) -> jax.Array:
    """lhs [m, k] (m a multiple of tm); rhs [L, G, k, n] (k, n multiples
    of tk, tn); group_sizes [G] int32 with sum <= m; `layer` int32
    scalar. Returns [m, n] fp32; rows past sum(group_sizes) unwritten."""
    m, k = lhs.shape
    n_groups, n = rhs.shape[1], rhs.shape[3]
    tm, tk, tn = tiling
    assert m % tm == 0 and k % tk == 0 and n % tn == 0, (m, k, n, tiling)
    meta, visits = make_group_metadata(
        group_sizes=group_sizes.astype(jnp.int32), m=m, tm=tm,
        start_group=jnp.zeros((), jnp.int32), num_nonzero_groups=n_groups,
        visit_empty_groups=False)
    return pl.pallas_call(
        functools.partial(_gmm_kernel, tm=tm),
        out_shape=jax.ShapeDtypeStruct((m, n), jnp.float32),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=2,
            grid=(n // tn, visits, k // tk),
            in_specs=[
                pl.BlockSpec((tm, tk), lambda n_i, v, k_i, meta, lay:
                             (meta[2][v], k_i)),
                pl.BlockSpec((None, None, tk, tn),
                             lambda n_i, v, k_i, meta, lay:
                             (lay[0], meta[1][v], k_i, n_i)),
            ],
            out_specs=pl.BlockSpec((tm, tn), lambda n_i, v, k_i, meta, lay:
                                   (meta[2][v], n_i)),
            scratch_shapes=[pltpu.VMEM((tm, tn), jnp.float32)],
        ),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary", "arbitrary")),
        interpret=resolve_interpret(interpret),
    )(meta, jnp.reshape(layer, (1,)).astype(jnp.int32), lhs, rhs)
