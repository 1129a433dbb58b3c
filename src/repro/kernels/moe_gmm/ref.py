"""Plain reference of the grouped matmul: rows [0, sizes[0]) of `lhs`
times rhs[0], the next sizes[1] rows times rhs[1], and so on; rows past
sum(sizes) come out as zeros."""
import jax.numpy as jnp


def gmm_ref(lhs, rhs, group_sizes):
    """lhs [m, k], rhs [g, k, n], group_sizes [g] int32 -> [m, n] fp32."""
    m = lhs.shape[0]
    ends = jnp.cumsum(group_sizes)
    gid = jnp.searchsorted(ends, jnp.arange(m), side="right")     # [m]
    out = jnp.einsum("mk,gkn->gmn", lhs, rhs,
                     preferred_element_type=jnp.float32)
    rows = jnp.take_along_axis(
        out, jnp.clip(gid, 0, rhs.shape[0] - 1)[None, :, None], axis=0)[0]
    return jnp.where((gid < rhs.shape[0])[:, None], rows, 0.0)
