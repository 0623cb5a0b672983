"""Pure-jnp grouped-matmul oracle: every group's matrix applied to every
row, each row keeping its own group's product."""

from __future__ import annotations

import jax
import jax.numpy as jnp


def moe_gmm_ref(lhs: jax.Array, rhs: jax.Array,
                group_sizes: jax.Array) -> jax.Array:
    """lhs (m, k), rhs (G, k, n), group_sizes (G,) -> (m, n) in float32;
    rows past the last group are zero."""
    ends = jnp.cumsum(group_sizes)
    row = jnp.arange(lhs.shape[0])
    group = jnp.searchsorted(ends, row, side="right")       # (m,)
    mine = group[None, :] == jnp.arange(rhs.shape[0])[:, None]   # (G, m)
    every = jnp.einsum("mk,gkn->gmn", lhs.astype(jnp.float32),
                       rhs.astype(jnp.float32),
                       precision=jax.lax.Precision.HIGHEST)
    return jnp.where(mine[..., None], every, 0.0).sum(0)
