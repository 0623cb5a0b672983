"""Grouped matmul over a chip's held experts, as a Pallas TPU kernel.

``moe_gmm(lhs, rhs, group_sizes)`` computes, for each group ``g``, the
rows ``lhs[o_g : o_g + group_sizes[g]] @ rhs[g]``, where ``o_g`` is the sum
of the sizes before ``g``: ``lhs`` holds the (token, expert) pairs sorted
by expert, ``rhs`` one weight matrix per held expert. Rows past the last
group (pairs routed to experts that another chip holds) are never read and
their output rows are left unwritten: the caller masks them.

Adapted from the forward kernel of ``jax.experimental.pallas.ops.tpu.
megablox.gmm``. The grid runs over (n tiles, m-tile visits, k tiles); a
visit is one (group, m tile) pair that holds rows of that group, so a group
with no rows costs nothing and an expert's weights stream from HBM once per
m tile its rows touch. The number of visits is read from the group sizes on
the device (a scalar-prefetched, dynamic grid), so the kernel's work
follows the routing, with no capacity.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax import lax
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

NAME = "moe_gmm"          # the kernel's name in the chip's trace


def group_metadata(group_sizes: jax.Array, m: int, tm: int):
    """(group offsets (G+1,), group id and m tile of each visit, number of
    visits). Visits are ordered by m tile, so that an output tile's visits
    are consecutive; empty groups get none."""
    G = group_sizes.shape[0]
    ends = jnp.cumsum(group_sizes)
    offsets = jnp.concatenate([jnp.zeros(1, jnp.int32), ends])
    starts = offsets[:-1]
    # tiles a group touches: from the tile of its first row to that of its
    # last, none where it is empty
    first_tile = starts // tm
    last_tile = (ends + tm - 1) // tm
    tiles = jnp.where(group_sizes == 0, 0, last_tile - first_tile)
    tiles_m = m // tm
    size = tiles_m + G - 1
    group_ids = jnp.repeat(jnp.arange(G, dtype=jnp.int32), tiles,
                           total_repeat_length=size)
    # the visit's m tile: the group's first tile plus the visit's rank
    # within the group
    visit = jnp.arange(size, dtype=jnp.int32)
    first_visit = jnp.cumsum(tiles) - tiles
    m_tile_ids = first_tile[group_ids] + visit - first_visit[group_ids]
    num_visits = tiles.sum()
    return (offsets, group_ids, m_tile_ids.astype(jnp.int32)), num_visits


@functools.partial(jax.jit, static_argnames=("tiling", "interpret"))
def moe_gmm(lhs: jax.Array, rhs: jax.Array, group_sizes: jax.Array, *,
            tiling: tuple[int, int, int] = (128, 1024, 1024),
            interpret: bool = False) -> jax.Array:
    """lhs (m, k), rhs (G, k, n), group_sizes (G,) int32 -> (m, n) in
    lhs's dtype, accumulated in float32. ``m`` a multiple of the row tile;
    ``k`` and ``n`` multiples of their tiles or smaller than them."""
    m, k = lhs.shape
    G, _, n = rhs.shape
    tm, tk, tn = tiling
    tk, tn = min(tk, k), min(tn, n)
    if m % tm or k % tk or n % tn:
        raise ValueError(f"(m, k, n) = {(m, k, n)} is not tiled by "
                         f"{(tm, tk, tn)}")
    tiles_k, tiles_n = k // tk, n // tn
    meta, num_visits = group_metadata(group_sizes.astype(jnp.int32), m, tm)

    def kernel(meta, lhs, rhs, out, acc):
        offsets, group_ids, m_tile_ids = meta
        visit, k_i = pl.program_id(1), pl.program_id(2)

        @pl.when(k_i == 0)
        def _zero():
            acc[...] = jnp.zeros_like(acc)

        acc[...] += lax.dot_general(
            lhs[...], rhs[...], (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)

        @pl.when(k_i == tiles_k - 1)
        def _store():
            g = group_ids[visit]
            row = (lax.broadcasted_iota(jnp.int32, (tm, tn), 0)
                   + m_tile_ids[visit] * tm)
            mine = (row >= offsets[g]) & (row < offsets[g + 1])
            out[...] = jnp.where(mine, acc[...],
                                 out[...].astype(jnp.float32)).astype(out.dtype)

    def lhs_index(n_i, visit, k_i, meta):
        return meta[2][visit], k_i

    def rhs_index(n_i, visit, k_i, meta):
        return meta[1][visit], k_i, n_i

    def out_index(n_i, visit, k_i, meta):
        return meta[2][visit], n_i

    bytes_accessed = (lhs.size * lhs.dtype.itemsize * tiles_n
                      + k * n * rhs.dtype.itemsize * G
                      + m * n * lhs.dtype.itemsize)
    call = pl.pallas_call(
        kernel,
        out_shape=jax.ShapeDtypeStruct((m, n), lhs.dtype),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1,
            in_specs=[pl.BlockSpec((tm, tk), lhs_index),
                      pl.BlockSpec((None, tk, tn), rhs_index)],
            out_specs=pl.BlockSpec((tm, tn), out_index),
            grid=(tiles_n, num_visits, tiles_k),
            scratch_shapes=[pltpu.VMEM((tm, tn), jnp.float32)],
        ),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary", "arbitrary")),
        cost_estimate=pl.CostEstimate(
            flops=2 * m * k * n, bytes_accessed=bytes_accessed,
            transcendentals=0),
        interpret=interpret,
        name=NAME,
    )
    return call(meta, lhs, rhs)
