"""Decode attention over the slot pool, as a Pallas TPU kernel.

``decode_attention_kernel(q, k_all, v_all, layer, lengths, block=...)``
attends one
query token per row to that row's keys and values in layer ``layer`` of the
stacked pool ``k_all`` / ``v_all`` ``(L, rows, S, KV, hd)``, positions
``0 .. lengths[row] - 1`` only. The pool stays in HBM, where the decode step
keeps it and in the layout it keeps it in: the kernel copies each row's
blocks of ``block`` positions into VMEM itself, double-buffered, the next
block (of the same row or of the next row that has one) in flight while the
current one is computed. Nothing reads past a row's length, and a row of
length 0 (a free slot) costs nothing and comes out as zeros.

All query heads of a row are computed at once against the unexpanded cache,
a block being ``(block, KV * hd)``: the row's queries are laid out
block-diagonally over that width (query head ``h`` in the columns of its KV
head), so that one matmul gives every head's scores over a block, and one
more every head's weighted values against every KV head, of which each head
keeps its own. Grouped-query
attention is ``G = H / KV`` heads per KV head; multi-head attention is
``G = 1``. Scores, the online softmax and the weighted sum are in float32;
the cache and the matmuls' inputs stay in the cache's dtype.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax import lax
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

NAME = "decode_attention"   # the kernel's name in the chip's trace
BLOCK_BYTES = 512 * 1024    # one block of keys: about half a megabyte
NEG_INF = -2.0**30          # large but finite: a masked score never NaNs


def block_len(seq_len: int, width: int, itemsize: int) -> int:
    """Positions per block for a pool of ``seq_len`` positions of ``width``
    elements: the largest multiple of 128 that divides ``seq_len`` and
    keeps one block of keys within ``BLOCK_BYTES`` (the smallest such
    multiple where none fits), or the whole row where none divides it."""
    tiles = [n for n in range(128, seq_len + 1, 128) if seq_len % n == 0]
    fits = [n for n in tiles if n * width * itemsize <= BLOCK_BYTES]
    return max(fits) if fits else min(tiles, default=seq_len)


def _body(layer_ref, lengths_ref, q_ref, k_hbm, v_hbm, o_ref,
          k_buf, v_buf, sem, m_ref, l_ref, acc_ref, *, block: int):
    rows, heads, hd = q_ref.shape
    kv = k_buf.shape[2]
    width = kv * hd
    groups = heads // kv
    layer = layer_ref[0]
    scale = hd**-0.5

    def n_blocks(r):
        return (lengths_ref[r] + block - 1) // block

    def next_row(r):
        """The first row after ``r`` that has a position, or ``rows``."""
        return lax.while_loop(
            lambda n: (n < rows) & (lengths_ref[jnp.minimum(n, rows - 1)] == 0),
            lambda n: n + 1, r + 1)

    def copies(r, i, slot):
        at = pl.ds(pl.multiple_of(i * block, block), block)
        return (pltpu.make_async_copy(k_hbm.at[layer, r, at], k_buf.at[slot],
                                      sem.at[0, slot]),
                pltpu.make_async_copy(v_hbm.at[layer, r, at], v_buf.at[slot],
                                      sem.at[1, slot]))

    def start(r, i, slot):
        for c in copies(r, i, slot):
            c.start()

    # the KV head of each query head, and of each column of the cache
    head_kv = lax.broadcasted_iota(jnp.int32, (heads, width), 0) // groups
    col_kv = lax.broadcasted_iota(jnp.int32, (heads, width), 1) // hd

    o_ref[...] = jnp.zeros_like(o_ref)
    first = next_row(-1)

    @pl.when(first < rows)
    def _():
        start(first, 0, 0)

    def row(carry):
        r, slot = carry
        n, after = n_blocks(r), next_row(r)
        q = q_ref[r].astype(jnp.float32)                    # (heads, hd)
        q_wide = jnp.concatenate([q] * kv, axis=1)          # (heads, width)
        q_wide = jnp.where(head_kv == col_kv, q_wide, 0.0).astype(k_buf.dtype)
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)
        acc_ref[...] = jnp.zeros_like(acc_ref)

        def step(i, slot):
            @pl.when(i + 1 < n)
            def _():
                start(r, i + 1, 1 - slot)

            @pl.when((i + 1 == n) & (after < rows))
            def _():
                start(after, 0, 1 - slot)

            for c in copies(r, i, slot):
                c.wait()
            k = k_buf[slot].reshape(block, width)
            s = lax.dot_general(q_wide, k, (((1,), (1,)), ((), ())),
                                preferred_element_type=jnp.float32) * scale
            pos = i * block + lax.broadcasted_iota(jnp.int32, s.shape, 1)
            s = jnp.where(pos < lengths_ref[r], s, NEG_INF)  # (heads, block)
            m_prev = m_ref[...]
            m_new = jnp.maximum(m_prev, s.max(axis=1, keepdims=True))
            p = jnp.exp(s - m_new)
            alpha = jnp.exp(m_prev - m_new)
            m_ref[...] = m_new
            l_ref[...] = alpha * l_ref[...] + p.sum(axis=1, keepdims=True)
            v = v_buf[slot].reshape(block, width)
            acc_ref[...] = alpha * acc_ref[...] + lax.dot_general(
                p.astype(v.dtype), v, (((1,), (0,)), ((), ())),
                preferred_element_type=jnp.float32)
            return 1 - slot

        slot = lax.fori_loop(0, n, step, slot)
        # each query head keeps the columns of its own KV head
        acc = jnp.where(head_kv == col_kv, acc_ref[...], 0.0)
        out = sum(acc[:, j * hd:(j + 1) * hd] for j in range(kv))
        o_ref[r] = (out / l_ref[...]).astype(o_ref.dtype)
        return after, slot

    lax.while_loop(lambda c: c[0] < rows, row, (first, jnp.int32(0)))


@functools.partial(jax.jit, static_argnames=("block", "interpret"))
def decode_attention_kernel(q: jax.Array, k_all: jax.Array, v_all: jax.Array,
                            layer: jax.Array, lengths: jax.Array, *,
                            block: int, interpret: bool = False) -> jax.Array:
    """q (rows, H, hd); k_all, v_all (L, rows, S, KV, hd); layer () int32;
    lengths (rows,) int32, each in 0 .. S -> (rows, H, hd) in q's dtype.
    ``block`` divides S."""
    rows, heads, hd = q.shape
    _, _, seq_len, kv, _ = k_all.shape
    if seq_len % block:
        raise ValueError(f"block {block} does not divide {seq_len} positions")
    whole = lambda *_: (0, 0, 0)
    kv_bytes = 2 * k_all.dtype.itemsize * rows * seq_len * kv * hd
    call = pl.pallas_call(
        functools.partial(_body, block=block),
        out_shape=jax.ShapeDtypeStruct(q.shape, q.dtype),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=2,
            grid=(1,),
            in_specs=[pl.BlockSpec(q.shape, whole),
                      pl.BlockSpec(memory_space=pl.ANY),
                      pl.BlockSpec(memory_space=pl.ANY)],
            out_specs=pl.BlockSpec(q.shape, whole),
            scratch_shapes=[
                pltpu.VMEM((2, block, kv, hd), k_all.dtype),
                pltpu.VMEM((2, block, kv, hd), v_all.dtype),
                pltpu.SemaphoreType.DMA((2, 2)),
                pltpu.VMEM((heads, 1), jnp.float32),
                pltpu.VMEM((heads, 1), jnp.float32),
                pltpu.VMEM((heads, kv * hd), jnp.float32),
            ],
        ),
        cost_estimate=pl.CostEstimate(
            flops=4 * rows * heads * hd * seq_len,
            bytes_accessed=kv_bytes + 2 * q.size * q.dtype.itemsize,
            transcendentals=rows * heads * seq_len),
        interpret=interpret,
        name=NAME,
    )
    return call(jnp.reshape(layer, (1,)).astype(jnp.int32),
                lengths.astype(jnp.int32), q, k_all, v_all)
