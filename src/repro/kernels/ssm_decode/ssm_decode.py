"""The SSM decode state over the slot pool, as a Pallas TPU kernel.

``ssm_decode_kernel(state_all, layer, live, da, xdt, B, C)`` steps the
state of each live row of layer ``layer`` of the stacked pool ``state_all``
``(L, rows, H, P, N)`` one token on, where it lies, and returns the
token's read of the new state::

    s' = s * da + xdt (x) B        (the B of the head's group)
    y  = sum_n s' * C              (the C of the head's group)

The pool stays in HBM and is written in place (the output is the input,
aliased). The kernel copies each live row's ``(H, P, N)`` slab of the layer
into VMEM, steps it there and copies it back: the slab is read once and
written once, with three slabs in flight, so that the next row's read and
the last row's write overlap the current row's arithmetic. A free row
(``live`` 0) is neither read nor written, and its ``y`` is zeros.

A slab is stepped in chunks of whole heads of one group, ``R = heads * P``
rows of its ``(H * P, N)`` view at a time. ``xdt`` and ``da`` vary along
those rows, so the kernel takes each row's as ``(2 * chunks, R)``, turns
it into columns in VMEM, and gives back ``y`` the same way round;
``B`` and ``C`` vary along ``N``, the lanes, and are read as rows.
Everything is float32.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax import lax
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

NAME = "ssm_decode"    # the kernel's name in the chip's trace
SLABS = 3              # slabs in VMEM: one stepped, one read, one written
LANES = 128


def chunk_heads(heads_per_group: int, head_dim: int) -> int:
    """Heads per chunk: the most heads of one group, dividing it, whose
    rows fill no more than a vreg's ``LANES`` (at least one head)."""
    return max(d for d in range(1, heads_per_group + 1)
               if heads_per_group % d == 0
               and (d == 1 or d * head_dim <= LANES))


def _body(layer_ref, live_ref, xd_ref, b_ref, c_ref, state_hbm, y_ref,
          state_out, slab, ycol, sem, *, heads: int):
    rows, chunks, width = y_ref.shape          # width = heads * P
    _, H, P, N = slab.shape
    per_group = H // b_ref.shape[1]
    layer = layer_ref[0]

    def next_row(r):
        """The first live row after ``r``, or ``rows``."""
        return lax.while_loop(
            lambda n: (n < rows) & (live_ref[jnp.minimum(n, rows - 1)] == 0),
            lambda n: n + 1, r + 1)

    def read(r, i):
        return pltpu.make_async_copy(state_hbm.at[layer, r], slab.at[i],
                                     sem.at[0, i])

    def write(r, i):
        return pltpu.make_async_copy(slab.at[i], state_out.at[layer, r],
                                     sem.at[1, i])

    y_ref[...] = jnp.zeros_like(y_ref)
    first = next_row(-1)

    @pl.when(first < rows)
    def _():
        read(first, 0).start()

    def row(carry):
        r, k = carry                       # the row, and the k-th live one
        i, nxt = k % SLABS, (k + 1) % SLABS
        after = next_row(r)

        # slab ``nxt`` last held row k + 1 - SLABS: its write lands first
        @pl.when(k + 1 >= SLABS)
        def _():
            write(r, nxt).wait()

        @pl.when(after < rows)
        def _():
            read(after, nxt).start()

        cols = xd_ref[r].T                 # (width, 2 * chunks): xdt, da
        read(r, i).wait()
        for j in range(chunks):
            g = j * heads // per_group
            at = pl.ds(j * heads, heads)
            s = slab[i, at].reshape(width, N)
            s = (s * cols[:, chunks + j:chunks + j + 1]
                 + cols[:, j:j + 1] * b_ref[r, g:g + 1, :])
            slab[i, at] = s.reshape(heads, P, N)
            ycol[:, j:j + 1] = jnp.sum(s * c_ref[r, g:g + 1, :], axis=1,
                                       keepdims=True)
        y_ref[r] = ycol[...].T
        write(r, i).start()
        return after, k + 1

    _, n = lax.while_loop(lambda c: c[0] < rows, row, (first, jnp.int32(0)))
    # the last SLABS - 1 live rows' writes are still in flight
    for back in range(1, SLABS):
        @pl.when(n >= back)
        def _():
            write(0, (n - back) % SLABS).wait()


def _vmem_bytes(*shapes) -> int:
    """VMEM that float32 arrays of ``shapes`` take, tiled (8, 128)."""
    total = 0
    for *lead, sub, lane in shapes:
        n = 1
        for d in lead:
            n *= d
        total += 4 * n * -(-sub // 8) * 8 * -(-lane // LANES) * LANES
    return total


@functools.partial(jax.jit, static_argnames=("interpret",))
def ssm_decode_kernel(state_all: jax.Array, layer: jax.Array,
                      live: jax.Array, da: jax.Array, xdt: jax.Array,
                      B: jax.Array, C: jax.Array, *,
                      interpret: bool = False):
    """state_all (L, rows, H, P, N) f32; layer () int32; live (rows,) bool;
    da (rows, H), xdt (rows, H, P), B and C (rows, G, N), all f32 ->
    (y (rows, H, P) f32, state_all with each live row of ``layer``
    stepped). The state is updated in place where the caller donates it."""
    _, rows, H, P, N = state_all.shape
    G = B.shape[1]
    heads = chunk_heads(H // G, P)
    chunks, width = H // heads, heads * P
    xd = jnp.concatenate([xdt.reshape(rows, chunks, width),
                          jnp.repeat(da, P, axis=1).reshape(rows, chunks,
                                                            width)], axis=1)
    whole = lambda *_: (0, 0, 0)
    blocks = [(rows, 2 * chunks, width), (rows, G, N), (rows, G, N),
              (rows, chunks, width)]
    # the double-buffered blocks, the scratch, and a row's columns
    vmem = (2 * _vmem_bytes(*blocks)
            + _vmem_bytes((SLABS * H, P, N), (width, chunks),
                          (width, 2 * chunks)))
    slab_bytes = 4 * H * P * N
    call = pl.pallas_call(
        functools.partial(_body, heads=heads),
        out_shape=(jax.ShapeDtypeStruct((rows, chunks, width), jnp.float32),
                   jax.ShapeDtypeStruct(state_all.shape, jnp.float32)),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=2,
            grid=(1,),
            in_specs=[pl.BlockSpec(blocks[0], whole),
                      pl.BlockSpec(blocks[1], whole),
                      pl.BlockSpec(blocks[2], whole),
                      pl.BlockSpec(memory_space=pl.ANY)],
            out_specs=(pl.BlockSpec(blocks[3], whole),
                       pl.BlockSpec(memory_space=pl.ANY)),
            scratch_shapes=[
                pltpu.VMEM((SLABS, H, P, N), jnp.float32),
                pltpu.VMEM((width, chunks), jnp.float32),
                pltpu.SemaphoreType.DMA((2, SLABS)),
            ],
        ),
        input_output_aliases={5: 1},    # the pool is stepped in place
        compiler_params=pltpu.CompilerParams(
            vmem_limit_bytes=vmem + 8 * 2**20),
        cost_estimate=pl.CostEstimate(
            flops=5 * rows * H * P * N,
            bytes_accessed=rows * (2 * slab_bytes + 4 * (3 * H * P + H
                                                         + 2 * G * N)),
            transcendentals=0),
        interpret=interpret,
        name=NAME,
    )
    y, state_all = call(jnp.reshape(layer, (1,)).astype(jnp.int32),
                        live.astype(jnp.int32), xd, B, C, state_all)
    return y.reshape(rows, H, P), state_all
