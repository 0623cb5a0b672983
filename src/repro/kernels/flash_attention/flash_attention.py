"""Pallas TPU kernel: blockwise flash attention (causal, GQA, sliding window).

Grid is (batch, heads, q_blocks, kv_blocks); the kv axis is the innermost
("arbitrary") dimension so the online-softmax state (running max / sum /
accumulator) lives in VMEM scratch across kv steps. Out-of-range blocks —
above the causal diagonal or entirely left of the sliding window — skip
their compute via ``pl.when``, which is where the window's FLOP savings
actually materialize on TPU (the pure-JAX ``chunked`` path masks instead;
see DESIGN.md §4).

GQA is expressed in the k/v BlockSpec index_map (``h // q_per_kv``): no
materialized head replication.

Block shapes default to (512 q x 512 kv) x head_dim — q/k/v tiles plus the
f32 accumulator fit comfortably in ~16 MiB VMEM for head_dim <= 256 and the
MXU sees [block_q, hd] x [hd, block_k] matmuls with 128-aligned dims.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

NEG_INF = -2.0**30
LANES = 128


def _flash_kernel(
    q_ref, k_ref, v_ref, o_ref,
    m_scratch, l_scratch, acc_scratch,
    *,
    sm_scale: float,
    causal: bool,
    window: int,
    block_q: int,
    block_k: int,
    seq_q: int,
    seq_k: int,
    q_offset: int,
):
    iq = pl.program_id(2)
    ik = pl.program_id(3)
    nk = pl.num_programs(3)

    @pl.when(ik == 0)
    def _init():
        m_scratch[...] = jnp.full_like(m_scratch, NEG_INF)
        l_scratch[...] = jnp.zeros_like(l_scratch)
        acc_scratch[...] = jnp.zeros_like(acc_scratch)

    # block-level skip: entirely above the causal diagonal, or entirely
    # out of the sliding window
    q_lo = iq * block_q + q_offset              # first absolute q position
    q_hi = q_lo + block_q - 1
    k_lo = ik * block_k
    k_hi = k_lo + block_k - 1
    live = True
    if causal:
        live = jnp.logical_and(live, q_hi >= k_lo)
    if window > 0:
        live = jnp.logical_and(live, k_hi > q_lo - window)

    @pl.when(live)
    def _compute():
        q = q_ref[0, 0].astype(jnp.float32)     # (block_q, hd)
        k = k_ref[0, 0].astype(jnp.float32)     # (block_k, hd)
        v = v_ref[0, 0].astype(jnp.float32)
        s = jax.lax.dot_general(
            q, k, (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32
        ) * sm_scale                            # (block_q, block_k)

        q_pos = q_lo + jax.lax.broadcasted_iota(jnp.int32, s.shape, 0)
        k_pos = k_lo + jax.lax.broadcasted_iota(jnp.int32, s.shape, 1)
        mask = k_pos < seq_k
        if causal:
            mask &= q_pos >= k_pos
        if window > 0:
            mask &= q_pos - k_pos < window
        s = jnp.where(mask, s, NEG_INF)

        m_prev = m_scratch[...]                 # (block_q, LANES)
        l_prev = l_scratch[...]
        m_cur = jnp.max(s, axis=1, keepdims=True)          # (block_q, 1)
        m_new = jnp.maximum(m_prev, jnp.broadcast_to(m_cur, m_prev.shape))
        corr = jnp.exp(m_prev - m_new)
        p = jnp.exp(s - m_new[:, :1])                      # (block_q, block_k)
        l_new = l_prev * corr + jnp.broadcast_to(
            jnp.sum(p, axis=1, keepdims=True), l_prev.shape
        )
        acc_scratch[...] = acc_scratch[...] * corr[:, :1] + jax.lax.dot_general(
            p, v, (((1,), (0,)), ((), ())), preferred_element_type=jnp.float32
        )
        m_scratch[...] = m_new
        l_scratch[...] = l_new

    @pl.when(ik == nk - 1)
    def _finalize():
        l = l_scratch[...][:, :1]
        o_ref[0, 0] = (
            acc_scratch[...] / jnp.maximum(l, 1e-37)
        ).astype(o_ref.dtype)


@functools.partial(
    jax.jit,
    static_argnames=(
        "causal", "window", "q_offset", "block_q", "block_k", "interpret",
    ),
)
def flash_attention_bhsd(
    q: jax.Array,            # (B, H, Sq, hd)
    k: jax.Array,            # (B, KV, Sk, hd)
    v: jax.Array,            # (B, KV, Sk, hd)
    *,
    causal: bool = True,
    window: int = 0,
    q_offset: int = 0,
    block_q: int = 512,
    block_k: int = 512,
    interpret: bool = False,
) -> jax.Array:
    B, H, Sq, hd = q.shape
    KV, Sk = k.shape[1], k.shape[2]
    q_per_kv = H // KV
    block_q = min(block_q, Sq)
    block_k = min(block_k, Sk)

    pad_q = (-Sq) % block_q
    pad_k = (-Sk) % block_k
    if pad_q:
        q = jnp.pad(q, ((0, 0), (0, 0), (0, pad_q), (0, 0)))
    if pad_k:
        k = jnp.pad(k, ((0, 0), (0, 0), (0, pad_k), (0, 0)))
        v = jnp.pad(v, ((0, 0), (0, 0), (0, pad_k), (0, 0)))
    nq = (Sq + pad_q) // block_q
    nk = (Sk + pad_k) // block_k

    kernel = functools.partial(
        _flash_kernel,
        sm_scale=hd**-0.5,
        causal=causal,
        window=window,
        block_q=block_q,
        block_k=block_k,
        seq_q=Sq,
        seq_k=Sk,
        q_offset=q_offset,
    )
    out = pl.pallas_call(
        kernel,
        grid=(B, H, nq, nk),
        in_specs=[
            pl.BlockSpec(
                (1, 1, block_q, hd), lambda b, h, iq, ik: (b, h, iq, 0)
            ),
            pl.BlockSpec(
                (1, 1, block_k, hd),
                lambda b, h, iq, ik, _g=q_per_kv: (b, h // _g, ik, 0),
            ),
            pl.BlockSpec(
                (1, 1, block_k, hd),
                lambda b, h, iq, ik, _g=q_per_kv: (b, h // _g, ik, 0),
            ),
        ],
        out_specs=pl.BlockSpec(
            (1, 1, block_q, hd), lambda b, h, iq, ik: (b, h, iq, 0)
        ),
        out_shape=jax.ShapeDtypeStruct((B, H, Sq + pad_q, hd), q.dtype),
        scratch_shapes=[
            pltpu.VMEM((block_q, LANES), jnp.float32),
            pltpu.VMEM((block_q, LANES), jnp.float32),
            pltpu.VMEM((block_q, hd), jnp.float32),
        ],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "parallel", "arbitrary"),
        ),
        interpret=interpret,
    )(q, k, v)
    return out[:, :, :Sq]
