"""Decode attention over one layer of the stacked slot pool: the Pallas
kernel on a TPU, the XLA reference (on that layer's slice) on any other
platform, chosen where the program is lowered."""

from __future__ import annotations

import jax

from .decode_attention import block_len, decode_attention_kernel
from .ref import decode_attention_ref


def block_of(k_all: jax.Array) -> int:
    """Positions per block the kernel reads for the pool ``k_all``."""
    _, _, seq_len, kv, hd = k_all.shape
    return block_len(seq_len, kv * hd, k_all.dtype.itemsize)


def _tpu(q, k_all, v_all, layer, lengths):
    out = decode_attention_kernel(q[:, 0], k_all, v_all, layer, lengths,
                                  block=block_of(k_all))
    return out[:, None]


def _xla(q, k_all, v_all, layer, lengths):
    cache = lambda a: jax.lax.dynamic_index_in_dim(a, layer, 0, keepdims=False)
    return decode_attention_ref(q, cache(k_all), cache(v_all), lengths)


def decode_attention(q: jax.Array, k_all: jax.Array, v_all: jax.Array,
                     layer: jax.Array, lengths: jax.Array) -> jax.Array:
    """q (B, 1, H, hd); k_all, v_all (L, B, S, KV, hd); layer () int32;
    lengths (B,) int32, the positions each row reads (0 for a free row) ->
    (B, 1, H, hd) in q's dtype, zeros for a row of length 0."""
    return jax.lax.platform_dependent(
        q, k_all, v_all, layer, lengths, tpu=_tpu, default=_xla)

