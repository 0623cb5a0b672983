"""Decode attention in plain XLA: one query token per row against the
row's cache, positions ``0 .. lengths[row] - 1``; a row of length 0 comes
out as zeros. GQA is a grouped einsum against the unexpanded cache.

The path off the TPU, the kernel's oracle, and the form that windowed and
cross attention use everywhere."""

from __future__ import annotations

import jax
import jax.numpy as jnp

from .decode_attention import NEG_INF


def decode_attention_ref(
    q: jax.Array,          # (B, 1, H, hd)
    k_cache: jax.Array,    # (B, S, KV, hd)
    v_cache: jax.Array,
    lengths: jax.Array,    # int32, () or (B,): positions each row reads
) -> jax.Array:
    """(B, 1, H, hd) in the cache's dtype; scores and softmax in float32.

    ``repeat_kv`` here would materialize (and, under SPMD, all-gather +
    f32-upcast) a head-expanded copy of the whole cache; the grouped form
    keeps the cache in its dtype and sharded. Under a sequence-sharded
    cache the softmax over S becomes a cross-device partial max and sum
    (flash-decode)."""
    b, s, kvh, hd = k_cache.shape
    h = q.shape[2]
    g = h // kvh
    qg = q.reshape(b, 1, kvh, g, hd)
    scores = jnp.einsum(
        "bqkgd,bskd->bkgqs", qg, k_cache,
        preferred_element_type=jnp.float32,
    ) * hd**-0.5                                         # (B,KV,G,1,S) f32
    lengths = jnp.reshape(lengths, (-1, 1, 1, 1, 1))
    scores = jnp.where(jnp.arange(s) < lengths, scores, NEG_INF)
    w = jax.nn.softmax(scores, axis=-1)
    out = jnp.einsum(
        "bkgqs,bskd->bqkgd", w.astype(v_cache.dtype), v_cache
    )
    out = jnp.where(lengths > 0, out, 0)
    return out.reshape(b, 1, h, hd)
