"""The decode-attention kernel in interpret mode against the XLA reference,
on one layer of a stacked slot pool, at the heads and head width of the
benchmark's attention models: grouped-query attention of 24 query heads
over 2 KV heads (starcoder2-3b) and multi-head attention of 16 over 16
(olmoe-1b-7b). Rows are read up to their lengths, in blocks the kernel
sizes from the pool's shape, and a free row (length 0) comes out as zeros."""

from __future__ import annotations

import jax.numpy as jnp
import numpy as np
import pytest

from repro.kernels.decode_attention import (
    block_len,
    decode_attention_kernel,
    decode_attention_ref,
)

HD = 128
LAYERS = 2


@pytest.mark.parametrize("heads,kv,seq_len", [(24, 2, 2048), (16, 16, 256)],
                         ids=["gqa-24-2", "mha-16-16"])
def test_kernel_matches_the_reference(heads, kv, seq_len):
    block = block_len(seq_len, kv * HD, 2)
    assert seq_len // block == 2           # a row can end in either block
    # each row's position being written (None: a free slot): the first
    # position, the last of a block, the first of the next, the last of
    # the cache, and past it (the ring has wrapped: every position valid)
    pos = [None, 0, block - 1, block, None, seq_len - 1, seq_len + 7]
    lengths = np.array([0 if p is None else min(p + 1, seq_len) for p in pos],
                       np.int32)
    assert lengths.tolist() == [0, 1, block, block + 1, 0, seq_len, seq_len]
    rows = len(pos)
    rng = np.random.default_rng(heads)
    pool = lambda: jnp.asarray(
        rng.standard_normal((LAYERS, rows, seq_len, kv, HD)), jnp.bfloat16)
    k_all, v_all = pool(), pool()
    q = jnp.asarray(rng.standard_normal((rows, heads, HD)), jnp.bfloat16)

    got = np.asarray(decode_attention_kernel(
        q, k_all, v_all, jnp.int32(1), jnp.asarray(lengths), block=block,
        interpret=True), np.float32)
    want = np.asarray(decode_attention_ref(
        q[:, None], k_all[1], v_all[1], jnp.asarray(lengths))[:, 0],
        np.float32)

    free = lengths == 0
    assert (got[free] == 0).all() and (want[free] == 0).all()
    np.testing.assert_allclose(got[~free], want[~free], rtol=2**-6,
                               atol=2**-6)
