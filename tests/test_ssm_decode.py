"""The SSM-state kernel in interpret mode against the XLA reference, on one
layer of a stacked slot pool of two layers, at the state shapes of the SSM
models: mamba2-370m's 32 heads of 64 x 128 in one group, and zamba2-7b's
state width of 64 (at fewer heads, and in two groups, so that a chunk of
heads reads its own group's B and C). Live rows, the first and the last
among them, are stepped and read; a free row's state, and every state of
the other layer, come out bit for bit as they went in, and a free row
reads zeros."""

from __future__ import annotations

import jax.numpy as jnp
import numpy as np
import pytest

from repro.kernels.ssm_decode import (
    chunk_heads,
    ssm_decode_kernel,
    ssm_decode_ref,
)

LAYERS = 2
LIVE = [True, False, True, True, False, False, True]   # first and last live


@pytest.mark.parametrize("heads,p,n,groups", [(32, 64, 128, 1), (8, 64, 64, 2)],
                         ids=["mamba2-370m", "zamba2-7b-state"])
@pytest.mark.parametrize("layer", [0, 1])
def test_kernel_matches_the_reference(heads, p, n, groups, layer):
    rows = len(LIVE)
    rng = np.random.default_rng(heads + layer)
    f = lambda *s: jnp.asarray(rng.standard_normal(s), jnp.float32)
    state = f(LAYERS, rows, heads, p, n)
    live = jnp.asarray(LIVE)
    da = jnp.asarray(rng.uniform(0.5, 1.0, (rows, heads)), jnp.float32)
    xdt, B, C = f(rows, heads, p), f(rows, groups, n), f(rows, groups, n)

    y, got = ssm_decode_kernel(state, jnp.int32(layer), live, da, xdt, B, C,
                               interpret=True)
    want_y, want = ssm_decode_ref(state[layer], live, da, xdt, B, C)

    y, got, state = np.asarray(y), np.asarray(got), np.asarray(state)
    free = ~np.asarray(LIVE)
    assert (y[free] == 0).all()
    np.testing.assert_allclose(y, np.asarray(want_y), rtol=1e-5, atol=1e-4)
    np.testing.assert_allclose(got[layer], np.asarray(want), rtol=1e-6,
                               atol=1e-6)
    assert (got[layer][free] == state[layer][free]).all()
    assert (got[1 - layer] == state[1 - layer]).all()


def test_a_pool_with_no_live_row_is_left_as_it_was():
    rows, heads, p, n = 3, 4, 64, 128
    rng = np.random.default_rng(0)
    state = jnp.asarray(rng.standard_normal((LAYERS, rows, heads, p, n)),
                        jnp.float32)
    ones = lambda *s: jnp.ones(s, jnp.float32)
    y, got = ssm_decode_kernel(state, jnp.int32(1), jnp.zeros(rows, bool),
                               ones(rows, heads), ones(rows, heads, p),
                               ones(rows, 1, n), ones(rows, 1, n),
                               interpret=True)
    assert (np.asarray(y) == 0).all()
    assert (np.asarray(got) == np.asarray(state)).all()


@pytest.mark.parametrize("per_group,p,want", [
    (32, 64, 2),      # mamba2-370m: two heads of 64 rows fill 128
    (112, 64, 2),     # zamba2-7b
    (8, 16, 8),       # the small test configurations
    (3, 64, 1),       # no divisor of 3 but 1 fits
    (4, 256, 1),      # a head wider than 128 rows goes alone
])
def test_chunks_are_whole_heads_of_one_group(per_group, p, want):
    assert chunk_heads(per_group, p) == want
