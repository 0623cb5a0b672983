"""Each family's plain reference agrees with the program's
``models.forward`` at a small size on the CPU, on the benchmark's weights."""

import json
import sys
from pathlib import Path

import numpy as np
import pytest

ROOT = Path(__file__).resolve().parents[2]
sys.path.insert(0, str(ROOT))

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402

from bench.families import dense, ssm  # noqa: E402
from bench.harness import check, quant, weights  # noqa: E402

DATA = Path(__file__).resolve().parent / "data"
CASES = [("tiny-ssm", ssm), ("tiny-dense", dense)]


def _setup(name, fam):
    from repro.configs import get_config

    c = json.loads((DATA / f"{name}.json").read_text())
    prog = get_config(c["program_arch"]).replace(**fam.program_overrides(c))
    return c, fam.layout(c), prog


@pytest.mark.parametrize("name,fam", CASES)
def test_reference_matches_models_forward(name, fam):
    from repro import models

    c, layout, prog = _setup(name, fam)
    params = weights.generate(layout, c["weight_seed"])
    T = 32
    tokens = np.random.default_rng(0).integers(0, 256, (2, T), dtype=np.int32)
    want = np.asarray(models.forward(prog, params, {"tokens": jnp.asarray(tokens)})[0])
    ref = check.Reference(fam, c, layout, c["weight_seed"], batch=2, length=T,
                          n_pos=T, vocab=256)
    idx = np.broadcast_to(np.arange(T, dtype=np.int32), (2, T))
    got = np.asarray(ref.logits(jnp.asarray(tokens), jnp.asarray(idx), "f32"))
    err = np.max(np.abs(got - want)) / np.max(np.abs(want))
    assert err < 1e-4, err
    # the control is a different computation: far off, but not wild
    low = np.asarray(ref.logits(jnp.asarray(tokens), jnp.asarray(idx), "fp8"))
    err_low = np.max(np.abs(low - want)) / np.max(np.abs(want))
    assert 30 * err < err_low < 1.0, (err, err_low)


@pytest.mark.parametrize("name,fam", CASES)
def test_one_layer_drawn_alone_equals_its_slice(name, fam):
    c, layout, _ = _setup(name, fam)
    whole = weights.generate(layout, c["weight_seed"])
    one = weights.layer_fn(layout, c["weight_seed"])(1)
    for n, s in layout.items():
        if s[3]:
            np.testing.assert_array_equal(
                np.asarray(one[n.split("/", 1)[1]]),
                np.asarray(whole[n][1]).astype(np.float32))
    assert weights.leaves_differing(whole, layout, c["weight_seed"]) == []
    first = next(iter(layout))
    bad = dict(whole, **{first: whole[first].at[0].add(1)})
    assert weights.leaves_differing(bad, layout, c["weight_seed"]) == [first]


def test_fp8_rounds_to_three_mantissa_bits():
    x = jnp.linspace(-3.0, 3.0, 1001, dtype=jnp.float32)
    rel = jnp.abs(quant.to_fp8(x) - x) / jnp.maximum(jnp.abs(x), 1e-1)
    assert 0.01 < float(rel.max()) <= 2.0**-4 + 1e-6


def test_gumbel_noise_is_the_serving_loops():
    """The loop's sampled token is argmax(logits / T + gumbel(key)); the
    check's noise reproduces it from the seed and the request id."""
    logits = jax.random.normal(jax.random.PRNGKey(0), (256,)) * 3
    rid, seed, pos, temp = 12, 2**31 - 5, 3, 0.7
    key = jax.random.fold_in(check.request_key(seed, rid), pos)
    want = jax.random.categorical(key, logits / temp)
    noise = check.gumbel(seed, [rid], pos + 1, 256, "float32")[0, pos]
    got = check.choose(logits[None], noise[None], temperature=temp, top_k=0)
    assert int(got[0]) == int(want)
