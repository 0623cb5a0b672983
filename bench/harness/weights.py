"""Seeded weights, drawn on the device by the benchmark, never by the
program.

Every leaf has its own key, folded from the configuration's weight seed and
the CRC-32 of its name, and every layer of a stacked leaf folds in its
index. So one layer can be drawn alone with the same values it has in the
whole stack: the reference draws its weights layer by layer, in float32,
from the same values that were published in the served dtype.

The rules keep every part of each block in play: norm scales near 1 and
biases near 0 but not equal to them, matrices at 1/sqrt(fan-in), and the
state-space leaves as the Mamba-2 initialisation draws them.
"""

from __future__ import annotations

import functools
import zlib

import jax
import jax.numpy as jnp
import numpy as np


def _leaf_key(seed: int, name: str):
    return jax.random.fold_in(
        jax.random.PRNGKey(seed), zlib.crc32(name.encode()) & 0x7FFFFFFF
    )


def _draw(key, shape, rule: str):
    """One layer's values, in float32."""
    normal = lambda std: jax.random.normal(key, shape, jnp.float32) * std
    uniform = lambda lo, hi: jax.random.uniform(key, shape, jnp.float32, lo, hi)
    if rule.startswith("normal:"):
        return normal(float(rule.split(":")[1]))
    if rule == "fan_in":
        return normal(shape[-2] ** -0.5)
    if rule == "fan_in_k":                # depthwise conv: fan-in is the width
        return normal(shape[0] ** -0.5)
    if rule == "scale":
        return 1.0 + normal(0.1)
    if rule == "bias":
        return normal(0.02)
    if rule == "a_log":
        return jnp.log(uniform(1.0, 16.0))
    if rule == "dt_bias":                 # softplus^-1 of dt, log-uniform
        dt = jnp.exp(uniform(np.log(1e-3), np.log(1e-1)))
        return dt + jnp.log(-jnp.expm1(-dt))
    if rule == "skip_d":
        return uniform(0.5, 1.5)
    raise ValueError(f"unknown weight rule {rule!r}")


def rounded(x, dtype: str):
    """``x`` rounded to ``dtype``'s precision, still in float32. An
    explicit rounding: XLA may drop a float32 -> bfloat16 -> float32 pair
    of converts (it allows excess precision), which would leave the values
    unrounded."""
    f = jnp.finfo(jnp.dtype(dtype))
    return jax.lax.reduce_precision(x, exponent_bits=f.nexp,
                                    mantissa_bits=f.nmant)


def draw_leaf_f32(seed: int, name: str, spec) -> jax.Array:
    """The whole leaf's values in its served dtype, held in float32."""
    shape, dtype, rule, stacked = spec
    key = _leaf_key(seed, name)
    if stacked:
        per = lambda l: _draw(jax.random.fold_in(key, l), shape[1:], rule)
        vals = jax.vmap(per)(jnp.arange(shape[0]))
    else:
        vals = _draw(key, shape, rule)
    return rounded(vals, dtype)


def draw_leaf(seed: int, name: str, spec) -> jax.Array:
    """The whole leaf, in its served dtype."""
    return draw_leaf_f32(seed, name, spec).astype(jnp.dtype(spec[1]))


def generate(layout: dict, seed: int) -> dict:
    """Every leaf, on the device, in one jitted call."""
    fn = jax.jit(lambda: {n: draw_leaf(seed, n, s) for n, s in layout.items()})
    return fn()


@functools.lru_cache(maxsize=None)
def _leaf_differs_fn(seed: int, name: str, spec):
    return jax.jit(lambda a: jnp.any(
        jnp.asarray(a, jnp.float32) != draw_leaf_f32(seed, name, spec)))


def leaves_differing(params: dict, layout: dict, seed: int) -> list[str]:
    """Names of the leaves whose device values are not, bit for bit, the
    values drawn from ``seed``; a leaf the params lack, or one of another
    shape or dtype, differs too. One leaf at a time, to keep the peak low."""
    bad = []
    for name, spec in layout.items():
        a = params.get(name)
        if (a is None or tuple(a.shape) != tuple(spec[0])
                or a.dtype != jnp.dtype(spec[1])):
            bad.append(name)
        elif bool(_leaf_differs_fn(seed, name, _hashable(spec))(a)):
            bad.append(name)
    return bad


def _hashable(spec):
    shape, dtype, rule, stacked = spec
    return (tuple(shape), dtype, rule, stacked)


def layer_fn(layout: dict, seed: int):
    """A jitted ``l -> {name: layer l of that leaf}`` over the stacked
    leaves, in float32, with the values of the served dtype. Names lose
    their stack's prefix: ``blocks/norm/scale`` is ``norm/scale``."""
    stacked = {n: s for n, s in layout.items() if s[3]}

    def one(l):
        return {
            n.split("/", 1)[1]: rounded(_draw(
                jax.random.fold_in(_leaf_key(seed, n), l), s[0][1:], s[2]
            ), s[1])
            for n, s in stacked.items()
        }

    return jax.jit(one)


def globals_f32(layout: dict, seed: int) -> dict:
    """The leaves that are not stacked by layer, in float32."""
    return jax.jit(lambda: {
        n: draw_leaf_f32(seed, n, s) for n, s in layout.items() if not s[3]
    })()
