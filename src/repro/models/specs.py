"""Parameter specifications: the models' *symbol manifests*.

Every model declares its parameters as ``{name: ParamSpec}`` — shape, dtype,
logical sharding axes, and initializer — WITHOUT allocating anything. This
single declaration drives:

* stable linking  — the spec dict converts 1:1 into ``SymbolRef``s (the
  application's relocation instructions) and into bundle symbol tables;
* initialization  — per-name key folding makes init order-independent;
* sharding        — logical axes resolve through dist.sharding rules;
* the dry-run     — ``jax.ShapeDtypeStruct`` stand-ins, no allocation.

Names are canonical `/`-separated paths; stacked-layer params carry the
leading "layers" logical axis (bundle-side these become stacked symbols,
loadable per-slice via RelocType.SLICE).
"""

from __future__ import annotations

import hashlib
from dataclasses import dataclass
from typing import Callable, Mapping, Optional

import jax
import jax.numpy as jnp
import numpy as np

from repro.core.resolver import np_dtype


@dataclass(frozen=True)
class ParamSpec:
    shape: tuple[int, ...]
    dtype: str
    axes: tuple[Optional[str], ...]      # logical axis names, len == ndim
    init: str = "normal"                 # normal | zeros | ones | fan_in

    def __post_init__(self):
        assert len(self.axes) == len(self.shape), (self.axes, self.shape)


def _name_hash(name: str) -> int:
    return int.from_bytes(hashlib.blake2b(name.encode(), digest_size=4).digest(), "big")


def _name_key(base: jax.Array, name: str) -> jax.Array:
    return jax.random.fold_in(base, _name_hash(name))


def _init_one(key: jax.Array, spec: ParamSpec) -> jax.Array:
    dt = jnp.dtype(spec.dtype)
    if spec.init == "zeros":
        return jnp.zeros(spec.shape, dt)
    if spec.init == "ones":
        return jnp.ones(spec.shape, dt)
    if spec.init == "fan_in":
        fan_in = spec.shape[-2] if len(spec.shape) >= 2 else spec.shape[-1]
        std = fan_in ** -0.5
    else:
        std = 0.02
    return (jax.random.normal(key, spec.shape, jnp.float32) * std).astype(dt)


def init_params(
    specs: Mapping[str, ParamSpec], seed: int = 0
) -> dict[str, jax.Array]:
    """Order-independent initialization: each param's key is derived from its
    name, so adding/removing symbols never perturbs its neighbours."""
    base = jax.random.key(seed)
    return {n: _init_one(_name_key(base, n), s) for n, s in specs.items()}


def _init_one_np(rng: np.random.Generator, spec: ParamSpec) -> np.ndarray:
    dt = np_dtype(spec.dtype)
    if spec.init == "zeros":
        return np.zeros(spec.shape, dt)
    if spec.init == "ones":
        return np.ones(spec.shape, dt)
    if spec.init == "fan_in":
        fan_in = spec.shape[-2] if len(spec.shape) >= 2 else spec.shape[-1]
        std = fan_in ** -0.5
    else:
        std = 0.02
    a = rng.standard_normal(spec.shape, dtype=np.float32)
    a *= np.float32(std)
    return a.astype(dt)


def init_params_np(
    specs: Mapping[str, ParamSpec], seed: int = 0
) -> dict[str, np.ndarray]:
    """``init_params`` on the host, with no JAX backend: a publisher builds
    weights without taking the chip. Same per-name seeding (each param's
    generator is keyed by ``(seed, name)``); numpy's generator, so the
    values differ from ``init_params``."""
    return {
        n: _init_one_np(np.random.default_rng((seed, _name_hash(n))), s)
        for n, s in specs.items()
    }


def abstract_params(specs: Mapping[str, ParamSpec]) -> dict[str, jax.ShapeDtypeStruct]:
    return {
        n: jax.ShapeDtypeStruct(s.shape, jnp.dtype(s.dtype))
        for n, s in specs.items()
    }


def param_bytes(specs: Mapping[str, ParamSpec]) -> int:
    return sum(
        int(np.prod(s.shape)) * jnp.dtype(s.dtype).itemsize for s in specs.values()
    )


def param_count(specs: Mapping[str, ParamSpec]) -> int:
    return sum(int(np.prod(s.shape)) for s in specs.values())
