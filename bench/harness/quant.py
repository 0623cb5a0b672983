"""Matmul precisions the reference runs in.

``f32`` is the reference itself: float32 operands at ``highest`` precision,
so that the TPU's matrix unit does not round them to bfloat16. ``fp8`` is
the control: each operand is scaled per tensor into float8 e4m3's range,
rounded to it and scaled back, and the product is taken as for ``f32``.
That is the step below bfloat16 that a later change might be tempted to
take, and the comparison that decides ``correct`` has to fail it.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

E4M3_MAX = 448.0


def to_fp8(a):
    scale = jnp.maximum(jnp.max(jnp.abs(a)), 1e-30) / E4M3_MAX
    return (a / scale).astype(jnp.float8_e4m3fn).astype(jnp.float32) * scale


def matmul(mode: str):
    if mode == "f32":
        return lambda a, b: jnp.matmul(
            a, b, precision=jax.lax.Precision.HIGHEST
        )
    if mode == "fp8":
        return lambda a, b: jnp.matmul(
            to_fp8(a), to_fp8(b), precision=jax.lax.Precision.HIGHEST
        )
    raise ValueError(f"unknown reference precision {mode!r}")


def einsum(mode: str, spec: str, a, b):
    if mode == "fp8":
        a, b = to_fp8(a), to_fp8(b)
    return jnp.einsum(spec, a, b, precision=jax.lax.Precision.HIGHEST)
