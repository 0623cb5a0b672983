"""The grouped matmul the expert layer calls: the Pallas kernel on a TPU,
``lax.ragged_dot`` on any other platform, chosen where the program is
lowered. The kernel's gradient is ``lax.ragged_dot``'s, so the training
path differentiates through either."""

from __future__ import annotations

import jax
import jax.numpy as jnp

from .moe_gmm import moe_gmm
from .ref import moe_gmm_ref

TM = 128                  # rows per tile: one MXU pass on a v5e


def _ragged(lhs, rhs, group_sizes):
    return jax.lax.ragged_dot(lhs, rhs, group_sizes)


@jax.custom_vjp
def _kernel(lhs, rhs, group_sizes):
    m = lhs.shape[0]
    pad = -m % TM
    if pad:
        lhs = jnp.pad(lhs, ((0, pad), (0, 0)))
    return moe_gmm(lhs, rhs, group_sizes, tiling=(TM, 1024, 1024))[:m]


def _kernel_fwd(lhs, rhs, group_sizes):
    return _kernel(lhs, rhs, group_sizes), (lhs, rhs, group_sizes)


def _kernel_bwd(res, g):
    lhs, rhs, group_sizes = res
    _, vjp = jax.vjp(lambda a, b: _ragged(a, b, group_sizes), lhs, rhs)
    return (*vjp(g), None)


_kernel.defvjp(_kernel_fwd, _kernel_bwd)


def gmm(lhs: jax.Array, rhs: jax.Array, group_sizes: jax.Array) -> jax.Array:
    """lhs (m, k) rows sorted by group, rhs (G, k, n), group_sizes (G,)
    int32 -> (m, n) in lhs's dtype. Rows past the last group hold no
    defined value: the caller masks them."""
    return jax.lax.platform_dependent(
        lhs, rhs, group_sizes, tpu=_kernel, default=_ragged)


__all__ = ["gmm", "moe_gmm", "moe_gmm_ref"]
