"""The SSM decode state in plain XLA: each live row's state one token on,
and the token's read of it; a free row's state is left as it was and its
read is zeros.

The path off the TPU and the kernel's oracle."""

from __future__ import annotations

import jax
import jax.numpy as jnp


def ssm_advance(state, da, xdt, B):
    """The SSM state one token on: ``state`` (..., H, P, N) decayed by
    ``da`` (..., H), plus the outer product of the token's ``xdt``
    (..., H, P) and ``B`` (..., G, N), which the H // G heads of a group
    share."""
    *lead, H, P, N = state.shape
    G = B.shape[-2]
    dbx = jnp.einsum(
        "...gjp,...gn->...gjpn", xdt.reshape(*lead, G, H // G, P), B
    )
    return state * da[..., None, None] + dbx.reshape(state.shape)


def ssm_decode_ref(
    state: jax.Array,      # (B, H, P, N) f32, one layer of the pool
    live: jax.Array,       # (B,) bool
    da: jax.Array,         # (B, H)
    xdt: jax.Array,        # (B, H, P)
    B: jax.Array,          # (B, G, N)
    C: jax.Array,          # (B, G, N)
) -> tuple[jax.Array, jax.Array]:
    """(y (B, H, P), the new state (B, H, P, N)), both float32."""
    H = state.shape[1]
    new = ssm_advance(state, da, xdt, B)
    Ch = jnp.repeat(C, H // C.shape[1], axis=1)                 # (B, H, N)
    y = jnp.einsum("bhpn,bhn->bhp", new, Ch)
    live = live[:, None, None]
    return (jnp.where(live, y, 0.0),
            jnp.where(live[..., None], new, state))
