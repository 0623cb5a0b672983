"""The SSM decode state of one layer of the stacked slot pool: the Pallas
kernel on a TPU, the XLA reference (on that layer's slice) on any other
platform, chosen where the program is lowered.

The kernel copies a row's state of a layer where it lies, which Mosaic can
do only where the state's last axis, N, fills whole vregs (a multiple of
128 lanes): a TPU with a narrower state (zamba2-7b's N = 64, which XLA lays
out with the heads minor) steps it through the reference too."""

from __future__ import annotations

import jax

from .ref import ssm_decode_ref
from .ssm_decode import LANES, ssm_decode_kernel


def _tpu(state_all, layer, live, da, xdt, B, C):
    if state_all.shape[-1] % LANES:
        return _xla(state_all, layer, live, da, xdt, B, C)
    return ssm_decode_kernel(state_all, layer, live, da, xdt, B, C)


def _xla(state_all, layer, live, da, xdt, B, C):
    state = jax.lax.dynamic_index_in_dim(state_all, layer, 0, keepdims=False)
    y, state = ssm_decode_ref(state, live, da, xdt, B, C)
    return y, jax.lax.dynamic_update_index_in_dim(state_all, state, layer, 0)


def ssm_decode(state_all: jax.Array, layer: jax.Array, live: jax.Array,
               da: jax.Array, xdt: jax.Array, B: jax.Array, C: jax.Array):
    """state_all (L, B, H, P, N) f32; layer () int32; live (B,) bool; the
    token's step da (B, H), xdt (B, H, P), B and C (B, G, N), f32 ->
    (y (B, H, P) f32, zeros for a free row; state_all with layer
    ``layer`` of each live row stepped and every other state as it was)."""
    return jax.lax.platform_dependent(
        state_all, layer, live, da, xdt, B, C, tpu=_tpu, default=_xla)
