"""Trace-time runtime switches.

``unroll_scans()`` makes every layer stack trace as straight-line code
instead of ``lax.scan``. Needed because XLA's HloCostAnalysis counts a while
loop's body ONCE (trip counts are opaque to it) — so the dry-run's cost
probes lower small-L configs unrolled and extrapolate affinely in layer-type
counts (launch/dryrun.py). Deployed programs keep the scans (small HLO,
fast compile).
"""

from __future__ import annotations

import contextlib
from contextvars import ContextVar

_UNROLL: ContextVar[bool] = ContextVar("repro_unroll_scans", default=False)


def scans_unrolled() -> bool:
    return _UNROLL.get()


@contextlib.contextmanager
def unroll_scans(on: bool = True):
    token = _UNROLL.set(on)
    try:
        yield
    finally:
        _UNROLL.reset(token)


def layer_loop(body, carry, xs: dict, first: int = 0):
    """``carry, y = body(carry, xs_l, i)`` for each layer ``i`` from
    ``first`` on, ``xs_l`` being that layer's slice of the stacked ``xs``;
    returns the last carry and the ``y``s stacked. A ``lax.scan`` carrying
    ``carry`` (so a cache in it is updated in place), straight-line code
    under ``unroll_scans()``."""
    import jax
    import jax.numpy as jnp

    n = next(iter(xs.values())).shape[0]
    if scans_unrolled():
        ys = []
        for j in range(n):
            carry, y = body(carry, {k: a[j] for k, a in xs.items()}, first + j)
            ys.append(y)
        return carry, jax.tree_util.tree_map(lambda *a: jnp.stack(a), *ys)

    def step(c, xs_j):
        xs_l, i = xs_j
        return body(c, xs_l, i)

    return jax.lax.scan(step, carry, (xs, first + jnp.arange(n)))


def remat_wrap(fn, cfg):
    """Apply the config's remat policy to a layer body."""
    import jax

    pol = getattr(cfg, "remat_policy", "nothing")
    if pol == "none":
        return fn
    if pol == "dots":
        return jax.checkpoint(fn, policy=jax.checkpoint_policies.dots_saveable)
    return jax.checkpoint(fn, policy=jax.checkpoint_policies.nothing_saveable)
