"""Mixture-of-Experts block: dropless routing over every expert, computed
for the experts this chip holds.

The router scores all ``E`` experts and each token keeps its top ``k``
(renormalised where the config asks). Under expert parallelism a chip
holds ``E_h`` consecutive experts from ``first``; the (token, expert)
pairs that land on them are sorted by expert, and a grouped matmul
(``kernels/moe_gmm``) runs each held expert's SwiGLU over exactly its
rows: no capacity, nothing dropped, no work for pairs routed to other
chips or for experts no token chose. The results are combined by the
routing weights. Pairs routed elsewhere add nothing here: that partial
output is what goes on to the residual (the exchange that would bring the
other chips' parts is not part of this layer).

Returns the output, the Switch load-balance auxiliary loss over all ``E``
experts, and the layer's counters (``COUNTERS``).
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

from repro.kernels.moe_gmm import gmm

#: The expert layer's counters, in the order of its ``counts`` array: the
#: (token, expert) pairs routed to held experts, and the held experts that
#: at least one pair chose.
COUNTERS = ("moe_rows", "moe_experts_hit")


def no_counts() -> jax.Array:
    return jnp.zeros((len(COUNTERS),), jnp.int32)


def moe_block(
    x: jax.Array,                 # (B, S, d)
    router_w: jax.Array,          # (d, E)
    w_gate: jax.Array,            # (E_h, d, ff)
    w_up: jax.Array,              # (E_h, d, ff)
    w_down: jax.Array,            # (E_h, ff, d)
    *,
    k: int,
    first: int = 0,
    norm_topk: bool = True,
    rows: jax.Array | None = None,   # (B,) bool: rows that route; None: all
) -> tuple[jax.Array, jax.Array, jax.Array]:
    B, S, d = x.shape
    E, E_h = router_w.shape[-1], w_gate.shape[0]
    T, P = B * S, B * S * k
    with jax.named_scope("moe"):
        xt = x.reshape(T, d)
        logits = (xt @ router_w.astype(x.dtype)).astype(jnp.float32)
        probs = jax.nn.softmax(logits, axis=-1)                   # (T, E)
        top_p, top_e = jax.lax.top_k(probs, k)                    # (T, k)
        if norm_topk:
            top_p = top_p / jnp.maximum(top_p.sum(-1, keepdims=True), 1e-9)
        local = top_e - first
        held = (local >= 0) & (local < E_h)
        if rows is not None:
            held &= jnp.repeat(rows, S)[:, None]
        # pairs sorted by held expert; every other pair after them
        key = jnp.where(held, local, E_h).reshape(P)
        order = jnp.argsort(key, stable=True)
        group_sizes = jnp.bincount(key, length=E_h + 1)[:E_h].astype(jnp.int32)
        xs = jnp.take(xt, order // k, axis=0)                    # (P, d)
        h = (jax.nn.silu(gmm(xs, w_gate.astype(x.dtype), group_sizes))
             * gmm(xs, w_up.astype(x.dtype), group_sizes))
        ys = gmm(h, w_down.astype(x.dtype), group_sizes)         # (P, d)
        n_held = group_sizes.sum()
        ys = jnp.where((jnp.arange(P) < n_held)[:, None], ys, 0)
        back = jnp.zeros((P,), jnp.int32).at[order].set(
            jnp.arange(P, dtype=jnp.int32))
        y = jnp.take(ys, back, axis=0).reshape(T, k, d)
        w = jnp.where(held, top_p, 0.0)
        out = jnp.einsum("tkd,tk->td", y.astype(jnp.float32), w)
        out = out.astype(x.dtype).reshape(B, S, d)
        counts = jnp.stack([n_held, (group_sizes > 0).sum()]).astype(jnp.int32)

    # ---- load-balance aux loss (Switch): E * sum_e f_e * P_e
    me = probs.mean(axis=0)                                       # (E,)
    ce = jax.nn.one_hot(top_e[:, 0], E, dtype=jnp.float32).mean(axis=0)
    aux = E * jnp.sum(me * ce)
    return out, aux, counts


def shared_expert(
    x: jax.Array,
    w_gate: jax.Array,      # (d, n_shared*ff)
    w_up: jax.Array,
    w_down: jax.Array,      # (n_shared*ff, d)
    gate_w: jax.Array,      # (d, 1) — sigmoid token gate (qwen2-moe)
) -> jax.Array:
    h = jax.nn.silu(x @ w_gate.astype(x.dtype)) * (x @ w_up.astype(x.dtype))
    y = h @ w_down.astype(x.dtype)
    g = jax.nn.sigmoid((x @ gate_w.astype(x.dtype)).astype(jnp.float32))
    return y * g.astype(x.dtype)
