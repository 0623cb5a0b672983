"""Shared layers: norms, RoPE, attention (naive / chunked-online-softmax /
Pallas), MLPs, and the cross-entropy loss.

The ``chunked`` attention path is a pure-JAX flash-attention analogue
(lax.scan over KV chunks with a running max/sum): it bounds activation
memory exactly like the Pallas kernel, compiles on any backend (so the
512-device dry-run can use it), and its block structure mirrors
kernels/flash_attention. ``naive`` is the O(S^2)-materializing oracle used
by tests; ``pallas`` is the TPU target.
"""

from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np

NEG_INF = -2.0**30  # large-but-finite: keeps masked softmax NaN-free
# a decode cache row's ``pos`` when the row holds no token (a free slot):
# its attention reads no cache and comes out as zeros
FREE_POS = -1


# ------------------------------------------------------------------- norms
def rms_norm(x: jax.Array, scale: jax.Array, eps: float = 1e-6) -> jax.Array:
    dt = x.dtype
    x = x.astype(jnp.float32)
    x = x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps)
    return (x * scale.astype(jnp.float32)).astype(dt)


def layer_norm(
    x: jax.Array, scale: jax.Array, bias: jax.Array, eps: float = 1e-6
) -> jax.Array:
    dt = x.dtype
    x = x.astype(jnp.float32)
    mu = jnp.mean(x, axis=-1, keepdims=True)
    var = jnp.mean((x - mu) ** 2, axis=-1, keepdims=True)
    y = (x - mu) * jax.lax.rsqrt(var + eps)
    return (y * scale.astype(jnp.float32) + bias.astype(jnp.float32)).astype(dt)


# -------------------------------------------------------------------- RoPE
def rope_angles(
    positions: jax.Array, head_dim: int, theta: float
) -> tuple[jax.Array, jax.Array]:
    half = head_dim // 2
    freqs = theta ** (-jnp.arange(0, half, dtype=jnp.float32) / half)
    ang = positions[..., None].astype(jnp.float32) * freqs  # (..., half)
    return jnp.sin(ang), jnp.cos(ang)


def apply_rope(x: jax.Array, sin: jax.Array, cos: jax.Array) -> jax.Array:
    """x: (..., seq, heads, head_dim); sin/cos: (..., seq, half)."""
    dt = x.dtype
    x = x.astype(jnp.float32)
    x1, x2 = jnp.split(x, 2, axis=-1)
    s = sin[..., None, :]
    c = cos[..., None, :]
    return jnp.concatenate([x1 * c - x2 * s, x2 * c + x1 * s], -1).astype(dt)


# --------------------------------------------------------------- attention
def repeat_kv(k: jax.Array, groups: int) -> jax.Array:
    """(B,S,KV,hd) -> (B,S,KV*groups,hd) for GQA."""
    if groups == 1:
        return k
    b, s, kv, hd = k.shape
    return jnp.broadcast_to(k[:, :, :, None, :], (b, s, kv, groups, hd)).reshape(
        b, s, kv * groups, hd
    )


def _window_mask(
    q_pos: jax.Array, k_pos: jax.Array, causal: bool, window: int
) -> jax.Array:
    m = jnp.ones((q_pos.shape[0], k_pos.shape[0]), bool)
    if causal:
        m &= q_pos[:, None] >= k_pos[None, :]
    if window > 0:
        m &= q_pos[:, None] - k_pos[None, :] < window
    return m


def naive_attention(
    q: jax.Array,
    k: jax.Array,
    v: jax.Array,
    *,
    causal: bool = True,
    window: int = 0,
    q_offset: int = 0,
) -> jax.Array:
    """Oracle: (B,Sq,H,hd) x (B,Sk,KV,hd) -> (B,Sq,H,hd), f32 softmax."""
    b, sq, h, hd = q.shape
    kv = k.shape[2]
    k = repeat_kv(k, h // kv)
    v = repeat_kv(v, h // kv)
    scores = jnp.einsum("bqhd,bkhd->bhqk", q, k).astype(jnp.float32)
    scores *= hd**-0.5
    q_pos = jnp.arange(sq) + q_offset
    k_pos = jnp.arange(k.shape[1])
    mask = _window_mask(q_pos, k_pos, causal, window)
    scores = jnp.where(mask[None, None], scores, NEG_INF)
    w = jax.nn.softmax(scores, axis=-1)
    return jnp.einsum("bhqk,bkhd->bqhd", w.astype(v.dtype), v)


def chunked_attention(
    q: jax.Array,
    k: jax.Array,
    v: jax.Array,
    *,
    causal: bool = True,
    window: int = 0,
    q_offset: int = 0,
    chunk: int = 512,
) -> jax.Array:
    """Flash-style online-softmax over KV chunks (pure JAX, any backend).

    Memory: O(Sq * chunk) scores instead of O(Sq * Sk).
    """
    b, sq, h, hd = q.shape
    sk, kvh = k.shape[1], k.shape[2]
    groups = h // kvh
    if sk % chunk != 0:
        pad = chunk - sk % chunk
        k = jnp.pad(k, ((0, 0), (0, pad), (0, 0), (0, 0)))
        v = jnp.pad(v, ((0, 0), (0, pad), (0, 0), (0, 0)))
        sk_pad = sk + pad
    else:
        sk_pad = sk
    n_chunks = sk_pad // chunk
    kc = k.reshape(b, n_chunks, chunk, kvh, hd)
    vc = v.reshape(b, n_chunks, chunk, kvh, hd)

    q_pos = jnp.arange(sq) + q_offset
    scale = hd**-0.5

    def body(carry, xs):
        acc, m, l = carry
        kci, vci, ci = xs
        kk = repeat_kv(kci, groups)
        vv = repeat_kv(vci, groups)
        s = jnp.einsum("bqhd,bkhd->bhqk", q, kk).astype(jnp.float32) * scale
        k_pos = ci * chunk + jnp.arange(chunk)
        mask = (k_pos[None, :] < sk) & _window_mask(q_pos, k_pos, causal, window)
        s = jnp.where(mask[None, None], s, NEG_INF)
        m_new = jnp.maximum(m, s.max(-1))
        p = jnp.exp(s - m_new[..., None])
        corr = jnp.exp(m - m_new)
        l = l * corr + p.sum(-1)
        acc = acc * corr[..., None] + jnp.einsum(
            "bhqk,bkhd->bhqd", p, vv.astype(jnp.float32)
        )
        return (acc, m_new, l), None

    acc0 = jnp.zeros((b, h, sq, hd), jnp.float32)
    m0 = jnp.full((b, h, sq), NEG_INF, jnp.float32)
    l0 = jnp.zeros((b, h, sq), jnp.float32)
    (acc, m, l), _ = jax.lax.scan(
        body, (acc0, m0, l0), (kc.swapaxes(0, 1), vc.swapaxes(0, 1),
                               jnp.arange(n_chunks))
    )
    out = acc / jnp.maximum(l[..., None], 1e-37)
    return out.swapaxes(1, 2).astype(q.dtype)


def attention(
    q, k, v, *, causal=True, window=0, q_offset=0, impl: str = "chunked",
    chunk: int = 512,
) -> jax.Array:
    if impl == "naive":
        return naive_attention(
            q, k, v, causal=causal, window=window, q_offset=q_offset
        )
    if impl == "chunked":
        ch = min(chunk, max(k.shape[1], 128))
        return chunked_attention(
            q, k, v, causal=causal, window=window, q_offset=q_offset, chunk=ch
        )
    if impl.startswith("pallas"):
        from repro.kernels.flash_attention import ops as fa_ops

        return fa_ops.flash_attention(
            q, k, v, causal=causal, window=window, q_offset=q_offset,
            interpret=impl == "pallas_interpret",
        )
    raise ValueError(f"unknown attention impl {impl!r}")


# --------------------------------------------------------------------- MLP
def mlp(x, w_gate, w_up, w_down, *, act: str = "silu",
        b_up=None, b_down=None) -> jax.Array:
    if act == "silu":
        h = jax.nn.silu(x @ w_gate) * (x @ w_up)
    else:
        h = x @ w_up
        if b_up is not None:
            h = h + b_up
        h = jax.nn.gelu(h)
    y = h @ w_down
    if b_down is not None:
        y = y + b_down
    return y


# -------------------------------------------------------------------- loss
def cross_entropy(
    logits: jax.Array, labels: jax.Array, mask: Optional[jax.Array] = None
) -> jax.Array:
    """Mean next-token CE; logits (B,S,V) any float dtype, f32 internally."""
    logits = logits.astype(jnp.float32)
    logz = jax.nn.logsumexp(logits, axis=-1)
    ll = jnp.take_along_axis(logits, labels[..., None], axis=-1)[..., 0]
    nll = logz - ll
    if mask is not None:
        return (nll * mask).sum() / jnp.maximum(mask.sum(), 1)
    return nll.mean()
