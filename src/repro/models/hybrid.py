"""Zamba2-style hybrid: Mamba2 backbone + ONE weight-shared attention block
applied every ``attn_every`` layers [arXiv:2411.15242].

The shared block consumes concat(x, x_embed0) (2*d) — the Zamba trick that
re-injects the initial embedding — runs attention + SwiGLU MLP at 2*d, and
projects back to d. All invocations reuse the SAME parameters: in stable-
linking terms, 14 references resolving to one provider symbol (exercised by
tests/test_system.py).

Decode keeps one KV cache per *invocation* (same weights, different
activations) plus the per-layer mamba conv/ssm states.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

from .common import (
    FREE_POS,
    apply_rope,
    attention,
    cross_entropy,
    mlp,
    rms_norm,
    rope_angles,
)
from repro.kernels.decode_attention import decode_attention_ref
from . import mamba2
from .runtime import remat_wrap, scans_unrolled
from .specs import ParamSpec


def _hd(cfg) -> int:
    return 2 * cfg.d_model // cfg.num_heads  # attention runs at 2*d


def n_invocations(cfg) -> int:
    return (cfg.num_layers + cfg.attn_every - 1) // cfg.attn_every


# --------------------------------------------------------------------------
def param_specs(cfg) -> dict[str, ParamSpec]:
    d, V, dt = cfg.d_model, cfg.vocab_size, cfg.dtype
    d2 = 2 * d
    hd = _hd(cfg)
    H, KV, ff = cfg.num_heads, cfg.num_kv_heads, cfg.d_ff
    specs = {
        "embed/tokens": ParamSpec((V, d), dt, ("vocab", "embed"), "normal"),
    }
    t = mamba2.block_specs(cfg)
    specs.update(
        {
            f"blocks/{n}": ParamSpec(
                (cfg.num_layers,) + s.shape, s.dtype, ("layers",) + s.axes, s.init
            )
            for n, s in t.items()
        }
    )
    specs.update(
        {
            "shared_attn/norm/scale": ParamSpec((d2,), dt, ("embed",), "ones"),
            "shared_attn/wq": ParamSpec((d2, H * hd), dt, ("embed", "heads"), "fan_in"),
            "shared_attn/wk": ParamSpec(
                (d2, KV * hd), dt, ("embed", "kv_heads"), "fan_in"
            ),
            "shared_attn/wv": ParamSpec(
                (d2, KV * hd), dt, ("embed", "kv_heads"), "fan_in"
            ),
            "shared_attn/wo": ParamSpec((H * hd, d2), dt, ("heads", "embed"), "fan_in"),
            "shared_attn/mlp_norm/scale": ParamSpec((d2,), dt, ("embed",), "ones"),
            "shared_attn/mlp/w_gate": ParamSpec((d2, ff), dt, ("embed", "mlp"), "fan_in"),
            "shared_attn/mlp/w_up": ParamSpec((d2, ff), dt, ("embed", "mlp"), "fan_in"),
            "shared_attn/mlp/w_down": ParamSpec((ff, d2), dt, ("mlp", "embed"), "fan_in"),
            "shared_attn/out_proj/w": ParamSpec((d2, d), dt, ("embed", "embed_tp"), "fan_in"),
            "final_norm/scale": ParamSpec((d,), dt, ("embed",), "ones"),
            "lm_head/w": ParamSpec((d, V), dt, ("embed", "vocab"), "fan_in"),
        }
    )
    return specs


# --------------------------------------------------------------------------
def _shared_block(cfg, params, x, x0, sin, cos, *, impl, collect_kv=False):
    B, S, d = x.shape
    hd = _hd(cfg)
    h = jnp.concatenate([x, x0], -1)                     # (B,S,2d)
    h = rms_norm(h, params["shared_attn/norm/scale"], cfg.norm_eps)
    q = (h @ params["shared_attn/wq"]).reshape(B, S, cfg.num_heads, hd)
    k = (h @ params["shared_attn/wk"]).reshape(B, S, cfg.num_kv_heads, hd)
    v = (h @ params["shared_attn/wv"]).reshape(B, S, cfg.num_kv_heads, hd)
    q = apply_rope(q, sin, cos)
    k = apply_rope(k, sin, cos)
    o = attention(q, k, v, causal=True, impl=impl)
    a = o.reshape(B, S, -1) @ params["shared_attn/wo"]
    hm = rms_norm(a, params["shared_attn/mlp_norm/scale"], cfg.norm_eps)
    a = a + mlp(
        hm,
        params["shared_attn/mlp/w_gate"],
        params["shared_attn/mlp/w_up"],
        params["shared_attn/mlp/w_down"],
    )
    out = x + a @ params["shared_attn/out_proj/w"]
    return (out, (k, v)) if collect_kv else (out, None)


def _mamba_group(cfg, params, x, lo, hi, *, collect_state=False):
    """Scan over mamba layers [lo, hi) (static slice of the stacked params)."""
    stacked = mamba2._stacked(params)
    sub = {n: a[lo:hi] for n, a in stacked.items()}

    if collect_state:
        def body(h, p):
            h, final, conv = mamba2.mamba_block(cfg, p, h, return_state=True)
            return h, (conv, final)
    else:
        def body(h, p):
            return mamba2.mamba_block(cfg, p, h), None

    body = remat_wrap(body, cfg)
    if scans_unrolled():
        outs = []
        for i in range(hi - lo):
            x, o = body(x, {n: a[i] for n, a in sub.items()})
            outs.append(o)
        if collect_state:
            return x, (jnp.stack([o[0] for o in outs]),
                       jnp.stack([o[1] for o in outs]))
        return x, None
    return jax.lax.scan(body, x, sub)


def forward(cfg, params, batch, *, impl: str = "chunked"):
    x = jnp.take(params["embed/tokens"], batch["tokens"], axis=0)
    x0 = x
    S = x.shape[1]
    sin, cos = rope_angles(jnp.arange(S), _hd(cfg), cfg.rope_theta)
    g = cfg.attn_every
    for lo in range(0, cfg.num_layers, g):
        x, _ = _shared_block(cfg, params, x, x0, sin, cos, impl=impl)
        x, _ = _mamba_group(cfg, params, x, lo, min(lo + g, cfg.num_layers))
    return mamba2.logits_fn(cfg, params, x), jnp.float32(0.0)


def loss_fn(cfg, params, batch, *, impl: str = "chunked", aux_coef=0.0):
    logits, _ = forward(cfg, params, batch, impl=impl)
    return cross_entropy(logits, batch["labels"])


# ------------------------------------------------------------------ decode
def cache_spec(cfg, batch: int, seq_len: int):
    m_shapes, m_axes = mamba2.cache_spec(cfg, batch, seq_len)
    hd = _hd(cfg)
    I = n_invocations(cfg)
    kv = jax.ShapeDtypeStruct(
        (I, batch, seq_len, cfg.num_kv_heads, hd), jnp.dtype(cfg.dtype)
    )
    kv_axes = ("stack", "batch", "cache_seq", "kv_heads", "head_dim")
    shapes = {**m_shapes, "k": kv, "v": kv}
    axes = {**m_axes, "k": kv_axes, "v": kv_axes}
    return shapes, axes


def init_cache(cfg, batch: int, seq_len: int):
    shapes, _ = cache_spec(cfg, batch, seq_len)
    return {k: jnp.zeros(s.shape, s.dtype) for k, s in shapes.items()}


def prefill(cfg, params, batch, *, impl: str = "chunked", cache_len=None):
    tokens = batch["tokens"]
    B, S = tokens.shape
    cache_len = cache_len or S
    x = jnp.take(params["embed/tokens"], tokens, axis=0)
    x0 = x
    sin, cos = rope_angles(jnp.arange(S), _hd(cfg), cfg.rope_theta)
    g = cfg.attn_every
    ks, vs, convs, ssms = [], [], [], []
    for lo in range(0, cfg.num_layers, g):
        x, (k, v) = _shared_block(
            cfg, params, x, x0, sin, cos, impl=impl, collect_kv=True
        )
        ks.append(k)
        vs.append(v)
        x, (conv, ssm) = _mamba_group(
            cfg, params, x, lo, min(lo + g, cfg.num_layers), collect_state=True
        )
        convs.append(conv)
        ssms.append(ssm)
    ks = jnp.stack(ks)
    vs = jnp.stack(vs)
    pad = cache_len - S
    if pad > 0:
        ks = jnp.pad(ks, ((0, 0), (0, 0), (0, pad), (0, 0), (0, 0)))
        vs = jnp.pad(vs, ((0, 0), (0, 0), (0, pad), (0, 0), (0, 0)))
    cache = {
        "k": ks,
        "v": vs,
        "conv": jnp.concatenate(convs),
        "ssm": jnp.concatenate(ssms),
        "pos": jnp.int32(S - 1),
    }
    return mamba2.logits_fn(cfg, params, x[:, -1:, :]), cache


def decode_step(cfg, params, cache, tokens):
    """tokens (B,1) + cache -> (logits (B,1,V), cache'). ``pos`` is a
    scalar or one position per row (B,). Every cache is written in place:
    each attention invocation its new token's K and V at each row's
    ``pos % S``, each mamba layer its conv state and its SSM state
    (``mamba2.decode_layers``). A row whose ``pos`` is ``FREE_POS`` (a
    free slot) reads no cache and keeps its SSM state as it was."""
    B = tokens.shape[0]
    hd = _hd(cfg)
    pos = cache["pos"] + 1
    rpos = jnp.broadcast_to(pos, (B,))
    rows = jnp.arange(B)
    S = cache["k"].shape[2]
    lengths = jnp.where(cache["pos"] == FREE_POS, 0, rpos + 1)
    x = jnp.take(params["embed/tokens"], tokens, axis=0)
    x0 = x
    sin, cos = rope_angles(rpos[:, None], hd, cfg.rope_theta)
    g = cfg.attn_every
    live = mamba2.live_rows(cache, B)
    k_all, v_all = cache["k"], cache["v"]
    conv, ssm = cache["conv"], cache["ssm"]
    for i, lo in enumerate(range(0, cfg.num_layers, g)):
        # shared attention with this invocation's cache
        h = jnp.concatenate([x, x0], -1)
        h = rms_norm(h, params["shared_attn/norm/scale"], cfg.norm_eps)
        q = (h @ params["shared_attn/wq"]).reshape(B, 1, cfg.num_heads, hd)
        k_new = (h @ params["shared_attn/wk"]).reshape(B, 1, cfg.num_kv_heads, hd)
        v_new = (h @ params["shared_attn/wv"]).reshape(B, 1, cfg.num_kv_heads, hd)
        q = apply_rope(q, sin, cos)
        k_new = apply_rope(k_new, sin, cos)
        k_all = k_all.at[i, rows, rpos % S].set(k_new[:, 0])
        v_all = v_all.at[i, rows, rpos % S].set(v_new[:, 0])
        o = decode_attention_ref(q, k_all[i], v_all[i], lengths)
        a = o.reshape(B, 1, -1) @ params["shared_attn/wo"]
        hm = rms_norm(a, params["shared_attn/mlp_norm/scale"], cfg.norm_eps)
        a = a + mlp(
            hm,
            params["shared_attn/mlp/w_gate"],
            params["shared_attn/mlp/w_up"],
            params["shared_attn/mlp/w_down"],
        )
        x = x + a @ params["shared_attn/out_proj/w"]
        # mamba group decode
        x, conv, ssm = mamba2.decode_layers(
            cfg, params, x, conv, ssm, live, lo, min(lo + g, cfg.num_layers)
        )
    logits = mamba2.logits_fn(cfg, params, x)
    return logits, {"k": k_all, "v": v_all, "conv": conv, "ssm": ssm,
                    "pos": pos}
