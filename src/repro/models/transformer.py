"""Unified transformer zoo: dense / GQA / QKV-bias / qk-norm / sliding-window
/ MoE / encoder-decoder / early-fusion VLM — one implementation, flag-driven.

Params are a flat ``{symbol_name: array}`` dict (the stable-linking symbol
space). Homogeneous layer stacks are *stacked* on a leading L axis and run
under ``lax.scan`` with per-layer remat (small HLO, bounded activations);
heterogeneous stacks (gemma3's 5:1 local:global pattern) unroll with static
per-layer window flags so local layers get genuinely cheaper decode reads.
"""

from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np

from .common import (
    FREE_POS,
    apply_rope,
    attention,
    cross_entropy,
    layer_norm,
    mlp,
    repeat_kv,
    rms_norm,
    rope_angles,
)
from repro.dist.context import constrain
from repro.kernels.decode_attention import (
    decode_attention,
    decode_attention_ref,
)
from .moe import moe_block, no_counts, shared_expert
from .runtime import layer_loop, remat_wrap, scans_unrolled
from .specs import ParamSpec

# --------------------------------------------------------------------------
# Parameter specs (symbol manifest)
# --------------------------------------------------------------------------


def _norm_specs(name: str, dim: int, cfg, axes=("embed",)) -> dict[str, ParamSpec]:
    d = {f"{name}/scale": ParamSpec((dim,), cfg.dtype, axes, "ones")}
    if cfg.use_bias:
        d[f"{name}/bias"] = ParamSpec((dim,), cfg.dtype, axes, "zeros")
    return d


def _attn_specs(cfg, d_in: int, d_out: int) -> dict[str, ParamSpec]:
    hd = cfg.resolved_head_dim
    H, KV = cfg.num_heads, cfg.num_kv_heads
    dt = cfg.dtype
    s = {
        "attn/wq": ParamSpec((d_in, H * hd), dt, ("embed", "heads"), "fan_in"),
        "attn/wk": ParamSpec((d_in, KV * hd), dt, ("embed", "kv_heads"), "fan_in"),
        "attn/wv": ParamSpec((d_in, KV * hd), dt, ("embed", "kv_heads"), "fan_in"),
        "attn/wo": ParamSpec((H * hd, d_out), dt, ("heads", "embed"), "fan_in"),
    }
    if cfg.qkv_bias:
        s["attn/bq"] = ParamSpec((H * hd,), dt, ("heads",), "zeros")
        s["attn/bk"] = ParamSpec((KV * hd,), dt, ("kv_heads",), "zeros")
        s["attn/bv"] = ParamSpec((KV * hd,), dt, ("kv_heads",), "zeros")
    if cfg.use_bias:
        s["attn/bo"] = ParamSpec((d_out,), dt, ("embed",), "zeros")
    if cfg.qk_norm and cfg.qk_norm_width == "full":
        s["attn/q_norm"] = ParamSpec((H * hd,), dt, ("heads",), "ones")
        s["attn/k_norm"] = ParamSpec((KV * hd,), dt, ("kv_heads",), "ones")
    elif cfg.qk_norm:
        s["attn/q_norm"] = ParamSpec((hd,), dt, ("head_dim",), "ones")
        s["attn/k_norm"] = ParamSpec((hd,), dt, ("head_dim",), "ones")
    return s


def _mlp_specs(cfg, d: int) -> dict[str, ParamSpec]:
    dt, ff = cfg.dtype, cfg.d_ff
    if cfg.is_moe:
        # the router scores every expert; this chip holds E_h of them
        E, E_h = cfg.num_experts, cfg.held_experts
        s = {
            "router/w": ParamSpec((d, E), dt, ("embed", "experts"), "fan_in"),
            "experts/w_gate": ParamSpec(
                (E_h, d, ff), dt, ("experts", "embed", "mlp"), "fan_in"
            ),
            "experts/w_up": ParamSpec(
                (E_h, d, ff), dt, ("experts", "embed", "mlp"), "fan_in"
            ),
            "experts/w_down": ParamSpec(
                (E_h, ff, d), dt, ("experts", "mlp", "embed"), "fan_in"
            ),
        }
        if cfg.num_shared_experts:
            sf = cfg.num_shared_experts * ff
            s["shared/w_gate"] = ParamSpec((d, sf), dt, ("embed", "mlp"), "fan_in")
            s["shared/w_up"] = ParamSpec((d, sf), dt, ("embed", "mlp"), "fan_in")
            s["shared/w_down"] = ParamSpec((sf, d), dt, ("mlp", "embed"), "fan_in")
            s["shared/gate"] = ParamSpec((d, 1), dt, ("embed", None), "fan_in")
        return s
    s = {
        "mlp/w_up": ParamSpec((d, ff), dt, ("embed", "mlp"), "fan_in"),
        "mlp/w_down": ParamSpec((ff, d), dt, ("mlp", "embed"), "fan_in"),
    }
    if cfg.act == "silu":
        s["mlp/w_gate"] = ParamSpec((d, ff), dt, ("embed", "mlp"), "fan_in")
    if cfg.use_bias:
        s["mlp/b_up"] = ParamSpec((ff,), dt, ("mlp",), "zeros")
        s["mlp/b_down"] = ParamSpec((d,), dt, ("embed",), "zeros")
    return s


def _block_specs(cfg, *, cross: bool = False) -> dict[str, ParamSpec]:
    d = cfg.d_model
    s: dict[str, ParamSpec] = {}
    s.update(_norm_specs("attn_norm", d, cfg))
    s.update(_attn_specs(cfg, d, d))
    if cross:
        s.update(_norm_specs("xattn_norm", d, cfg))
        s.update({f"x{k}": v for k, v in _attn_specs(cfg, d, d).items()})
    s.update(_norm_specs("mlp_norm", d, cfg))
    s.update(_mlp_specs(cfg, d))
    return s


def _stack(prefix: str, L: int, template: dict[str, ParamSpec]):
    return {
        f"{prefix}/{n}": ParamSpec(
            (L,) + t.shape, t.dtype, ("layers",) + t.axes, t.init
        )
        for n, t in template.items()
    }


def param_specs(cfg) -> dict[str, ParamSpec]:
    d, V, dt = cfg.d_model, cfg.vocab_size, cfg.dtype
    specs: dict[str, ParamSpec] = {
        "embed/tokens": ParamSpec((V, d), dt, ("vocab", "embed"), "normal"),
    }
    if cfg.frontend == "audio_frames":
        specs["frontend/proj"] = ParamSpec(
            (d, d), dt, ("embed", "embed_tp"), "fan_in"
        )
    if cfg.is_encdec:
        specs.update(_stack("enc", cfg.encoder_layers, _block_specs(cfg)))
        specs.update(_norm_specs("enc_final_norm", d, cfg))
        specs.update(_stack("dec", cfg.num_layers, _block_specs(cfg, cross=True)))
    else:
        specs.update(_stack("blocks", cfg.num_layers, _block_specs(cfg)))
    specs.update(_norm_specs("final_norm", d, cfg))
    if not cfg.tie_embeddings:
        specs["lm_head/w"] = ParamSpec((d, V), dt, ("embed", "vocab"), "fan_in")
    return specs


# --------------------------------------------------------------------------
# Block application
# --------------------------------------------------------------------------


def _norm(p, name, x, cfg):
    if cfg.use_bias:
        return layer_norm(x, p[f"{name}/scale"], p[f"{name}/bias"], cfg.norm_eps)
    return rms_norm(x, p[f"{name}/scale"], cfg.norm_eps)


def _project_qkv(cfg, p, x, *, prefix="attn"):
    B, S, _ = x.shape
    hd = cfg.resolved_head_dim
    H, KV = cfg.num_heads, cfg.num_kv_heads
    q = x @ p[f"{prefix}/wq"]
    k = x @ p[f"{prefix}/wk"]
    v = x @ p[f"{prefix}/wv"]
    if cfg.qkv_bias:
        q = q + p[f"{prefix}/bq"]
        k = k + p[f"{prefix}/bk"]
        v = v + p[f"{prefix}/bv"]
    full = cfg.qk_norm and cfg.qk_norm_width == "full"
    if full:    # olmoe: over the whole projection, before the split
        q = rms_norm(q, p[f"{prefix}/q_norm"], cfg.norm_eps)
        k = rms_norm(k, p[f"{prefix}/k_norm"], cfg.norm_eps)
    q = q.reshape(B, S, H, hd)
    k = k.reshape(B, S, KV, hd)
    v = v.reshape(B, S, KV, hd)
    if cfg.qk_norm and not full:
        q = rms_norm(q, p[f"{prefix}/q_norm"], cfg.norm_eps)
        k = rms_norm(k, p[f"{prefix}/k_norm"], cfg.norm_eps)
    return q, k, v


def _self_attention(cfg, p, x, sin, cos, *, window, impl, q_offset=0):
    B, S, _ = x.shape
    q, k, v = _project_qkv(cfg, p, x)
    q = apply_rope(q, sin, cos)
    k = apply_rope(k, sin, cos)
    o = attention(
        q, k, v, causal=True, window=window, q_offset=q_offset, impl=impl
    )
    o = o.reshape(B, S, -1) @ p["attn/wo"]
    if cfg.use_bias:
        o = o + p["attn/bo"]
    return o, k, v


def _mlp_or_moe(cfg, p, x, rows=None):
    """Returns (out, aux_loss, the expert layer's counts). ``rows`` (B,)
    marks the rows an expert layer routes (None: all)."""
    if cfg.is_moe:
        out, aux, counts = moe_block(
            x,
            p["router/w"],
            p["experts/w_gate"],
            p["experts/w_up"],
            p["experts/w_down"],
            k=cfg.experts_per_token,
            first=cfg.first_expert,
            norm_topk=cfg.norm_topk_prob,
            rows=rows,
        )
        if cfg.num_shared_experts:
            out = out + shared_expert(
                x,
                p["shared/w_gate"],
                p["shared/w_up"],
                p["shared/w_down"],
                p["shared/gate"],
            )
        return out, aux, counts
    return (
        mlp(
            x,
            p.get("mlp/w_gate"),
            p["mlp/w_up"],
            p["mlp/w_down"],
            act=cfg.act,
            b_up=p.get("mlp/b_up"),
            b_down=p.get("mlp/b_down"),
        ),
        jnp.float32(0.0),
        no_counts(),
    )


def _gather_weights(cfg, p, *, cross=False):
    """FSDP weight unsharding at use-site: drop the `embed`(->data) axis
    from each block weight's sharding. XLA emits one all-gather per weight
    per use (overlappable — TPU_PERF_XLA_FLAGS) instead of psum-ing every
    activation matmul over the sharded contraction dim (§Perf hillclimb D)."""
    tmpl = _block_specs(cfg, cross=cross)
    out = {}
    for n, a in p.items():
        spec = tmpl.get(n)
        if spec is None:
            out[n] = a
            continue
        axes = tuple(None if ax in ("embed",) else ax for ax in spec.axes)
        out[n] = constrain(a, axes)
    return out


def _block(cfg, p, x, sin, cos, *, window, impl, enc_out=None,
           collect_kv=False):
    x = constrain(x, ("batch", "seq", None))  # keep activations DP-sharded
    p = _gather_weights(cfg, p, cross=enc_out is not None)
    h = _norm(p, "attn_norm", x, cfg)
    o, k, v = _self_attention(cfg, p, h, sin, cos, window=window, impl=impl)
    x = x + o
    if enc_out is not None:  # cross attention (decoder of enc-dec)
        h = _norm(p, "xattn_norm", x, cfg)
        B, S, _ = h.shape
        hd = cfg.resolved_head_dim
        q = (h @ p["xattn/wq"]).reshape(B, S, cfg.num_heads, hd)
        xk = (enc_out @ p["xattn/wk"]).reshape(
            B, enc_out.shape[1], cfg.num_kv_heads, hd
        )
        xv = (enc_out @ p["xattn/wv"]).reshape(
            B, enc_out.shape[1], cfg.num_kv_heads, hd
        )
        o = attention(q, xk, xv, causal=False, impl=impl)
        o = o.reshape(B, S, -1) @ p["xattn/wo"]
        x = x + o
    h = _norm(p, "mlp_norm", x, cfg)
    m, aux, counts = _mlp_or_moe(cfg, p, h)
    x = x + m
    return x, aux, counts, ((k, v) if collect_kv else None)


def _stacked_params(params: dict, prefix: str) -> dict:
    plen = len(prefix) + 1
    return {n[plen:]: a for n, a in params.items() if n.startswith(prefix + "/")}


def _layer_windows(cfg) -> list[int]:
    """Per-layer attention windows; 0 = full/global."""
    L = cfg.num_layers
    if cfg.sliding_window <= 0:
        return [0] * L
    g = cfg.global_every
    return [0 if (g and (i + 1) % g == 0) else cfg.sliding_window
            for i in range(L)]


def run_stack(
    cfg,
    params,
    prefix,
    x,
    sin,
    cos,
    *,
    impl,
    enc_out=None,
    collect_kv=False,
    remat=True,
):
    """Run a layer stack; homogeneous window -> lax.scan, else unrolled.
    Returns (x, aux loss, (K, V) per layer or None, the expert layers'
    counts summed over layers)."""
    stacked = _stacked_params(params, prefix)
    windows = _layer_windows(cfg) if prefix != "enc" else [0] * cfg.encoder_layers
    homogeneous = len(set(windows)) == 1 and not scans_unrolled()

    if homogeneous:
        def body(carry, xs):
            h, aux, counts = carry
            h2, aux_l, counts_l, kv = _block(
                cfg, xs, h, sin, cos, window=windows[0], impl=impl,
                enc_out=enc_out, collect_kv=collect_kv,
            )
            return (h2, aux + aux_l, counts + counts_l), kv

        if remat:
            body = remat_wrap(body, cfg)
        (x, aux, counts), kvs = jax.lax.scan(
            body, (x, jnp.float32(0.0), no_counts()), stacked)
        return x, aux, kvs, counts

    # heterogeneous (gemma3 local:global): unrolled, static per-layer window
    aux = jnp.float32(0.0)
    counts = no_counts()
    ks, vs = [], []
    L = len(windows)
    for i in range(L):
        p_i = {n: a[i] for n, a in stacked.items()}
        blk = functools.partial(
            _block, cfg, p_i, window=windows[i], impl=impl,
            enc_out=enc_out, collect_kv=collect_kv,
        )
        if remat:
            blk = remat_wrap(blk, cfg)
        x, aux_l, counts_l, kv = blk(x, sin, cos)
        aux = aux + aux_l
        counts = counts + counts_l
        if collect_kv:
            ks.append(kv[0])
            vs.append(kv[1])
    kvs = (jnp.stack(ks), jnp.stack(vs)) if collect_kv else None
    return x, aux, kvs, counts


# --------------------------------------------------------------------------
# Entry points
# --------------------------------------------------------------------------


def _embed_in(cfg, params, batch):
    if cfg.is_encdec:
        tokens = batch["tokens"]
    else:
        tokens = batch["tokens"]
    x = jnp.take(params["embed/tokens"], tokens, axis=0)
    return x


def _encode(cfg, params, frames, impl):
    """Encoder over precomputed frame embeddings (modality stub)."""
    x = frames.astype(jnp.dtype(cfg.dtype))
    if "frontend/proj" in params:
        x = x @ params["frontend/proj"]
    S = x.shape[1]
    sin, cos = rope_angles(jnp.arange(S), cfg.resolved_head_dim, cfg.rope_theta)
    x, _, _, _ = run_stack(cfg, params, "enc", x, sin, cos, impl=impl)
    return _norm(params, "enc_final_norm", x, cfg)


def logits_fn(cfg, params, x):
    x = _norm(params, "final_norm", x, cfg)
    logits = (
        x @ params["embed/tokens"].T
        if cfg.tie_embeddings
        else x @ params["lm_head/w"]
    )
    return constrain(logits, ("batch", "seq", "vocab"))


def forward(cfg, params, batch, *, impl: str = "chunked"):
    """Full-sequence forward -> logits (B, S, V). Batch keys:
    tokens (B,S) [+ frames (B,S_enc,d) for enc-dec/audio]."""
    x = _embed_in(cfg, params, batch)
    S = x.shape[1]
    sin, cos = rope_angles(jnp.arange(S), cfg.resolved_head_dim, cfg.rope_theta)
    enc_out = None
    if cfg.is_encdec:
        enc_out = _encode(cfg, params, batch["frames"], impl)
        x, aux, _, _ = run_stack(
            cfg, params, "dec", x, sin, cos, impl=impl, enc_out=enc_out
        )
    else:
        x, aux, _, _ = run_stack(cfg, params, "blocks", x, sin, cos, impl=impl)
    return logits_fn(cfg, params, x), aux


def loss_fn(cfg, params, batch, *, impl: str = "chunked", aux_coef=0.01):
    logits, aux = forward(cfg, params, batch, impl=impl)
    ce = cross_entropy(logits, batch["labels"])
    return ce + aux_coef * aux


# ------------------------------------------------------------------ decode
def cache_spec(cfg, batch: int, seq_len: int):
    """(shapes, logical axes) for the decode cache — dry-run friendly."""
    hd = cfg.resolved_head_dim
    KV = cfg.num_kv_heads
    dt = cfg.dtype
    L = cfg.num_layers
    kv_axes = ("layers", "batch", "cache_seq", "kv_heads", "head_dim")
    shapes = {
        "k": jax.ShapeDtypeStruct((L, batch, seq_len, KV, hd), jnp.dtype(dt)),
        "v": jax.ShapeDtypeStruct((L, batch, seq_len, KV, hd), jnp.dtype(dt)),
        "pos": jax.ShapeDtypeStruct((), jnp.int32),
    }
    axes = {"k": kv_axes, "v": kv_axes, "pos": ()}
    if cfg.is_encdec:
        xkv = jax.ShapeDtypeStruct((L, batch, seq_len, KV, hd), jnp.dtype(dt))
        shapes.update({"xk": xkv, "xv": xkv})
        axes.update({"xk": kv_axes, "xv": kv_axes})
    return shapes, axes


def kernel_layers(cfg, seq_len: int) -> int:
    """Self-attention layers whose decode step reads a cache of
    ``seq_len`` positions through the decode-attention kernel: all but
    those with a window shorter than the cache."""
    return sum(not (w and w < seq_len) for w in _layer_windows(cfg))


def init_cache(cfg, batch: int, seq_len: int):
    shapes, _ = cache_spec(cfg, batch, seq_len)
    return {k: jnp.zeros(s.shape, s.dtype) for k, s in shapes.items()}


def prefill(cfg, params, batch, *, impl: str = "chunked", cache_len=None,
            counters: bool = False):
    """Process a prompt; returns (last-position logits, filled cache), and
    with ``counters`` the expert layers' counts summed over layers
    (``moe.COUNTERS``; zeros for a model with none)."""
    tokens = batch["tokens"]
    B, S = tokens.shape
    cache_len = cache_len or S
    x = jnp.take(params["embed/tokens"], tokens, axis=0)
    sin, cos = rope_angles(jnp.arange(S), cfg.resolved_head_dim, cfg.rope_theta)
    enc_out = None
    extra = {}
    if cfg.is_encdec:
        enc_out = _encode(cfg, params, batch["frames"], impl)
        x, _, kvs, counts = run_stack(
            cfg, params, "dec", x, sin, cos, impl=impl, enc_out=enc_out,
            collect_kv=True,
        )
        # precompute cross K/V once (reused every decode step)
        stacked = _stacked_params(params, "dec")
        hd = cfg.resolved_head_dim

        def xkv(p_wk, p_wv):
            xk = (enc_out @ p_wk).reshape(
                B, enc_out.shape[1], cfg.num_kv_heads, hd
            )
            xv = (enc_out @ p_wv).reshape(
                B, enc_out.shape[1], cfg.num_kv_heads, hd
            )
            return xk, xv

        xks, xvs = jax.vmap(xkv)(stacked["xattn/wk"], stacked["xattn/wv"])
        extra = {"xk": xks, "xv": xvs}
    else:
        x, _, kvs, counts = run_stack(
            cfg, params, "blocks", x, sin, cos, impl=impl, collect_kv=True
        )
    ks, vs = kvs
    pad = cache_len - S
    if pad > 0:
        ks = jnp.pad(ks, ((0, 0), (0, 0), (0, pad), (0, 0), (0, 0)))
        vs = jnp.pad(vs, ((0, 0), (0, 0), (0, pad), (0, 0), (0, 0)))
    cache = {"k": ks, "v": vs, "pos": jnp.int32(S - 1), **extra}
    logits = logits_fn(cfg, params, x[:, -1:, :])
    return (logits, cache, counts) if counters else (logits, cache)


def decode_step(cfg, params, cache, tokens, *, active=None,
                counters: bool = False):
    """One decode step: tokens (B,1) + cache -> (logits (B,1,V), cache'),
    and with ``counters`` the expert layers' counts summed over layers.

    ``pos`` is a scalar or one position per row (B,). The stacked K/V
    caches are carried through the layer loop: each layer writes its new
    token's K and V at each row's ``pos % S`` in place, and a full-attention
    layer hands the whole stacked cache and its own index to the
    decode-attention kernel, which reads each row's positions up to
    ``min(pos + 1, S)`` where the cache lies (windowed layers read their
    window through XLA). A row whose ``pos`` is ``FREE_POS`` (a free slot)
    reads no cache. ``active`` (B,) bool marks the rows an expert layer
    routes: a free slot's row costs its experts nothing (None: every
    row)."""
    B = tokens.shape[0]
    hd = cfg.resolved_head_dim
    pos = cache["pos"] + 1  # position being written
    rpos = jnp.broadcast_to(pos, (B,))
    rows = jnp.arange(B)
    x = jnp.take(params["embed/tokens"], tokens, axis=0)
    sin, cos = rope_angles(rpos[:, None], hd, cfg.rope_theta)
    prefix = "dec" if cfg.is_encdec else "blocks"
    stacked = _stacked_params(params, prefix)
    windows = _layer_windows(cfg)
    homogeneous = len(set(windows)) == 1
    S = cache["k"].shape[2]
    lengths = jnp.where(cache["pos"] == FREE_POS, 0, jnp.minimum(rpos + 1, S))

    def layer(carry, p, i, window, xk=None, xv=None):
        x, k_all, v_all = carry
        h = _norm(p, "attn_norm", x, cfg)
        q, k_new, v_new = _project_qkv(cfg, p, h)
        q = apply_rope(q, sin, cos)
        k_new = apply_rope(k_new, sin, cos)
        k_all = k_all.at[i, rows, rpos % S].set(k_new[:, 0])
        v_all = v_all.at[i, rows, rpos % S].set(v_new[:, 0])
        if window and window < S:
            start = jnp.clip(rpos - window + 1, 0, S - window)
            take = jax.vmap(
                lambda c, s0: jax.lax.dynamic_slice_in_dim(c, s0, window, 0)
            )
            k_c = jax.lax.dynamic_index_in_dim(k_all, i, 0, keepdims=False)
            v_c = jax.lax.dynamic_index_in_dim(v_all, i, 0, keepdims=False)
            o = decode_attention_ref(q, take(k_c, start), take(v_c, start),
                                     rpos - start + 1)
        else:
            o = decode_attention(q, k_all, v_all, jnp.asarray(i, jnp.int32),
                                 lengths)
        o = o.reshape(B, 1, -1) @ p["attn/wo"]
        if cfg.use_bias:
            o = o + p["attn/bo"]
        x = x + o
        if xk is not None:
            h = _norm(p, "xattn_norm", x, cfg)
            q2 = (h @ p["xattn/wq"]).reshape(B, 1, cfg.num_heads, hd)
            o = decode_attention_ref(q2, xk, xv, jnp.int32(xk.shape[1]))
            x = x + o.reshape(B, 1, -1) @ p["xattn/wo"]
        h = _norm(p, "mlp_norm", x, cfg)
        m, _, counts = _mlp_or_moe(cfg, p, h, rows=active)
        return (x + m, k_all, v_all), counts

    carry = (x, cache["k"], cache["v"])
    if homogeneous:
        xs = dict(stacked)
        if cfg.is_encdec:
            # the cross caches are read-only: they ride as scan inputs
            xs["__xk"] = cache["xk"]
            xs["__xv"] = cache["xv"]

        def body(carry, xs_l, i):
            xk = xs_l.pop("__xk", None)
            xv = xs_l.pop("__xv", None)
            return layer(carry, xs_l, i, windows[0], xk, xv)

        carry, counts = layer_loop(body, carry, xs)
        counts = counts.sum(0)
    else:
        counts = no_counts()
        for i, w in enumerate(windows):
            p_i = {n: a[i] for n, a in stacked.items()}
            xk = cache["xk"][i] if cfg.is_encdec else None
            xv = cache["xv"][i] if cfg.is_encdec else None
            carry, counts_l = layer(carry, p_i, i, w, xk, xv)
            counts = counts + counts_l
    x, ks, vs = carry

    logits = logits_fn(cfg, params, x)
    new_cache = dict(cache)
    new_cache.update({"k": ks, "v": vs, "pos": pos})
    return (logits, new_cache, counts) if counters else (logits, new_cache)
