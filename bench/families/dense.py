"""Dense decoder family (StarCoder2): parameter layout, weight rules, a
plain float32 reference forward, and the operations and bytes that the
metrics count.

Written from the StarCoder2 paper (arXiv:2402.19173, section 5, "model
architecture") and the published ``Starcoder2ForCausalLM`` layer: pre-norm
blocks with biased LayerNorm, grouped-query attention with biased query,
key and value projections and rotary embeddings over the whole head
(half-split rotation), a biased output projection, and a biased two-layer
MLP with tanh GELU; the output head is the embedding, tied. The reference is a
full causal forward over the whole sequence in float32 at ``highest``
matmul precision: no cache, no online softmax. Only the parameter names
follow the program's checkpoint layout.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

from bench.families.ssm import _np
from bench.harness import quant

Q_BLOCK = 256        # queries per attention block, to bound the score matrix


def sizes(c: dict) -> dict:
    hd = c["hidden_size"] // c["num_attention_heads"]
    return dict(
        d=c["hidden_size"], heads=c["num_attention_heads"],
        kv=c["num_key_value_heads"], hd=hd, ff=c["intermediate_size"],
        layers=c["num_hidden_layers"], vocab=c["vocab_size"],
        tokens=c["vocab_size"],
    )


def program_overrides(c: dict) -> dict:
    return dict(
        family="dense", num_layers=c["num_hidden_layers"],
        d_model=c["hidden_size"], num_heads=c["num_attention_heads"],
        num_kv_heads=c["num_key_value_heads"], head_dim=0,
        d_ff=c["intermediate_size"], vocab_size=c["vocab_size"],
        use_bias=c["use_bias"], qkv_bias=c["use_bias"], act="gelu",
        rope_theta=c["rope_theta"], norm_eps=c["norm_epsilon"],
        tie_embeddings=c["tie_word_embeddings"],
        sliding_window=c["sliding_window"], dtype=c["dtype"],
    )


def layout(c: dict) -> dict:
    s = sizes(c)
    dt, L, d = c["dtype"], s["layers"], s["d"]
    out = {"embed/tokens": ((s["vocab"], d), dt, "normal:0.02", False)}
    block = {
        "attn_norm/scale": ((d,), "scale"),
        "attn_norm/bias": ((d,), "bias"),
        "attn/wq": ((d, s["heads"] * s["hd"]), "fan_in"),
        "attn/wk": ((d, s["kv"] * s["hd"]), "fan_in"),
        "attn/wv": ((d, s["kv"] * s["hd"]), "fan_in"),
        "attn/wo": ((s["heads"] * s["hd"], d), "fan_in"),
        "attn/bq": ((s["heads"] * s["hd"],), "bias"),
        "attn/bk": ((s["kv"] * s["hd"],), "bias"),
        "attn/bv": ((s["kv"] * s["hd"],), "bias"),
        "attn/bo": ((d,), "bias"),
        "mlp_norm/scale": ((d,), "scale"),
        "mlp_norm/bias": ((d,), "bias"),
        "mlp/w_up": ((d, s["ff"]), "fan_in"),
        "mlp/w_down": ((s["ff"], d), "fan_in"),
        "mlp/b_up": ((s["ff"],), "bias"),
        "mlp/b_down": ((d,), "bias"),
    }
    for name, (shape, rule) in block.items():
        out[f"blocks/{name}"] = ((L,) + shape, dt, rule, True)
    out["final_norm/scale"] = ((d,), dt, "scale", False)
    out["final_norm/bias"] = ((d,), dt, "bias", False)
    if not c["tie_word_embeddings"]:
        out["lm_head/w"] = ((d, s["vocab"]), dt, "fan_in", False)
    return out


# ----------------------------------------------------------------- reference
def _layernorm(x, scale, bias, eps):
    mu = jnp.mean(x, -1, keepdims=True)
    var = jnp.mean((x - mu) ** 2, -1, keepdims=True)
    return (x - mu) * jax.lax.rsqrt(var + eps) * scale + bias


def _rope(x, theta):
    """x (B, T, heads, hd): rotate each position's halves by its angles."""
    T, hd = x.shape[1], x.shape[-1]
    half = hd // 2
    inv = theta ** (-np.arange(half, dtype=np.float64) / half)
    ang = np.arange(T, dtype=np.float64)[:, None] * inv[None, :]
    sin = jnp.asarray(np.sin(ang), jnp.float32)[None, :, None, :]
    cos = jnp.asarray(np.cos(ang), jnp.float32)[None, :, None, :]
    x1, x2 = x[..., :half], x[..., half:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)


def embed(c: dict, g: dict, tokens):
    return jnp.take(g["embed/tokens"], tokens, axis=0)


def layer(c: dict, p: dict, x, *, mode: str = "f32"):
    """One block over (B, T, d) float32; T a multiple of ``Q_BLOCK`` or
    smaller than it."""
    s = sizes(c)
    mm = quant.matmul(mode)
    eps = c["norm_epsilon"]
    B_, T, d = x.shape
    H, KV, hd = s["heads"], s["kv"], s["hd"]
    h = _layernorm(x, p["attn_norm/scale"], p["attn_norm/bias"], eps)
    q = mm(h, p["attn/wq"]) + p["attn/bq"]
    k = mm(h, p["attn/wk"]) + p["attn/bk"]
    v = mm(h, p["attn/wv"]) + p["attn/bv"]
    q = _rope(q.reshape(B_, T, H, hd), c["rope_theta"])
    k = _rope(k.reshape(B_, T, KV, hd), c["rope_theta"])
    v = v.reshape(B_, T, KV, hd)
    q = q.reshape(B_, T, KV, H // KV, hd)
    qb = min(Q_BLOCK, T)
    blocks = q.reshape(B_, T // qb, qb, KV, H // KV, hd).swapaxes(0, 1)
    keys = jnp.arange(T)

    def attend(args):
        qi, i = args
        sc = quant.einsum(mode, "bqkgd,bskd->bkgqs", qi, k) * hd**-0.5
        pos = i * qb + jnp.arange(qb)
        sc = jnp.where(keys[None, :] <= pos[:, None], sc, -jnp.inf)
        w = jax.nn.softmax(sc, axis=-1)
        return quant.einsum(mode, "bkgqs,bskd->bqkgd", w, v)

    o = jax.lax.map(attend, (blocks, jnp.arange(T // qb)))
    o = o.swapaxes(0, 1).reshape(B_, T, H * hd)
    x = x + mm(o, p["attn/wo"]) + p["attn/bo"]
    h = _layernorm(x, p["mlp_norm/scale"], p["mlp_norm/bias"], eps)
    h = jax.nn.gelu(mm(h, p["mlp/w_up"]) + p["mlp/b_up"], approximate=True)
    return x + mm(h, p["mlp/w_down"]) + p["mlp/b_down"]


def head(c: dict, g: dict, x, *, mode: str = "f32"):
    x = _layernorm(x, g["final_norm/scale"], g["final_norm/bias"],
                   c["norm_epsilon"])
    w = g["embed/tokens"].T if c["tie_word_embeddings"] else g["lm_head/w"]
    return quant.matmul(mode)(x, w)


# ------------------------------------------------------------------ counters
def weight_bytes(c: dict) -> int:
    return sum(
        int(np.prod(shape)) * np.dtype(_np(dt)).itemsize
        for shape, dt, _, _ in layout(c).values()
    )


def _kv_entry_bytes(c: dict) -> int:
    """K and V of one position, over every layer."""
    s = sizes(c)
    return s["layers"] * 2 * s["kv"] * s["hd"] * np.dtype(_np(c["dtype"])).itemsize


def slot_state_bytes(c: dict, cache_len: int) -> int:
    return cache_len * _kv_entry_bytes(c) + 4


def step_bytes(c: dict, positions) -> int:
    """Bytes one decode step needs: the weights once and, for each active
    slot at position p, its keys and values 0..p read and entry p written."""
    e = _kv_entry_bytes(c)
    return weight_bytes(c) + sum((p + 2) * e for p in positions)


def _matmul_flops(c: dict) -> int:
    s = sizes(c)
    per_layer = (
        2 * s["d"] * (s["heads"] + 2 * s["kv"]) * s["hd"]
        + 2 * s["heads"] * s["hd"] * s["d"]
        + 4 * s["d"] * s["ff"]
    )
    return s["layers"] * per_layer


def _attn_flops(c: dict, keys: int) -> int:
    """Scores and weighted values of one query over ``keys`` keys."""
    s = sizes(c)
    return s["layers"] * 4 * s["heads"] * s["hd"] * keys


def prefill_flops(c: dict, prompt_len: int) -> int:
    s = sizes(c)
    causal_keys = prompt_len * (prompt_len + 1) // 2
    return (
        prompt_len * _matmul_flops(c) + _attn_flops(c, causal_keys)
        + 2 * s["d"] * s["vocab"]
    )


def prefill_bytes(c: dict, prompt_len: int) -> int:
    """Bytes a prompt needs: the weights once, and its keys and values."""
    return weight_bytes(c) + prompt_len * _kv_entry_bytes(c)


def decode_flops(c: dict, position: int) -> int:
    s = sizes(c)
    return _matmul_flops(c) + _attn_flops(c, position + 1) + 2 * s["d"] * s["vocab"]
