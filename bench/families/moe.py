"""Routed-expert decoder family (OLMoE) as one chip's share of an
expert-parallel replica: parameter layout, weight rules, a plain float32
reference forward, and the operations and bytes that the metrics count.

Written from the OLMoE paper (arXiv:2409.02060, section 2 and appendix B)
and the published ``OlmoeForCausalLM`` layer: pre-norm blocks with
RMSNorm; multi-head attention whose query and key projections are each
RMS-normalised over their whole projected width before the split into
heads, with rotary embeddings over the whole head (half-split rotation);
a sparse MoE block whose router scores every expert by a softmax and keeps
the top ``k`` weights as they are (``norm_topk_prob`` false), each expert
a SwiGLU MLP; no biases; an untied output head.

This chip holds ``num_experts`` of the ``num_experts_published`` experts
of every layer, from ``first_held_expert`` on. The router keeps its
published width and top-k; a token's weight on an expert held elsewhere
adds nothing here, in the program and in this reference alike. The
reference computes every held expert densely over every token and weights
it by the routing (zero where the token did not choose it): no sort, no
groups, no capacity. The attention's rotation and block size come from
``bench/families/dense.py``. Only the parameter names follow the
program's checkpoint layout.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

from bench.families.dense import Q_BLOCK, _rope
from bench.families.ssm import _np
from bench.harness import quant


def sizes(c: dict) -> dict:
    hd = c["hidden_size"] // c["num_attention_heads"]
    return dict(
        d=c["hidden_size"], heads=c["num_attention_heads"],
        kv=c["num_key_value_heads"], hd=hd, ff=c["intermediate_size"],
        layers=c["num_hidden_layers"], vocab=c["vocab_size"],
        tokens=c["vocab_size"], experts=c["num_experts_published"],
        held=c["num_experts"], first=c["first_held_expert"],
        k=c["num_experts_per_tok"],
    )


def program_overrides(c: dict) -> dict:
    s = sizes(c)
    return dict(
        family="moe", num_layers=s["layers"], d_model=s["d"],
        num_heads=s["heads"], num_kv_heads=s["kv"], head_dim=0,
        d_ff=s["ff"], vocab_size=s["vocab"], num_experts=s["experts"],
        experts_per_token=s["k"], experts_held=s["held"],
        first_expert=s["first"], norm_topk_prob=c["norm_topk_prob"],
        num_shared_experts=0, qk_norm=True, qk_norm_width="full",
        use_bias=False, qkv_bias=False, act="silu",
        rope_theta=c["rope_theta"], norm_eps=c["rms_norm_eps"],
        tie_embeddings=c["tie_word_embeddings"], sliding_window=0,
        dtype=c["dtype"],
    )


def layout(c: dict) -> dict:
    s = sizes(c)
    dt, L, d, ff, E_h = c["dtype"], s["layers"], s["d"], s["ff"], s["held"]
    out = {"embed/tokens": ((s["vocab"], d), dt, "normal:0.02", False)}
    block = {
        "attn_norm/scale": ((d,), "scale"),
        "attn/wq": ((d, s["heads"] * s["hd"]), "fan_in"),
        "attn/wk": ((d, s["kv"] * s["hd"]), "fan_in"),
        "attn/wv": ((d, s["kv"] * s["hd"]), "fan_in"),
        "attn/wo": ((s["heads"] * s["hd"], d), "fan_in"),
        "attn/q_norm": ((s["heads"] * s["hd"],), "scale"),
        "attn/k_norm": ((s["kv"] * s["hd"],), "scale"),
        "mlp_norm/scale": ((d,), "scale"),
        "router/w": ((d, s["experts"]), "fan_in"),
        "experts/w_gate": ((E_h, d, ff), "fan_in"),
        "experts/w_up": ((E_h, d, ff), "fan_in"),
        "experts/w_down": ((E_h, ff, d), "fan_in"),
    }
    for name, (shape, rule) in block.items():
        out[f"blocks/{name}"] = ((L,) + shape, dt, rule, True)
    out["final_norm/scale"] = ((d,), dt, "scale", False)
    if not c["tie_word_embeddings"]:
        out["lm_head/w"] = ((d, s["vocab"]), dt, "fan_in", False)
    return out


# ----------------------------------------------------------------- reference
def _rmsnorm(x, scale, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) * scale


def embed(c: dict, g: dict, tokens):
    return jnp.take(g["embed/tokens"], tokens, axis=0)


def _attention(c, q, k, v, mode):
    """Causal attention of (B, T, H, hd) queries over (B, T, KV, hd) keys
    and values, ``Q_BLOCK`` queries at a time."""
    s = sizes(c)
    B_, T, H, hd = q.shape
    KV = s["kv"]
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
    return o.swapaxes(0, 1).reshape(B_, T, H * hd)


def routing(c: dict, h, router_w, mm):
    """(B, T, E) weights: the softmax's top-k where a token chose the
    expert, renormalised only where the configuration says, else 0."""
    s = sizes(c)
    probs = jax.nn.softmax(mm(h, router_w), axis=-1)
    kth = jax.lax.top_k(probs, s["k"])[0][..., -1:]
    w = jnp.where(probs >= kth, probs, 0.0)
    if c["norm_topk_prob"]:
        w = w / w.sum(-1, keepdims=True)
    return w


def experts(c: dict, p: dict, h, w, mm, held=None):
    """The held experts' part of the MoE output for (B, T, d) ``h`` with
    routing weights ``w`` (B, T, E): each held expert over every token,
    weighted. ``held`` overrides which experts (global ids) the weights
    ``p`` hold, in order."""
    s = sizes(c)
    ids = np.arange(s["first"], s["first"] + s["held"]) if held is None else held

    def one(acc, e):
        wg, wu, wd, eid = e
        y = mm(jax.nn.silu(mm(h, wg)) * mm(h, wu), wd)
        return acc + y * jnp.take(w, eid, axis=-1)[..., None], None

    out, _ = jax.lax.scan(one, jnp.zeros_like(h), (
        p["experts/w_gate"], p["experts/w_up"], p["experts/w_down"],
        jnp.asarray(ids, jnp.int32)))
    return out


def layer(c: dict, p: dict, x, *, mode: str = "f32"):
    """One block over (B, T, d) float32; T a multiple of ``Q_BLOCK`` or
    smaller than it."""
    s = sizes(c)
    mm = quant.matmul(mode)
    eps = c["rms_norm_eps"]
    B_, T, _ = x.shape
    H, KV, hd = s["heads"], s["kv"], s["hd"]
    h = _rmsnorm(x, p["attn_norm/scale"], eps)
    q = _rmsnorm(mm(h, p["attn/wq"]), p["attn/q_norm"], eps)
    k = _rmsnorm(mm(h, p["attn/wk"]), p["attn/k_norm"], eps)
    v = mm(h, p["attn/wv"])
    q = _rope(q.reshape(B_, T, H, hd), c["rope_theta"])
    k = _rope(k.reshape(B_, T, KV, hd), c["rope_theta"])
    o = _attention(c, q, k, v.reshape(B_, T, KV, hd), mode)
    x = x + mm(o, p["attn/wo"])
    h = _rmsnorm(x, p["mlp_norm/scale"], eps)
    return x + experts(c, p, h, routing(c, h, p["router/w"], mm), mm)


def head(c: dict, g: dict, x, *, mode: str = "f32"):
    x = _rmsnorm(x, g["final_norm/scale"], c["rms_norm_eps"])
    w = g["embed/tokens"].T if c["tie_word_embeddings"] else g["lm_head/w"]
    return quant.matmul(mode)(x, w)


# ------------------------------------------------------------------ counters
def _bytes(c: dict) -> int:
    return np.dtype(_np(c["dtype"])).itemsize


def weight_bytes(c: dict) -> int:
    return sum(
        int(np.prod(shape)) * np.dtype(_np(dt)).itemsize
        for shape, dt, _, _ in layout(c).values()
    )


def expert_bytes(c: dict) -> int:
    """One expert's three matrices."""
    s = sizes(c)
    return 3 * s["d"] * s["ff"] * _bytes(c)


def experts_hit(c: dict, n: int) -> float:
    """Held experts of one layer that ``n`` tokens hit, expected under
    uniform routing: E_h (1 - (1 - k/E)^n)."""
    s = sizes(c)
    return s["held"] * (1.0 - (1.0 - s["k"] / s["experts"]) ** n)


def _dense_weight_bytes(c: dict) -> int:
    """Every weight but the held experts'; the head counted, the embedding
    not (a step reads one of its rows per token)."""
    s = sizes(c)
    return (weight_bytes(c) - s["layers"] * s["held"] * expert_bytes(c)
            - s["vocab"] * s["d"] * _bytes(c))


def _weights_read(c: dict, n: int) -> float:
    return _dense_weight_bytes(c) + sizes(c)["layers"] * experts_hit(c, n) \
        * expert_bytes(c)


def _kv_entry_bytes(c: dict) -> int:
    s = sizes(c)
    return s["layers"] * 2 * s["kv"] * s["hd"] * _bytes(c)


def slot_state_bytes(c: dict, cache_len: int) -> int:
    return cache_len * _kv_entry_bytes(c) + 4


def step_bytes(c: dict, positions) -> float:
    """Bytes one decode step needs: the weights outside the held experts
    once, the held experts the active rows hit (expected under uniform
    routing), and for each active slot at position p its keys and values
    0..p read and entry p written."""
    e = _kv_entry_bytes(c)
    return _weights_read(c, len(positions)) + sum((p + 2) * e for p in positions)


def _matmul_flops(c: dict) -> float:
    """One token's matmuls, its held experts by expectation: k E_h / E of
    its k choices land here."""
    s = sizes(c)
    per_layer = (
        2 * s["d"] * (s["heads"] + 2 * s["kv"]) * s["hd"]
        + 2 * s["heads"] * s["hd"] * s["d"]
        + 2 * s["d"] * s["experts"]
        + s["k"] * s["held"] / s["experts"] * 6 * s["d"] * s["ff"]
    )
    return s["layers"] * per_layer


def _attn_flops(c: dict, keys: int) -> int:
    s = sizes(c)
    return s["layers"] * 4 * s["heads"] * s["hd"] * keys


def prefill_flops(c: dict, prompt_len: int) -> float:
    s = sizes(c)
    causal_keys = prompt_len * (prompt_len + 1) // 2
    return (prompt_len * _matmul_flops(c) + _attn_flops(c, causal_keys)
            + 2 * s["d"] * s["vocab"])


def prefill_bytes(c: dict, prompt_len: int) -> float:
    """Bytes a prompt needs: the weights its tokens hit once, and its keys
    and values."""
    return _weights_read(c, prompt_len) + prompt_len * _kv_entry_bytes(c)


def decode_flops(c: dict, position: int) -> float:
    s = sizes(c)
    return _matmul_flops(c) + _attn_flops(c, position + 1) + 2 * s["d"] * s["vocab"]


def gmm_flops(c: dict, rows: int) -> int:
    """The grouped matmuls' operations for ``rows`` (token, held expert)
    pairs: gate, up and down, 2 d ff each."""
    s = sizes(c)
    return 6 * rows * s["d"] * s["ff"]


def gmm_bytes(c: dict, rows: int, hit: int) -> int:
    """The grouped matmuls' bytes: each hit expert's three matrices once,
    and each row in and out of each of the three calls (gate and up read d
    and write ff, down reads ff and writes d)."""
    s = sizes(c)
    return hit * expert_bytes(c) + rows * 3 * (s["d"] + s["ff"]) * _bytes(c)
