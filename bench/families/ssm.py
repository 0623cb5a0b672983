"""Mamba-2 (SSD) family: parameter layout, weight rules, a plain float32
reference forward, and the operations and bytes that the metrics count.

Written from the Mamba-2 paper (arXiv:2405.21060, section 7, "the Mamba-2
block") and the published ``mamba_ssm`` layer, not from the program under
test. The reference runs the selective-state recurrence one token at a time,
in float32 at ``highest`` matmul precision: no chunked scan, no cache, no
batching tricks. Only the parameter names follow the program's checkpoint
layout, as a loader of a published checkpoint follows its key names.

Block, per layer (x is the residual stream):

    h = rmsnorm(x) ; [z | xBC | dt] = h @ in_proj
    xBC = silu(causal_depthwise_conv(xBC) + conv_b) ; [xs | B | C] = xBC
    dt = softplus(dt + dt_bias) ; A = -exp(A_log)
    s_t = exp(dt_t A) s_{t-1} + dt_t xs_t B_t^T ; y_t = s_t C_t + D xs_t
    x = x + rmsnorm(y * silu(z)) @ out_proj
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

from bench.harness import quant


def sizes(c: dict) -> dict:
    """Widths; ``vocab`` counts the embedding's rows, padded up to
    ``pad_vocab_size_multiple`` as the published checkpoint pads them, and
    ``tokens`` the vocabulary that prompts draw from."""
    d = c["d_model"]
    inner = c["expand"] * d
    heads = inner // c["headdim"]
    n, g = c["d_state"], c["ngroups"]
    conv_ch = inner + 2 * g * n
    pad = c.get("pad_vocab_size_multiple", 1)
    return dict(
        d=d, inner=inner, heads=heads, p=c["headdim"], n=n, g=g,
        k=c["d_conv"], conv_ch=conv_ch, in_proj=2 * inner + 2 * g * n + heads,
        layers=c["n_layer"], vocab=-(-c["vocab_size"] // pad) * pad,
        tokens=c["vocab_size"],
    )


def program_overrides(c: dict) -> dict:
    """The program's ``ModelConfig`` fields that this file states."""
    return dict(
        family="ssm", num_layers=c["n_layer"], d_model=c["d_model"],
        num_heads=0, num_kv_heads=0, d_ff=0, vocab_size=sizes(c)["vocab"],
        ssm_state=c["d_state"], ssm_head_dim=c["headdim"],
        ssm_expand=c["expand"], ssm_conv=c["d_conv"],
        ssm_groups=c["ngroups"], ssm_chunk=c["chunk_size"],
        norm_eps=c["norm_epsilon"], tie_embeddings=c["tie_embeddings"],
        dtype=c["dtype"],
    )


def layout(c: dict) -> dict:
    """name -> (shape, dtype, rule, stacked). ``rule`` names how the
    benchmark draws the leaf (``bench.harness.weights``)."""
    s = sizes(c)
    dt, L = c["dtype"], s["layers"]
    out = {"embed/tokens": ((s["vocab"], s["d"]), dt, "normal:0.02", False)}
    block = {
        "norm/scale": ((s["d"],), dt, "scale"),
        "in_proj/w": ((s["d"], s["in_proj"]), dt, "fan_in"),
        "conv/w": ((s["k"], s["conv_ch"]), dt, "fan_in_k"),
        "conv/b": ((s["conv_ch"],), dt, "bias"),
        "A_log": ((s["heads"],), "float32", "a_log"),
        "dt_bias": ((s["heads"],), "float32", "dt_bias"),
        "D": ((s["heads"],), "float32", "skip_d"),
        "gate_norm/scale": ((s["inner"],), dt, "scale"),
        "out_proj/w": ((s["inner"], s["d"]), dt, "fan_in"),
    }
    for name, (shape, dtype, rule) in block.items():
        out[f"blocks/{name}"] = ((L,) + shape, dtype, rule, True)
    out["final_norm/scale"] = ((s["d"],), dt, "scale", False)
    if not c["tie_embeddings"]:
        out["lm_head/w"] = ((s["d"], s["vocab"]), dt, "fan_in", False)
    return out


# ----------------------------------------------------------------- reference
def _rmsnorm(x, scale, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) * scale


def embed(c: dict, g: dict, tokens):
    return jnp.take(g["embed/tokens"], tokens, axis=0)


def layer(c: dict, p: dict, x, *, mode: str = "f32"):
    """One block over (B, T, d) float32, from position 0 with no state."""
    s = sizes(c)
    mm = quant.matmul(mode)
    B_, T, _ = x.shape
    H, P, N, G, K = s["heads"], s["p"], s["n"], s["g"], s["k"]
    h = _rmsnorm(x, p["norm/scale"], c["norm_epsilon"])
    proj = mm(h, p["in_proj/w"])
    z = proj[..., : s["inner"]]
    xbc = proj[..., s["inner"]: s["inner"] + s["conv_ch"]]
    dt = proj[..., s["inner"] + s["conv_ch"]:]
    padded = jnp.pad(xbc, ((0, 0), (K - 1, 0), (0, 0)))
    conv = sum(padded[:, i: i + T] * p["conv/w"][i] for i in range(K))
    xbc = jax.nn.silu(conv + p["conv/b"])
    xs = xbc[..., : s["inner"]].reshape(B_, T, H, P)
    Bm = xbc[..., s["inner"]: s["inner"] + G * N].reshape(B_, T, G, N)
    Cm = xbc[..., s["inner"] + G * N:].reshape(B_, T, G, N)
    Bm = jnp.repeat(Bm, H // G, axis=2)
    Cm = jnp.repeat(Cm, H // G, axis=2)
    dt = jax.nn.softplus(dt + p["dt_bias"])                   # (B, T, H)
    A = -jnp.exp(p["A_log"])

    def step(state, inp):
        x_t, b_t, c_t, dt_t = inp                             # per token
        state = state * jnp.exp(dt_t * A)[..., None, None] + jnp.einsum(
            "bhp,bhn->bhpn", x_t * dt_t[..., None], b_t
        )
        return state, jnp.einsum("bhpn,bhn->bhp", state, c_t)

    state0 = jnp.zeros((B_, H, P, N), jnp.float32)
    seq = tuple(jnp.moveaxis(a, 1, 0) for a in (xs, Bm, Cm, dt))
    _, y = jax.lax.scan(step, state0, seq)
    y = jnp.moveaxis(y, 0, 1) + xs * p["D"][:, None]
    y = y.reshape(B_, T, s["inner"]) * jax.nn.silu(z)
    y = _rmsnorm(y, p["gate_norm/scale"], c["norm_epsilon"])
    return x + mm(y, p["out_proj/w"])


def head(c: dict, g: dict, x, *, mode: str = "f32"):
    """Logits (..., V) from final hidden states (..., d)."""
    x = _rmsnorm(x, g["final_norm/scale"], c["norm_epsilon"])
    w = g["embed/tokens"].T if c["tie_embeddings"] else g["lm_head/w"]
    return quant.matmul(mode)(x, w)


# ------------------------------------------------------------------ counters
def weight_bytes(c: dict) -> int:
    return sum(
        int(np.prod(shape)) * np.dtype(_np(dt)).itemsize
        for shape, dt, _, _ in layout(c).values()
    )


def slot_state_bytes(c: dict, cache_len: int = 0) -> int:
    """One decode slot's state: conv history and SSM state per layer, and
    the slot's position."""
    s = sizes(c)
    conv = (s["k"] - 1) * s["conv_ch"] * np.dtype(_np(c["dtype"])).itemsize
    ssm = s["heads"] * s["p"] * s["n"] * 4
    return s["layers"] * (conv + ssm) + 4


def step_bytes(c: dict, positions) -> int:
    """Bytes one decode step needs to move: the weights once, and each
    active slot's state read and written. ``positions`` lists the active
    slots' positions; an SSM's state does not grow with them."""
    return weight_bytes(c) + 2 * len(positions) * slot_state_bytes(c)


def _token_flops(c: dict) -> int:
    s = sizes(c)
    per_layer = (
        2 * s["d"] * s["in_proj"] + 2 * s["inner"] * s["d"]
        + 2 * s["k"] * s["conv_ch"]
        + 5 * s["heads"] * s["p"] * s["n"]    # decay, outer product, C read
    )
    return s["layers"] * per_layer


def prefill_flops(c: dict, prompt_len: int) -> int:
    """Operations a prompt needs: every token through every block, and the
    logits of its last position."""
    s = sizes(c)
    return prompt_len * _token_flops(c) + 2 * s["d"] * s["vocab"]


def prefill_bytes(c: dict, prompt_len: int) -> int:
    """Bytes a prompt needs: the weights once, and the slot state written."""
    return weight_bytes(c) + slot_state_bytes(c)


def decode_flops(c: dict, position: int) -> int:
    s = sizes(c)
    return _token_flops(c) + 2 * s["d"] * s["vocab"]


def _np(name: str):
    if name == "bfloat16":
        import ml_dtypes

        return ml_dtypes.bfloat16
    return np.dtype(name)
