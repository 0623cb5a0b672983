"""Mamba2 — SSD (state-space duality) blocks [arXiv:2405.21060].

Training/prefill uses the chunked SSD algorithm (quadratic within
``ssm_chunk``-sized chunks, recurrent across chunks — the paper's Listing 1
adapted to JAX with stacked-layer ``lax.scan``). Decode is the O(1)/token
recurrence — this is what makes the arch long_500k-capable.

Layer layout (per block, stacked on L):
    norm -> in_proj -> [z | x | B | C | dt] -> causal depthwise conv (x,B,C)
         -> SSD -> +D*x -> gated RMSNorm(silu(z)) -> out_proj
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

from .common import cross_entropy, rms_norm
from .runtime import layer_loop, remat_wrap, scans_unrolled
from .specs import ParamSpec

NEG_INF = -2.0**30


# --------------------------------------------------------------------------
# dims
# --------------------------------------------------------------------------
def dims(cfg):
    d_inner = cfg.ssm_inner
    H = cfg.ssm_heads
    P = cfg.ssm_head_dim
    G = cfg.ssm_groups
    N = cfg.ssm_state
    conv_ch = d_inner + 2 * G * N
    d_in_proj = 2 * d_inner + 2 * G * N + H
    return d_inner, H, P, G, N, conv_ch, d_in_proj


# --------------------------------------------------------------------------
# specs
# --------------------------------------------------------------------------
def block_specs(cfg) -> dict[str, ParamSpec]:
    d = cfg.d_model
    dt = cfg.dtype
    d_inner, H, P, G, N, conv_ch, d_in_proj = dims(cfg)
    return {
        "norm/scale": ParamSpec((d,), dt, ("embed",), "ones"),
        "in_proj/w": ParamSpec((d, d_in_proj), dt, ("embed", "ssm_inner"), "fan_in"),
        "conv/w": ParamSpec((cfg.ssm_conv, conv_ch), dt,
                            ("conv_kernel", "ssm_inner"), "normal"),
        "conv/b": ParamSpec((conv_ch,), dt, ("ssm_inner",), "zeros"),
        "A_log": ParamSpec((H,), "float32", ("ssm_heads",), "ones"),
        "dt_bias": ParamSpec((H,), "float32", ("ssm_heads",), "zeros"),
        "D": ParamSpec((H,), "float32", ("ssm_heads",), "ones"),
        "gate_norm/scale": ParamSpec((d_inner,), dt, ("ssm_inner",), "ones"),
        "out_proj/w": ParamSpec((d_inner, d), dt, ("ssm_inner", "embed"), "fan_in"),
    }


def param_specs(cfg) -> dict[str, ParamSpec]:
    d, V, dt = cfg.d_model, cfg.vocab_size, cfg.dtype
    specs = {
        "embed/tokens": ParamSpec((V, d), dt, ("vocab", "embed"), "normal"),
    }
    t = block_specs(cfg)
    specs.update(
        {
            f"blocks/{n}": ParamSpec(
                (cfg.num_layers,) + s.shape, s.dtype, ("layers",) + s.axes, s.init
            )
            for n, s in t.items()
        }
    )
    specs["final_norm/scale"] = ParamSpec((d,), dt, ("embed",), "ones")
    if not cfg.tie_embeddings:
        specs["lm_head/w"] = ParamSpec((d, V), dt, ("embed", "vocab"), "fan_in")
    return specs


# --------------------------------------------------------------------------
# SSD core
# --------------------------------------------------------------------------
def _segsum(x: jax.Array) -> jax.Array:
    """(..., T) -> (..., T, T) with out[i,j] = sum_{j<k<=i} x[k]; -inf above diag."""
    T = x.shape[-1]
    cs = jnp.cumsum(x, -1)
    ss = cs[..., :, None] - cs[..., None, :]
    mask = jnp.tril(jnp.ones((T, T), bool))
    return jnp.where(mask, ss, NEG_INF)


def ssd(x, a, B, C, *, chunk: int, initial_state=None):
    """Chunked SSD scan.

    x: (b, s, h, p)   — dt-premultiplied inputs
    a: (b, s, h)      — per-step log decays (A * dt, negative)
    B, C: (b, s, h, n) — already head-expanded
    Returns (y: (b, s, h, p), final_state: (b, h, p, n)). f32 internally.
    """
    b, s, h, p = x.shape
    n = B.shape[-1]
    pad = (-s) % chunk
    if pad:
        x = jnp.pad(x, ((0, 0), (0, pad), (0, 0), (0, 0)))
        a = jnp.pad(a, ((0, 0), (0, pad), (0, 0)))
        B = jnp.pad(B, ((0, 0), (0, pad), (0, 0), (0, 0)))
        C = jnp.pad(C, ((0, 0), (0, pad), (0, 0), (0, 0)))
    sp = s + pad
    c = sp // chunk
    # operands stay in their input dtype (bf16 on TPU -> MXU matmuls);
    # accumulation is forced to f32 via preferred_element_type. Decay
    # chains are always f32 (exp/cumsum numerics). §Perf hillclimb A.
    f32 = jnp.float32
    x = x.reshape(b, c, chunk, h, p)
    B = B.reshape(b, c, chunk, h, n)
    C = C.reshape(b, c, chunk, h, n)
    a = a.reshape(b, c, chunk, h).transpose(0, 3, 1, 2).astype(f32)
    a_cum = jnp.cumsum(a, -1)                                  # (b,h,c,l)

    # 1. intra-chunk (quadratic within chunk)
    L = jnp.exp(_segsum(a))                                    # (b,h,c,l,l)
    g = jnp.einsum(
        "bclhn,bcshn->bhcls", C, B, preferred_element_type=f32
    )
    y_diag = jnp.einsum(
        "bhcls,bcshp->bclhp", (g * L).astype(x.dtype), x,
        preferred_element_type=f32,
    )

    # 2. chunk-final states
    decay_states = jnp.exp(a_cum[..., -1:] - a_cum)            # (b,h,c,l)
    states = jnp.einsum(
        "bclhn,bhcl,bclhp->bchpn", B, decay_states.astype(B.dtype), x,
        preferred_element_type=f32,
    )

    # 3. inter-chunk recurrence
    if initial_state is None:
        initial_state = jnp.zeros((b, h, p, n), f32)
    states = jnp.concatenate(
        [initial_state[:, None].astype(states.dtype), states], 1
    )                                                          # (b,c+1,...)
    chunk_decay = a_cum[..., -1]                               # (b,h,c)
    dc = jnp.exp(
        _segsum(jnp.pad(chunk_decay, ((0, 0), (0, 0), (1, 0))))
    )                                                          # (b,h,c+1,c+1)
    new_states = jnp.einsum(
        "bhzc,bchpn->bzhpn", dc, states, preferred_element_type=f32
    )
    states, final = new_states[:, :-1], new_states[:, -1]

    # 4. state -> output
    out_decay = jnp.exp(a_cum)                                 # (b,h,c,l)
    y_off = jnp.einsum(
        "bclhn,bchpn,bhcl->bclhp", C, states.astype(C.dtype),
        out_decay.astype(C.dtype), preferred_element_type=f32,
    )

    y = (y_diag + y_off).reshape(b, sp, h, p)[:, :s]
    return y, final


def ssd_ref(x, a, B, C, *, initial_state=None):
    """Sequential O(s) oracle for tests."""
    b, s, h, p = x.shape
    n = B.shape[-1]
    state = (
        jnp.zeros((b, h, p, n), jnp.float32)
        if initial_state is None
        else initial_state.astype(jnp.float32)
    )
    ys = []
    for t in range(s):
        da = jnp.exp(a[:, t].astype(jnp.float32))              # (b,h)
        state = state * da[..., None, None] + jnp.einsum(
            "bhp,bhn->bhpn", x[:, t].astype(jnp.float32), B[:, t].astype(jnp.float32)
        )
        ys.append(jnp.einsum("bhpn,bhn->bhp", state, C[:, t].astype(jnp.float32)))
    return jnp.stack(ys, 1), state


# --------------------------------------------------------------------------
# block forward
# --------------------------------------------------------------------------
def _split_proj(cfg, proj):
    d_inner, H, P, G, N, conv_ch, _ = dims(cfg)
    z, xBC, dt = jnp.split(proj, [d_inner, d_inner + conv_ch], axis=-1)
    return z, xBC, dt


def _causal_conv(xBC, w, b_, conv_state=None):
    """Depthwise causal conv along S. xBC (B,S,C); w (K,C).

    With ``conv_state`` (B,K-1,C) the sequence is prepended (decode path /
    chunked prefill continuation); otherwise zero history.
    """
    K = w.shape[0]
    if conv_state is None:
        conv_state = jnp.zeros((xBC.shape[0], K - 1, xBC.shape[2]), xBC.dtype)
    xt = jnp.concatenate([conv_state, xBC], 1)
    out = sum(
        xt[:, i : i + xBC.shape[1]] * w[i] for i in range(K)
    )
    return out + b_, xt[:, -(K - 1):]


def mamba_block(cfg, p, x, *, state=None, conv_state=None, return_state=False):
    """Full-sequence mamba2 block. x (B,S,d) -> (B,S,d) [+ states]."""
    from repro.dist.context import constrain

    x = constrain(x, ("batch", "seq", None))
    # FSDP weight unsharding at use-site (see transformer._gather_weights)
    tmpl = block_specs(cfg)
    p = {
        n: (
            constrain(
                a,
                tuple(None if ax == "embed" else ax for ax in tmpl[n].axes),
            )
            if n in tmpl
            else a
        )
        for n, a in p.items()
    }
    d_inner, H, P, G, N, conv_ch, _ = dims(cfg)
    B_, S, _ = x.shape
    h = rms_norm(x, p["norm/scale"], cfg.norm_eps)
    proj = h @ p["in_proj/w"]
    z, xBC, dt_raw = _split_proj(cfg, proj)
    xBC, new_conv = _causal_conv(xBC, p["conv/w"], p["conv/b"], conv_state)
    xBC = jax.nn.silu(xBC)
    xs, Bc, Cc = jnp.split(xBC, [d_inner, d_inner + G * N], axis=-1)
    xs = xs.reshape(B_, S, H, P)
    Bc = jnp.repeat(Bc.reshape(B_, S, G, N), H // G, axis=2)
    Cc = jnp.repeat(Cc.reshape(B_, S, G, N), H // G, axis=2)
    dt = jax.nn.softplus(dt_raw.astype(jnp.float32) + p["dt_bias"])   # (B,S,H)
    A = -jnp.exp(p["A_log"])                                          # (H,)
    y, final = ssd(
        xs * dt[..., None].astype(xs.dtype),
        dt * A,
        Bc,
        Cc,
        chunk=cfg.ssm_chunk,
        initial_state=state,
    )
    y = y + xs.astype(jnp.float32) * p["D"][:, None]
    y = y.reshape(B_, S, d_inner).astype(x.dtype)
    y = rms_norm(y * jax.nn.silu(z), p["gate_norm/scale"], cfg.norm_eps)
    out = x + y @ p["out_proj/w"]
    if return_state:
        return out, final, new_conv
    return out


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


def mamba_block_decode(cfg, p, x, conv_state, ssm_state):
    """One-token recurrence. x (B,1,d). Returns the output, the new conv
    state, and the token's step of the SSM state, ``(da, xdt, B)``:
    ``ssm_advance(ssm_state, *step)`` is the new SSM state, which the
    output reads without storing it."""
    d_inner, H, P, G, N, conv_ch, _ = dims(cfg)
    B_ = x.shape[0]
    h = rms_norm(x, p["norm/scale"], cfg.norm_eps)
    proj = h @ p["in_proj/w"]
    z, xBC, dt_raw = _split_proj(cfg, proj)
    # conv: shift register
    window = jnp.concatenate([conv_state, xBC], 1)              # (B,K,C)
    xBC = (window * p["conv/w"]).sum(1, keepdims=True) + p["conv/b"]
    new_conv = window[:, 1:]
    xBC = jax.nn.silu(xBC)
    xs, Bc, Cc = jnp.split(xBC, [d_inner, d_inner + G * N], axis=-1)
    xs = xs.reshape(B_, H, P).astype(jnp.float32)
    Bc = Bc.reshape(B_, G, N).astype(jnp.float32)
    Cc = jnp.repeat(Cc.reshape(B_, G, N), H // G, axis=1).astype(jnp.float32)
    dt = jax.nn.softplus(dt_raw[:, 0].astype(jnp.float32) + p["dt_bias"])  # (B,H)
    A = -jnp.exp(p["A_log"])
    step = (jnp.exp(dt * A), xs * dt[..., None], Bc)            # da (B,H)
    new_state = ssm_advance(ssm_state, *step)
    y = jnp.einsum("bhpn,bhn->bhp", new_state, Cc) + xs * p["D"][:, None]
    y = y.reshape(B_, 1, d_inner).astype(x.dtype)
    y = rms_norm(y * jax.nn.silu(z), p["gate_norm/scale"], cfg.norm_eps)
    return x + y @ p["out_proj/w"], new_conv, step


# --------------------------------------------------------------------------
# model entry points
# --------------------------------------------------------------------------
def _stacked(params, prefix="blocks"):
    plen = len(prefix) + 1
    return {n[plen:]: a for n, a in params.items() if n.startswith(prefix + "/")}


def logits_fn(cfg, params, x):
    from repro.dist.context import constrain

    x = rms_norm(x, params["final_norm/scale"], cfg.norm_eps)
    logits = (
        x @ params["embed/tokens"].T
        if cfg.tie_embeddings
        else x @ params["lm_head/w"]
    )
    return constrain(logits, ("batch", "seq", "vocab"))


def forward(cfg, params, batch, *, impl: str = "chunked"):
    x = jnp.take(params["embed/tokens"], batch["tokens"], axis=0)
    stacked = _stacked(params)

    def body(h, p):
        return mamba_block(cfg, p, h), None

    body = remat_wrap(body, cfg)
    if scans_unrolled():
        for i in range(cfg.num_layers):
            x, _ = body(x, {n: a[i] for n, a in stacked.items()})
    else:
        x, _ = jax.lax.scan(body, x, stacked)
    return logits_fn(cfg, params, x), jnp.float32(0.0)


def loss_fn(cfg, params, batch, *, impl: str = "chunked", aux_coef=0.0):
    logits, _ = forward(cfg, params, batch, impl=impl)
    return cross_entropy(logits, batch["labels"])


def cache_spec(cfg, batch: int, seq_len: int):
    d_inner, H, P, G, N, conv_ch, _ = dims(cfg)
    L, K = cfg.num_layers, cfg.ssm_conv
    shapes = {
        "conv": jax.ShapeDtypeStruct(
            (L, batch, K - 1, conv_ch), jnp.dtype(cfg.dtype)
        ),
        "ssm": jax.ShapeDtypeStruct((L, batch, H, P, N), jnp.float32),
        "pos": jax.ShapeDtypeStruct((), jnp.int32),
    }
    axes = {
        "conv": ("layers", "batch", None, "ssm_inner"),
        "ssm": ("layers", "batch", "ssm_heads", "head_dim", "ssm_state"),
        "pos": (),
    }
    return shapes, axes


def init_cache(cfg, batch: int, seq_len: int):
    shapes, _ = cache_spec(cfg, batch, seq_len)
    return {k: jnp.zeros(s.shape, s.dtype) for k, s in shapes.items()}


def prefill(cfg, params, batch, *, impl: str = "chunked", cache_len=None):
    tokens = batch["tokens"]
    x = jnp.take(params["embed/tokens"], tokens, axis=0)
    stacked = _stacked(params)

    def body(h, p):
        h, final, conv = mamba_block(cfg, p, h, return_state=True)
        return h, (conv, final)

    body = remat_wrap(body, cfg)
    if scans_unrolled():
        outs = []
        for i in range(cfg.num_layers):
            x, o = body(x, {n: a[i] for n, a in stacked.items()})
            outs.append(o)
        convs = jnp.stack([o[0] for o in outs])
        ssms = jnp.stack([o[1] for o in outs])
    else:
        x, (convs, ssms) = jax.lax.scan(body, x, stacked)
    cache = {"conv": convs, "ssm": ssms, "pos": jnp.int32(tokens.shape[1] - 1)}
    return logits_fn(cfg, params, x[:, -1:, :]), cache


def decode_layers(cfg, params, x, conv, ssm, lo: int, hi: int):
    """Mamba layers ``[lo, hi)`` of one decode step, on the whole stacked
    caches. ``conv`` is carried through the layer loop, and each layer
    writes its new conv state into it in place. Each layer reads its SSM
    state from ``ssm`` and returns its step; the caller advances the
    whole ``ssm`` cache by the stacked steps once (``ssm_advance``), in
    place, after the loop: a layer that wrote its state back would have
    to copy it, since it also reads it."""
    sub = {n: a[lo:hi] for n, a in _stacked(params).items()}

    def body(carry, p, i):
        h, conv = carry
        h, c, step = mamba_block_decode(
            cfg, p, h,
            jax.lax.dynamic_index_in_dim(conv, i, 0, keepdims=False),
            jax.lax.dynamic_index_in_dim(ssm, i, 0, keepdims=False),
        )
        return (h, jax.lax.dynamic_update_index_in_dim(conv, c, i, 0)), step

    (x, conv), steps = layer_loop(body, (x, conv), sub, lo)
    return x, conv, steps


def decode_step(cfg, params, cache, tokens):
    """tokens (B,1) + cache -> (logits (B,1,V), cache'). ``pos`` is a
    scalar or one position per row (B,); the state does not read it."""
    x = jnp.take(params["embed/tokens"], tokens, axis=0)
    x, conv, steps = decode_layers(
        cfg, params, x, cache["conv"], cache["ssm"], 0, cfg.num_layers
    )
    logits = logits_fn(cfg, params, x)
    return logits, {"conv": conv, "ssm": ssm_advance(cache["ssm"], *steps),
                    "pos": cache["pos"] + 1}
