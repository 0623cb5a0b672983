"""Uniform model API, dispatched on config family.

    param_specs(cfg)                  -> {name: ParamSpec}   (symbol manifest)
    init_params(cfg, seed)            -> {name: array}
    init_params_np(cfg, seed)         -> {name: np.ndarray}  (no JAX backend)
    loss_fn(cfg, params, batch)       -> scalar
    forward(cfg, params, batch)       -> (logits, aux)
    prefill(cfg, params, batch)       -> (logits, cache[, counts])
    decode_step(cfg, params, cache, tokens) -> (logits, cache[, counts])
    cache_spec / init_cache(cfg, B, S)
    manifest_refs(cfg)                -> [SymbolRef]  (stable-linking imports)
    input_specs(cfg, shape)           -> {name: ShapeDtypeStruct} (dry-run)
    input_axes(cfg, shape)            -> {name: logical axes}
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

from repro.core import SymbolRef

from . import hybrid, mamba2, transformer
from .common import FREE_POS
from .specs import ParamSpec, abstract_params, init_params as _init
from .specs import init_params_np as _init_np
from .specs import param_bytes, param_count


def _mod(cfg):
    if cfg.family == "ssm":
        return mamba2
    if cfg.family == "hybrid":
        return hybrid
    return transformer  # dense / moe / audio / vlm


def param_specs(cfg) -> dict[str, ParamSpec]:
    return _mod(cfg).param_specs(cfg)


def init_params(cfg, seed: int = 0):
    return _init(param_specs(cfg), seed)


def init_params_np(cfg, seed: int = 0):
    return _init_np(param_specs(cfg), seed)


def forward(cfg, params, batch, *, impl="chunked"):
    return _mod(cfg).forward(cfg, params, batch, impl=impl)


def loss_fn(cfg, params, batch, *, impl="chunked"):
    return _mod(cfg).loss_fn(cfg, params, batch, impl=impl)


def prefill(cfg, params, batch, *, impl="chunked", cache_len=None, **moe):
    """(logits, cache); a model with routed experts (transformer family)
    also takes ``counters=True`` and then returns the expert layers'
    counts (``moe.COUNTERS``, summed over layers) as well."""
    return _mod(cfg).prefill(cfg, params, batch, impl=impl,
                             cache_len=cache_len, **moe)


def decode_step(cfg, params, cache, tokens, **moe):
    """(logits, cache); a model with routed experts also takes ``active``
    (B,), the rows its expert layers route, and ``counters``. A row whose
    ``pos`` is ``FREE_POS`` holds no token: its attention reads no cache."""
    return _mod(cfg).decode_step(cfg, params, cache, tokens, **moe)


def cache_spec(cfg, batch, seq_len):
    return _mod(cfg).cache_spec(cfg, batch, seq_len)


def init_cache(cfg, batch, seq_len):
    return _mod(cfg).init_cache(cfg, batch, seq_len)


# ------------------------------------------------------------ stable linking
def manifest_refs(cfg, *, fragment: bool = False) -> list[SymbolRef]:
    """The model's relocation instructions: one SymbolRef per parameter.

    ``fragment=True`` explodes stacked-layer (and per-expert) tensors into
    per-slice references ("blocks/attn/wq[7]", "...w_gate[3][42]") — the
    relocation-count regime of the paper's Pynamic benchmark, and the mode
    that enables per-layer/per-expert interposition."""
    refs: list[SymbolRef] = []
    for name, s in param_specs(cfg).items():
        if fragment and s.axes and s.axes[0] == "layers" and len(s.shape) > 1:
            L = s.shape[0]
            if len(s.axes) > 1 and s.axes[1] == "experts" and len(s.shape) > 2:
                for l in range(L):
                    for e in range(s.shape[1]):
                        refs.append(
                            SymbolRef(
                                f"{name}[{l}][{e}]", tuple(s.shape[2:]), s.dtype
                            )
                        )
            else:
                for l in range(L):
                    refs.append(
                        SymbolRef(f"{name}[{l}]", tuple(s.shape[1:]), s.dtype)
                    )
        else:
            refs.append(SymbolRef(name, tuple(s.shape), s.dtype))
    return refs


def abstract(cfg):
    return abstract_params(param_specs(cfg))


def n_params(cfg) -> int:
    return param_count(param_specs(cfg))


def n_active_params(cfg) -> int:
    """Active parameters per token (MoE discounts inactive experts)."""
    specs = param_specs(cfg)
    total = 0
    for name, s in specs.items():
        n = int(np.prod(s.shape))
        if "/experts/" in name and cfg.num_experts:
            n = n * cfg.experts_per_token // cfg.num_experts
        total += n
    return total


def n_param_bytes(cfg) -> int:
    return param_bytes(param_specs(cfg))


# --------------------------------------------------------------- input specs
def input_specs(cfg, shape) -> dict[str, jax.ShapeDtypeStruct]:
    """ShapeDtypeStruct stand-ins for every model input of a given shape
    config — weak-type-correct, shardable, zero allocation."""
    B, S = shape.global_batch, shape.seq_len
    tok = lambda b, s: jax.ShapeDtypeStruct((b, s), jnp.int32)
    if shape.kind == "train":
        specs = {"tokens": tok(B, S), "labels": tok(B, S)}
        if cfg.is_encdec:
            # modality frontend stub: precomputed frame embeddings
            specs["frames"] = jax.ShapeDtypeStruct(
                (B, S, cfg.d_model), jnp.dtype(cfg.dtype)
            )
        return specs
    if shape.kind == "prefill":
        specs = {"tokens": tok(B, S)}
        if cfg.is_encdec:
            specs["frames"] = jax.ShapeDtypeStruct(
                (B, S, cfg.d_model), jnp.dtype(cfg.dtype)
            )
        return specs
    if shape.kind == "decode":
        cache_shapes, _ = cache_spec(cfg, B, S)
        return {"tokens": tok(B, 1), "cache": cache_shapes}
    raise ValueError(shape.kind)


def input_axes(cfg, shape) -> dict:
    """Logical sharding axes matching input_specs' structure."""
    if shape.kind in ("train", "prefill"):
        axes = {"tokens": ("batch", "seq")}
        if shape.kind == "train":
            axes["labels"] = ("batch", "seq")
        if cfg.is_encdec:
            axes["frames"] = ("batch", "seq", "embed_tp")
        return axes
    _, cache_axes = cache_spec(cfg, shape.global_batch, shape.seq_len)
    return {"tokens": ("batch", None), "cache": cache_axes}
