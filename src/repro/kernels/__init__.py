"""Pallas TPU kernels for the perf-critical hot spots.

    paged_reloc_copy — the paper's relocation-table walk as a scalar-
                       prefetched paged HBM gather (the stable-linking
                       epoch loader's TPU form)
    flash_attention  — blockwise online-softmax attention (causal / GQA /
                       sliding window) for train + prefill
    rmsnorm          — fused norm
    moe_gmm          — grouped matmul over a chip's held experts, rows
                       sorted by expert, group sizes from the routing
                       (the expert layer's dropless SwiGLU)
    decode_attention — one token per row against one layer of the stacked
                       slot pool, read in place, each row's blocks only up
                       to its length (the decode step's attention)
    ssm_decode       — one token's step of one layer of the stacked SSM
                       state, each live row read once and written once in
                       place, free rows untouched (the decode step's SSM)

Each package: <name>.py (pl.pallas_call + BlockSpec), ops.py (jit wrapper),
ref.py (pure-jnp oracle). Validated on CPU with interpret=True; compiled
via Mosaic on TPU.
"""

from . import (
    decode_attention,
    flash_attention,
    moe_gmm,
    paged_reloc_copy,
    rmsnorm,
    ssm_decode,
)

__all__ = ["decode_attention", "flash_attention", "moe_gmm",
           "paged_reloc_copy", "rmsnorm", "ssm_decode"]
