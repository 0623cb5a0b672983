"""jit'd wrappers: numpy relocation state <-> kernel-friendly page arrays."""

from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

from repro.core import PAGE_BYTES
from repro.core.relocation import PageTable

from .paged_reloc_copy import PAGE_SHAPE, paged_reloc_copy
from .ref import paged_reloc_copy_ref


def as_pages(buf: np.ndarray | bytes, n_pages: int) -> np.ndarray:
    """bytes -> (n_pages, 8, 128) int32 pages (zero-padded)."""
    raw = np.frombuffer(bytes(buf), dtype=np.uint8)
    out = np.zeros(n_pages * PAGE_BYTES, np.uint8)
    out[: raw.size] = raw
    return out.view(np.int32).reshape((n_pages,) + PAGE_SHAPE)


def pages_to_bytes(pages: np.ndarray) -> bytes:
    return np.asarray(pages).view(np.int32).tobytes()


def apply_page_table(
    pt: PageTable,
    blob: np.ndarray,
    arena: np.ndarray,
    *,
    impl: str = "pallas",
) -> jax.Array:
    """Execute a compiled page table: impl in {pallas, pallas_interpret, ref}."""
    src = jnp.asarray(pt.src_page)
    dst = jnp.asarray(pt.dst_page)
    blob_j = jnp.asarray(blob)
    arena_j = jnp.asarray(arena)
    if impl == "ref":
        return paged_reloc_copy_ref(blob_j, arena_j, src, dst)
    return paged_reloc_copy(
        blob_j, arena_j, src, dst, interpret=(impl == "pallas_interpret")
    )
