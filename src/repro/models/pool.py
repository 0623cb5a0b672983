"""The slot pool: one family's decode state for many requests at once.

A serve loop holds a fixed pool of decode slots and advances every slot
with one call of ``decode_step``. The pool is the family's own B=1 decode
cache at batch = slots: every array leaf carries the slot at axis 1
(``(L, slots, ...)``) and ``pos`` is one position per slot. This module is
all the serving layer needs to know of a family to keep such a pool:

* its layout — ``empty`` builds the zero pool from a prefilled row and
  ``splice`` writes one row in at a slot;
* its free rows — ``free_rows`` marks the rows that hold no request the
  way the family needs, and gives the arguments ``decode_step`` takes for
  them;
* its counters — ``step_counts`` works out, on the host, what the
  decode-attention and SSM-state kernels read in a step, and
  ``read_counts`` fetches the expert layers' counts, which
  ``prefill_kwargs`` and ``free_rows`` ask for where the model has
  experts;
* the rest — ``prompt_batch`` (the prefill's inputs) and ``attends``
  (whether rows need a cache length).
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

from repro.kernels import decode_attention

from . import api, moe, transformer
from .common import FREE_POS

#: Axis of the slot (batch) in every array leaf of a family's decode cache.
_SLOT_AXIS = 1


def attends(cfg) -> bool:
    """Whether the family attends over a KV cache: its rows then need a
    cache length."""
    return cfg.family != "ssm"


def _pool_shape(row_shape: tuple, slots: int) -> tuple:
    if not row_shape:
        return (slots,)
    return row_shape[:_SLOT_AXIS] + (slots,) + row_shape[_SLOT_AXIS + 1:]


def empty(row, slots: int):
    """A pool of ``slots`` zero rows shaped like the B=1 cache ``row``: the
    slot at axis 1 of each array, a scalar (``pos``) one per slot."""
    return jax.tree_util.tree_map(
        lambda r: jnp.zeros(_pool_shape(np.shape(r), slots), r.dtype), row)


def splice(pool, row, idx):
    """``pool`` with the B=1 cache ``row`` written in at slot ``idx``; on a
    donated pool this is an in-place row write."""
    return jax.tree_util.tree_map(
        lambda s, r: jax.lax.dynamic_update_slice_in_dim(
            s, jnp.atleast_1d(r).astype(s.dtype), idx,
            _SLOT_AXIS if r.ndim else 0,
        ),
        pool,
        row,
    )


def free_rows(cfg, pool, active):
    """``pool`` with the rows that ``active`` (slots,) bool leaves out
    marked as holding no request, and the keyword arguments
    ``decode_step`` takes for them: ``(pool, kwargs)``.

    A free row gets ``pos = FREE_POS``, so that it reads no cache and its
    SSM state is neither read nor written. A model with experts is handed
    ``active``, so that it routes none of a free row's tokens, and
    ``counters=True``: its step also returns the expert layers' counts
    (``moe.COUNTERS``)."""
    pool = dict(pool, pos=jnp.where(active, pool["pos"], FREE_POS))
    return pool, (dict(active=active, counters=True) if cfg.is_moe else {})


def prefill_kwargs(cfg) -> dict:
    """The keyword arguments ``prefill`` takes on a serve loop's admission:
    ``counters=True`` for a model with experts, so that it also returns
    the expert layers' counts; none otherwise."""
    return dict(counters=True) if cfg.is_moe else {}


def prompt_batch(cfg, prompts) -> dict:
    """The prefill's inputs for ``prompts`` (B, S): the tokens, and for an
    encoder-decoder a modality stub, frames drawn from a fixed seed."""
    batch = {"tokens": jnp.asarray(prompts, jnp.int32)}
    if cfg.is_encdec:
        rng = np.random.default_rng(0)
        batch["frames"] = jnp.asarray(
            rng.standard_normal(prompts.shape + (cfg.d_model,)),
            jnp.dtype(cfg.dtype),
        )
    return batch


def step_counts(cfg, pool, positions) -> dict | None:
    """What the step's kernels read in the next step of ``pool``, from
    ``positions``, the position each active row decodes at.

    Where the decode-attention kernel runs: ``attn_kv_read``, the
    positions it is handed (each row's ``min(pos + 1, S)``, rounded up to
    its block), and ``attn_kv_held``, the positions the pool holds, both
    summed over the layers it runs in. Where the family keeps an SSM
    state: ``ssm_rows_stepped``, the rows whose state the step reads and
    writes (the active ones), and ``ssm_rows_held``, the rows the pool
    holds, both summed over the SSM layers. None where the step has
    neither."""
    counts = {}
    if "ssm" in pool:
        layers, slots = pool["ssm"].shape[:2]
        counts.update(ssm_rows_stepped=layers * len(positions),
                      ssm_rows_held=layers * slots)
    if api._mod(cfg) is transformer:
        k = pool["k"]
        slots, seq_len = k.shape[_SLOT_AXIS], k.shape[2]
        layers = transformer.kernel_layers(cfg, seq_len)
        if layers:
            block = decode_attention.block_of(k)
            read = sum(-(-min(p + 1, seq_len) // block) * block
                       for p in positions)
            counts.update(attn_kv_read=layers * read,
                          attn_kv_held=layers * slots * seq_len)
    return counts or None


def read_counts(counts) -> dict:
    """The expert layers' counts, fetched from the device, by name."""
    return dict(zip(moe.COUNTERS, np.asarray(counts).tolist()))
