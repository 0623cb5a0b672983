"""The slot pool: every family's ``decode_step`` on a pool of rows at
different positions, against each row stepped alone.

The slot scheduler keeps its pool in the family's own cache layout (every
array leaf ``(L, slots, ...)``, ``pos`` one per slot), built, spliced and
marked by ``models.pool``, and steps it with one call of
``models.decode_step``. A row of the pool must come out as it would alone
at B=1 with a scalar ``pos``: the same logits and the same next cache.
"""

from __future__ import annotations

from collections import deque

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro import models
from repro.configs import get_config
from repro.models.runtime import unroll_scans

CACHE_LEN = 32
PROMPT_LENS = (5, 13, 19)        # past the windowed archs' window of 16
ENC_LEN = 8                      # one encoder length for every row

FAMILIES = {
    "dense": "starcoder2-3b",
    "windowed": "gemma3-1b",
    "moe": "olmoe-1b-7b",
    "mamba2": "mamba2-370m",
    "hybrid": "zamba2-7b",
    "encdec": "seamless-m4t-large-v2",
}


def _rows(cfg, params, rng):
    """One B=1 prefilled cache per prompt length, and a next token each."""
    rows = []
    for n in PROMPT_LENS:
        batch = {"tokens": jnp.asarray(
            rng.integers(0, cfg.vocab_size, (1, n), dtype=np.int32))}
        if cfg.is_encdec:
            batch["frames"] = jnp.asarray(
                rng.standard_normal((1, ENC_LEN, cfg.d_model)), jnp.float32)
        _, cache = models.prefill(cfg, params, batch, impl="naive",
                                  cache_len=CACHE_LEN)
        rows.append(cache)
    toks = rng.integers(0, cfg.vocab_size, (len(rows), 1), dtype=np.int32)
    return rows, jnp.asarray(toks)


def _pool(rows, slots=None):
    """The rows spliced into the first slots of a pool of ``slots`` (as
    many as there are rows by default)."""
    pool = models.pool.empty(rows[0], slots or len(rows))
    for b, row in enumerate(rows):
        pool = models.pool.splice(pool, row, b)
    return pool


def _row(pool, b):
    return jax.tree_util.tree_map(
        lambda a: a[b] if a.ndim == 1 else a[:, b:b + 1], pool
    )


@pytest.mark.parametrize("family", sorted(FAMILIES))
def test_pool_step_matches_each_row_alone(family):
    """The rows in a pool with one free slot, stepped as the scheduler
    steps it: free rows marked by ``models.pool.free_rows``."""
    cfg = get_config(FAMILIES[family], smoke=True)
    params = models.init_params(cfg, 0)
    rows, toks = _rows(cfg, params, np.random.default_rng(7))
    step = jax.jit(lambda p, c, t: models.decode_step(cfg, p, c, t))

    @jax.jit
    def pool_step(p, c, t, active):
        c, free = models.pool.free_rows(cfg, c, active)
        return models.decode_step(cfg, p, c, t, **free)[:2]

    slots = len(rows) + 1
    active = jnp.arange(slots) < len(rows)
    logits, pool = pool_step(params, _pool(rows, slots),
                             jnp.concatenate([toks, toks[:1]]), active)
    assert pool["pos"].shape == (slots,)
    # the free row (pos 0 in the zero pool) was marked where it attends
    free_pos = models.FREE_POS if models.pool.attends(cfg) else 0
    assert int(pool["pos"][-1]) == free_pos + 1
    for b, row in enumerate(rows):
        want_logits, want = step(params, row, toks[b:b + 1])
        assert want["pos"].shape == ()
        np.testing.assert_allclose(
            np.asarray(logits[b:b + 1]), np.asarray(want_logits),
            rtol=1e-5, atol=1e-5,
        )
        got = _row(pool, b)
        assert int(got["pos"]) == int(want["pos"]) == PROMPT_LENS[b]
        for name in want:
            np.testing.assert_allclose(
                np.asarray(got[name]), np.asarray(want[name]),
                rtol=1e-5, atol=1e-5, err_msg=name,
            )


@pytest.mark.parametrize("family", ["mamba2", "hybrid", "windowed"])
def test_unrolled_pool_step_matches_the_scan(family):
    """The straight-line form (``unroll_scans``) writes the same pool."""
    cfg = get_config(FAMILIES[family], smoke=True)
    params = models.init_params(cfg, 0)
    rows, toks = _rows(cfg, params, np.random.default_rng(8))
    pool = _pool(rows)
    a_logits, a = models.decode_step(cfg, params, pool, toks)
    with unroll_scans():
        b_logits, b = models.decode_step(cfg, params, pool, toks)
    np.testing.assert_allclose(np.asarray(a_logits), np.asarray(b_logits),
                               rtol=1e-5, atol=1e-5)
    for name in a:
        np.testing.assert_allclose(np.asarray(a[name]), np.asarray(b[name]),
                                   rtol=1e-5, atol=1e-5, err_msg=name)


# The tokens a sampled serve loop with admissions mid-flight produced when
# the scheduler stepped a vmap of B=1 decode steps over a slot-major pool
# (rids 0..4; temperature 0.7, top-k 8, sampling seed 5). Stepping the pool
# in place in the family's own layout must not change one of them. The moe
# row was computed again one request at a time (prefill, then decode steps
# at B=1) when the expert layer became dropless and olmoe took its
# published routing (no renormalisation) and full-width q/k norms.
SERVED = {
    "dense": [[193, 125, 18, 200, 89, 202], [181, 131, 207],
              [44, 99, 202, 27, 18], [191, 124, 207, 120],
              [190, 64, 190, 64, 64, 81]],
    "windowed": [[77, 78, 79, 223, 231, 197], [231, 191, 240],
                 [70, 94, 124, 42, 223], [70, 77, 70, 104],
                 [16, 223, 223, 63, 227, 215]],
    "moe": [[89, 42, 234, 103, 231, 137], [89, 116, 187],
            [187, 52, 38, 204, 99], [240, 168, 207, 236],
            [190, 247, 190, 37, 105, 40]],
    "mamba2": [[41, 3, 104, 6, 80, 48], [67, 196, 53], [5, 142, 124, 13, 32],
               [98, 252, 44, 196], [61, 85, 47, 179, 98, 2]],
    "hybrid": [[136, 233, 87, 223, 12, 183], [168, 23, 114],
               [215, 14, 148, 97, 2], [61, 7, 209, 89],
               [59, 98, 139, 178, 122, 98]],
    "encdec": [[193, 193, 98, 189, 98, 213], [50, 55, 58],
               [62, 30, 158, 85, 58], [85, 85, 49, 120],
               [49, 77, 62, 193, 98, 193]],
}


@pytest.mark.parametrize("family", sorted(FAMILIES))
def test_midflight_admissions_serve_the_same_tokens(family):
    """Five requests of different lengths through three slots, arriving
    every other poll, so rows join a pool whose other rows sit at other
    positions (an encoder-decoder's requests share one prompt length: its
    cross caches are as long as the prompt)."""
    from repro.serve import STOP, Request, ServeEngine

    cfg = get_config(FAMILIES[family], smoke=True)
    engine = ServeEngine(cfg, models.init_params(cfg, 0),
                         cache_len=CACHE_LEN, impl="naive")
    rng = np.random.default_rng(11)
    lens = (9,) * 5 if cfg.is_encdec else (5, 9, 13, 7, 11)
    pending = deque(
        Request(rid=i, max_new_tokens=m,
                prompt=rng.integers(0, cfg.vocab_size, n, dtype=np.int32))
        for i, (n, m) in enumerate(zip(lens, (6, 3, 5, 4, 6)))
    )
    polls = {"n": 0}

    def trickle():
        polls["n"] += 1
        if not pending:
            return STOP
        return pending.popleft() if polls["n"] % 2 else None

    done = {}
    report = engine.serve_loop(
        trickle, lambda c: done.setdefault(c.rid, c), max_batch=3,
        max_queue=2, temperature=0.7, top_k=8, sampling_seed=5,
    )
    assert report.completed == 5 and report.peak_active <= 3
    assert [done[i].tokens.tolist() for i in range(5)] == SERVED[family]


@pytest.mark.parametrize("family", sorted(FAMILIES))
def test_the_step_calls_decode_step_as_the_benchmark_patches_it(
        family, monkeypatch):
    """The serve loop looks ``decode_step`` up on ``repro.models`` when it
    traces its step, and calls it with exactly four positional arguments
    for a family without experts: a wrapper of that form installed there
    serves the tokens the loop serves unpatched. A model with experts is
    also handed ``active`` and ``counters=True``."""
    real = models.decode_step
    calls = []

    def four(cfg, params, cache, tokens):
        calls.append({})
        return real(cfg, params, cache, tokens)

    def routed(cfg, params, cache, tokens, *, active, counters):
        calls.append({"active": active.shape, "counters": counters})
        return real(cfg, params, cache, tokens, active=active,
                    counters=counters)

    moe = get_config(FAMILIES[family], smoke=True).is_moe
    monkeypatch.setattr(models, "decode_step", routed if moe else four)
    test_midflight_admissions_serve_the_same_tokens(family)
    assert calls
    assert all(c == ({"active": (3,), "counters": True} if moe else {})
               for c in calls)


# (architecture, KV heads) of a tiny grouped-query and a tiny multi-head
# model, and the tokens the serve loop below gave for them when attention
# read the whole pool through XLA
COUNTED = {
    "gqa": ("starcoder2-3b", 2, [[83, 64, 83, 186], [12, 253, 253, 18]]),
    "mha": ("olmoe-1b-7b", 4, [[209, 238, 182, 54], [27, 158, 201, 122]]),
}


@pytest.mark.parametrize("heads", sorted(COUNTED))
def test_step_spans_count_the_kernels_reads(heads, monkeypatch):
    """Two requests in three slots (one free), prompts of 127 and 5 tokens
    in a pool of 256 positions read in blocks of 128: each ``serve.step``
    span carries the positions the decode-attention kernel is handed,
    rounded up to its block, and those the pool holds, summed over layers;
    the tokens are those of the XLA path."""
    import importlib

    from repro.core import spans
    from repro.serve import STOP, Request, ServeEngine

    arch, kv, tokens = COUNTED[heads]
    cfg = get_config(arch, smoke=True).replace(num_kv_heads=kv)
    width = kv * cfg.resolved_head_dim * np.dtype(cfg.dtype).itemsize
    kernel = importlib.import_module(
        "repro.kernels.decode_attention.decode_attention")
    monkeypatch.setattr(kernel, "BLOCK_BYTES", 128 * width)
    engine = ServeEngine(cfg, models.init_params(cfg, 0), cache_len=256)
    rng = np.random.default_rng(3)
    reqs = [Request(rid=i, max_new_tokens=4, prompt=rng.integers(
        0, cfg.vocab_size, n, dtype=np.int32)) for i, n in enumerate((127, 5))]
    src = iter(reqs + [STOP])
    done = {}
    seen = max((r.index for r in spans.records()), default=-1)
    engine.serve_loop(lambda: next(src), lambda c: done.setdefault(c.rid, c),
                      max_batch=3, max_queue=4, on_delta=lambda d: None)

    steps = [r.attrs for r in spans.records()
             if r.name == "serve.step" and r.index > seen]
    L = cfg.num_layers
    # step k reads positions 0 .. 127 + k and 0 .. 5 + k of each layer:
    # (128, 6), (129, 7), (130, 8), rounded up to blocks of 128
    assert [s["attn_kv_read"] for s in steps] == [
        L * (128 + 128), L * (256 + 128), L * (256 + 128)]
    assert [s["attn_kv_held"] for s in steps] == [L * 3 * 256] * 3
    assert [done[i].tokens.tolist() for i in range(2)] == tokens


@pytest.mark.parametrize("family", ["dense", "hybrid", "mamba2", "windowed"])
def test_step_counts_count_only_the_layers_that_run_the_kernel(family):
    """``models.pool.step_counts`` on a pool of 4 slots of 32 positions
    (one block of 32) with rows at positions 4 and 12: each row is handed
    one block in each layer that runs the kernel; a step with no such
    layer (mamba2's and the hybrid's) counts nothing."""
    cfg = get_config(FAMILIES[family], smoke=True)
    params = models.init_params(cfg, 0)
    rows, _ = _rows(cfg, params, np.random.default_rng(5))
    got = models.pool.step_counts(cfg, _pool(rows, 4), [4, 12])
    if family in ("hybrid", "mamba2"):
        assert got is None
        return
    # windowed: one of its two layers attends to the whole cache
    layers = {"dense": 2, "windowed": 1}[family]
    assert got == {"attn_kv_read": layers * 2 * 32,
                   "attn_kv_held": layers * 4 * 32}
