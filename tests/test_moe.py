"""The expert layer: dropless routing over every expert, computed for the
experts a chip holds, and its grouped-matmul kernel against its oracle.
Small sizes on the CPU; the kernel in interpret mode."""

from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro import models
from repro.configs import get_config
from repro.kernels.moe_gmm import gmm, moe_gmm, moe_gmm_ref
from repro.models.moe import moe_block


# (group sizes, rows): empty groups, groups across tile edges, and rows past
# the last group (pairs routed to experts another chip holds)
GROUPS = {
    "uneven": ([37, 0, 100, 1, 50], 256),
    "empty_first_and_last": ([0, 128, 0, 64, 0], 256),
    "all_in_one": ([0, 0, 200, 0], 256),
    "none": ([0, 0, 0], 128),
}


@pytest.mark.parametrize("case", sorted(GROUPS))
@pytest.mark.parametrize("dtype", ["float32", "bfloat16"])
def test_moe_gmm_interpret_matches_ref(case, dtype):
    sizes, m = GROUPS[case]
    G, k, n = len(sizes), 64, 96
    rng = np.random.default_rng(len(sizes) + m)
    lhs = jnp.asarray(rng.standard_normal((m, k)), dtype)
    rhs = jnp.asarray(rng.standard_normal((G, k, n)) / 8, dtype)
    gs = jnp.asarray(sizes, jnp.int32)
    got = moe_gmm(lhs, rhs, gs, tiling=(128, 32, 32), interpret=True)
    want = moe_gmm_ref(lhs, rhs, gs)
    rows = sum(sizes)
    tol = 1e-5 if dtype == "float32" else 2e-2
    np.testing.assert_allclose(np.asarray(got[:rows], np.float32),
                               np.asarray(want[:rows]), rtol=tol, atol=tol)
    # the platform's path (ragged_dot here) agrees, and is differentiable
    np.testing.assert_allclose(np.asarray(gmm(lhs, rhs, gs)[:rows], np.float32),
                               np.asarray(want[:rows]), rtol=tol, atol=tol)


def _experts(E_h=8, d=32, ff=16, seed=0):
    ks = jax.random.split(jax.random.PRNGKey(seed), 3)
    return (jax.random.normal(ks[0], (E_h, d, ff)) / d**0.5,
            jax.random.normal(ks[1], (E_h, d, ff)) / d**0.5,
            jax.random.normal(ks[2], (E_h, ff, d)) / ff**0.5)


def _dense(x, router_w, wg, wu, wd, *, k, first=0, norm_topk=True):
    """Every token through every expert it chose, one at a time."""
    T = x.shape[0]
    probs = np.asarray(jax.nn.softmax(x @ router_w, -1))
    out = np.zeros_like(np.asarray(x))
    for t in range(T):
        top = np.argsort(-probs[t])[:k]
        w = probs[t, top] / (probs[t, top].sum() if norm_topk else 1.0)
        for e, we in zip(top, w):
            if first <= e < first + wg.shape[0]:
                i = e - first
                h = jax.nn.silu(x[t] @ wg[i]) * (x[t] @ wu[i])
                out[t] += we * np.asarray(h @ wd[i])
    return out


def test_dropless_under_skewed_routing():
    """Every token chooses the same two experts: each of them takes all 64
    tokens, four times what a capacity of 1.25 k T / E would have kept,
    and no token is dropped."""
    T, d, E, k = 64, 32, 8, 2
    x = jnp.abs(jax.random.normal(jax.random.PRNGKey(1), (1, T, d))) + 0.1
    router_w = jnp.full((d, E), -1.0).at[:, 3].set(1.0).at[:, 5].set(0.5)
    wg, wu, wd = _experts(E, d)
    out, _, counts = moe_block(x, router_w, wg, wu, wd, k=k, norm_topk=False)
    assert counts.tolist() == [T * k, 2]
    want = _dense(x[0], router_w, wg, wu, wd, k=k, norm_topk=False)
    np.testing.assert_allclose(np.asarray(out[0]), want, rtol=1e-4, atol=1e-5)


def test_held_share_routes_over_every_expert():
    """A chip holding experts 4..7 of 8: the router still scores all 8 and
    keeps its top k; only the held experts' part is computed, renormalised
    over the k the token chose, not over the held ones."""
    T, d, E, k = 40, 32, 8, 3
    x = jax.random.normal(jax.random.PRNGKey(2), (2, T // 2, d))
    router_w = jax.random.normal(jax.random.PRNGKey(3), (d, E))
    wg, wu, wd = _experts(E)
    share = tuple(w[4:] for w in (wg, wu, wd))
    out, _, counts = moe_block(x, router_w, *share, k=k, first=4)
    want = _dense(x.reshape(T, d), router_w, *share, k=k, first=4)
    np.testing.assert_allclose(np.asarray(out.reshape(T, d)), want,
                               rtol=1e-4, atol=1e-5)
    top = np.argsort(-np.asarray(x.reshape(T, d) @ router_w), -1)[:, :k]
    assert int(counts[0]) == int((top >= 4).sum())
    assert int(counts[1]) == len(set(top[top >= 4].tolist()))


def test_free_rows_route_nowhere():
    """Rows marked free cost the experts nothing and leave the other rows'
    outputs as they were."""
    d, E = 32, 8
    x = jax.random.normal(jax.random.PRNGKey(4), (4, 1, d))
    router_w = jax.random.normal(jax.random.PRNGKey(5), (d, E))
    w = _experts(E)
    full, _, c_full = moe_block(x, router_w, *w, k=2)
    rows = jnp.asarray([True, False, True, False])
    part, _, c_part = moe_block(x, router_w, *w, k=2, rows=rows)
    np.testing.assert_allclose(np.asarray(part[rows]), np.asarray(full[rows]),
                               rtol=1e-6, atol=1e-6)
    assert not np.asarray(part[~rows]).any()
    assert int(c_full[0]) == 8 and int(c_part[0]) == 4


@pytest.mark.parametrize("width", ["head", "full"])
def test_qk_norm_full_width_against_per_head(width):
    """olmoe's q and k norms run over the whole projected width before the
    split into heads; gemma3's run per head. Each form against a plain
    RMSNorm over its span."""
    from repro.models.transformer import _attn_specs, _project_qkv

    cfg = get_config("olmoe-1b-7b", smoke=True).replace(qk_norm_width=width)
    H, KV, hd = cfg.num_heads, cfg.num_kv_heads, cfg.resolved_head_dim
    specs = _attn_specs(cfg, cfg.d_model, cfg.d_model)
    span = {"head": (hd, hd), "full": (H * hd, KV * hd)}[width]
    assert (specs["attn/q_norm"].shape[0], specs["attn/k_norm"].shape[0]) == span
    ks = jax.random.split(jax.random.PRNGKey(6), 6)
    p = {n: jax.random.normal(k_, s.shape) * (0.3 if "norm" not in n else 1)
         for (n, s), k_ in zip(sorted(specs.items()), ks)}
    x = jax.random.normal(jax.random.PRNGKey(7), (2, 5, cfg.d_model))
    q, k, _ = _project_qkv(cfg, p, x)

    def rms(a, scale):
        a = np.asarray(a, np.float64)
        return a / np.sqrt((a * a).mean(-1, keepdims=True) + cfg.norm_eps) \
            * np.asarray(scale)

    qp, kp = np.asarray(x @ p["attn/wq"]), np.asarray(x @ p["attn/wk"])
    if width == "full":
        want_q = rms(qp, p["attn/q_norm"]).reshape(2, 5, H, hd)
        want_k = rms(kp, p["attn/k_norm"]).reshape(2, 5, KV, hd)
    else:
        want_q = rms(qp.reshape(2, 5, H, hd), p["attn/q_norm"])
        want_k = rms(kp.reshape(2, 5, KV, hd), p["attn/k_norm"])
    np.testing.assert_allclose(np.asarray(q), want_q, rtol=1e-4, atol=1e-5)
    np.testing.assert_allclose(np.asarray(k), want_k, rtol=1e-4, atol=1e-5)


def test_published_olmoe_config():
    cfg = get_config("olmoe-1b-7b")
    assert (cfg.num_experts, cfg.experts_per_token, cfg.held_experts) == (64, 8, 64)
    assert not cfg.norm_topk_prob and not cfg.tie_embeddings
    assert (cfg.qk_norm, cfg.qk_norm_width) == (True, "full")
    assert (cfg.rope_theta, cfg.norm_eps) == (10_000.0, 1e-5)
    assert models.n_params(cfg) == 6_919_161_856
    cut = cfg.replace(experts_held=16)
    assert models.n_params(cut) == 2_087_323_648


def test_serve_loop_records_the_expert_counters_on_its_spans():
    """A streaming serve loop reads the counters at the syncs it already
    makes: on each decode step's span and on each prefill's."""
    from repro.core import spans
    from repro.serve import STOP, Request, ServeEngine

    cfg = get_config("olmoe-1b-7b", smoke=True)
    engine = ServeEngine(cfg, models.init_params(cfg, 0), cache_len=32,
                         impl="naive")
    rng = np.random.default_rng(5)
    reqs = [Request(rid=9000 + i, max_new_tokens=4,
                    prompt=rng.integers(0, cfg.vocab_size, n, dtype=np.int32))
            for i, n in enumerate((5, 9, 7))]
    src = iter(reqs + [STOP])
    engine.serve_loop(lambda: next(src), lambda c: None, max_batch=2,
                      on_delta=lambda d: None)
    L, k = cfg.num_layers, cfg.experts_per_token
    steps = [r for r in spans.records() if r.name == "serve.step"
             and "moe_rows" in (r.attrs or {})][-6:]
    assert steps
    for r in steps:
        # every active row's k choices in each layer land on a held expert
        assert r.attrs["moe_rows"] == r.attrs["n_active"] * k * L
        assert 1 <= r.attrs["moe_experts_hit"] <= cfg.num_experts * L
    pre = [r for r in spans.records() if r.name == "serve.admit.prefill"
           and r.attrs.get("prompt_len") in (5, 9, 7)][-3:]
    assert [r.attrs["moe_rows"] for r in pre] == [
        r.attrs["prompt_len"] * k * L for r in pre]
