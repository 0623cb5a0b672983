"""Per-architecture smoke tests: reduced config, one forward + one train
step on CPU — output shapes + finiteness (deliverable f)."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro import models
from repro.configs import ARCHS, get_config
from repro.launch.steps import make_train_fn
from repro.optim import OptConfig, init_opt_state

B, S = 2, 16


def _batch(cfg, rng):
    batch = {
        "tokens": jnp.asarray(
            rng.integers(0, cfg.vocab_size, (B, S), dtype=np.int32)
        ),
        "labels": jnp.asarray(
            rng.integers(0, cfg.vocab_size, (B, S), dtype=np.int32)
        ),
    }
    if cfg.is_encdec:
        batch["frames"] = jnp.asarray(
            rng.standard_normal((B, S, cfg.d_model)), jnp.float32
        )
    return batch


@pytest.mark.parametrize("arch", sorted(ARCHS))
def test_forward_shapes_and_finite(arch):
    cfg = get_config(arch, smoke=True)
    params = models.init_params(cfg, 0)
    batch = _batch(cfg, np.random.default_rng(0))
    logits, aux = models.forward(cfg, params, batch, impl="naive")
    assert logits.shape == (B, S, cfg.vocab_size)
    assert bool(jnp.isfinite(logits).all())
    assert bool(jnp.isfinite(aux))


@pytest.mark.parametrize("arch", sorted(ARCHS))
def test_train_step_runs_and_loss_finite(arch):
    cfg = get_config(arch, smoke=True)
    params = models.init_params(cfg, 0)
    opt = init_opt_state(params)
    step = jax.jit(
        make_train_fn(cfg, OptConfig(peak_lr=1e-3, warmup_steps=1),
                      num_microbatches=2, impl="naive")
    )
    batch = _batch(cfg, np.random.default_rng(1))
    params2, opt2, metrics = step(params, opt, batch)
    assert bool(jnp.isfinite(metrics["loss"]))
    assert bool(jnp.isfinite(metrics["grad_norm"]))
    assert int(opt2["step"]) == 1
    # params actually moved
    moved = any(
        not np.array_equal(np.asarray(params[k]), np.asarray(params2[k]))
        for k in params
    )
    assert moved


@pytest.mark.parametrize("arch", ["gemma3-1b", "mamba2-370m", "zamba2-7b",
                                  "seamless-m4t-large-v2", "olmoe-1b-7b"])
def test_prefill_decode_matches_forward(arch):
    """Greedy-decode consistency: decode logits == full-forward logits
    (MoE routing is dropless, so no token's experts depend on the batch)."""
    cfg = get_config(arch, smoke=True)
    params = models.init_params(cfg, 0)
    rng = np.random.default_rng(2)
    tokens = jnp.asarray(rng.integers(0, cfg.vocab_size, (B, S), np.int32))
    batch = {"tokens": tokens}
    if cfg.is_encdec:
        batch["frames"] = jnp.asarray(
            rng.standard_normal((B, S, cfg.d_model)), jnp.float32
        )
    full, _ = models.forward(cfg, params, batch, impl="naive")
    pre = dict(batch)
    pre["tokens"] = tokens[:, : S - 1]
    lg, cache = models.prefill(cfg, params, pre, impl="naive", cache_len=S + 2)
    assert np.allclose(lg[:, 0], full[:, S - 2], atol=2e-4)
    lg2, cache = models.decode_step(cfg, params, cache, tokens[:, S - 1 : S])
    assert np.allclose(lg2[:, 0], full[:, S - 1], atol=2e-4)


def test_sliding_window_masks_differ_from_full():
    """gemma3 local layers must actually restrict attention."""
    cfg = get_config("gemma3-1b", smoke=True)
    from repro.models.transformer import _layer_windows

    windows = _layer_windows(cfg)
    assert 0 in windows and cfg.sliding_window in windows


def test_unroll_scans_equivalence():
    """Unrolled tracing (dry-run cost probes) == scanned tracing."""
    from repro.models.runtime import unroll_scans

    for arch in ["mamba2-370m", "zamba2-7b", "deepseek-67b"]:
        cfg = get_config(arch, smoke=True)
        params = models.init_params(cfg, 0)
        batch = _batch(cfg, np.random.default_rng(3))
        a, _ = models.forward(cfg, params, batch, impl="naive")
        with unroll_scans():
            b, _ = models.forward(cfg, params, batch, impl="naive")
        assert np.allclose(np.asarray(a), np.asarray(b), atol=1e-5), arch


def test_shared_block_weight_reuse_zamba():
    """zamba2's attention params appear ONCE but are applied at every
    invocation — the many-references-one-symbol case."""
    cfg = get_config("zamba2-7b", smoke=True)
    specs = models.param_specs(cfg)
    shared = [n for n in specs if n.startswith("shared_attn/")]
    assert shared  # exactly one copy of the shared block
    # perturbing the single shared tensor changes the output
    params = models.init_params(cfg, 0)
    batch = _batch(cfg, np.random.default_rng(4))
    base, _ = models.forward(cfg, params, batch, impl="naive")
    params2 = dict(params)
    params2["shared_attn/wq"] = params["shared_attn/wq"] + 1.0
    pert, _ = models.forward(cfg, params2, batch, impl="naive")
    assert not np.allclose(np.asarray(base), np.asarray(pert))


def test_init_params_np_is_host_only_and_keyed_by_name():
    """Publishing builds weights without a JAX backend (a serving parent
    must leave the chip to its workers); each tensor depends only on
    (seed, name), so adding a parameter perturbs none of the others."""
    import os
    import subprocess
    import sys
    from pathlib import Path

    src = Path(__file__).resolve().parents[1] / "src"
    code = (
        "from repro import models\n"
        "from repro.configs import get_config\n"
        "from repro.core.chips import jax_backend_initialized\n"
        "models.init_params_np(get_config('mamba2-370m', smoke=True), 0)\n"
        "assert not jax_backend_initialized()\n"
    )
    out = subprocess.run(
        [sys.executable, "-c", code], capture_output=True, text=True,
        env=dict(os.environ, PYTHONPATH=str(src)), timeout=120,
    )
    assert out.returncode == 0, out.stderr[-2000:]

    from repro.models.specs import ParamSpec, init_params_np

    specs = {"a": ParamSpec((4, 8), "bfloat16", (None, None), "fan_in"),
             "b": ParamSpec((8,), "float32", (None,))}
    one = init_params_np(specs, seed=5)
    more = init_params_np(
        dict(specs, c=ParamSpec((2,), "float32", (None,))), seed=5
    )
    for n in specs:
        assert one[n].dtype == more[n].dtype
        np.testing.assert_array_equal(one[n], more[n])
    assert not np.array_equal(init_params_np(specs, seed=6)["b"], one["b"])
