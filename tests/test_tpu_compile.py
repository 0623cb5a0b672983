"""Compile the serving path and the Pallas kernels for a TPU v5e chip.

Nothing runs: the TPU compiler, which is installed with JAX, compiles for a
chip that is described (``v5e:2x2``) and not attached. That catches what the
CPU and interpret mode cannot: a program that does not fit the chip's
memory, and a kernel the chip's compiler refuses.

The topology is described in a module-scoped fixture, never at import: only
one process at a time may load the TPU library, and every test worker
imports this file. Keep every such compile in this one file, so that one
worker loads the library.
"""

from __future__ import annotations

import functools
import re

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro import models
from repro.configs import get_config

V5E_HBM_BYTES = 16 * 2**30


@pytest.fixture(scope="module")
def one_chip():
    with pytest.MonkeyPatch.context() as mp:
        mp.setenv("TPU_LOG_DIR", "disabled")
        from jax.experimental import topologies

        try:
            topo = topologies.get_topology_desc(
                platform="tpu", topology_name="v5e:2x2"
            )
        except Exception as e:  # no TPU compiler in this installation
            pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
        yield SingleDeviceSharding(topo.devices[0])


def _on(sharding, tree):
    return jax.tree_util.tree_map(
        lambda s: jax.ShapeDtypeStruct(s.shape, s.dtype, sharding=sharding),
        tree,
    )


def _fits(compiled) -> None:
    mem = compiled.memory_analysis()
    used = mem.argument_size_in_bytes + mem.temp_size_in_bytes
    assert used < V5E_HBM_BYTES, used


@pytest.fixture(scope="module")
def engine():
    from repro.serve import ServeEngine

    cfg = get_config("mamba2-370m")
    return ServeEngine(cfg, params=None, cache_len=512 + 16)


def test_mamba2_prefill_compiles_for_v5e(one_chip, engine):
    cfg = engine.cfg
    params = _on(one_chip, models.abstract(cfg))
    batch = _on(one_chip, {"tokens": jax.ShapeDtypeStruct((1, 512), jnp.int32)})
    compiled = engine._prefill.lower(params, batch).compile()
    _fits(compiled)


# (arch, slots, cache_len) of the benchmark's cells
SLOT_POOLS = {
    "mamba2-370m": (64, 512 + 16),
    "starcoder2-3b": (32, 4096),
    "olmoe-1b-7b": (48, 1536),
}


def _served(arch):
    """The configuration a cell serves: olmoe as one chip's share under
    expert parallelism 4, 16 of its 64 experts held."""
    cfg = get_config(arch)
    return cfg.replace(experts_held=16) if cfg.is_moe else cfg


def test_olmoe_prefill_compiles_for_v5e(one_chip):
    """The longest prompt of the chat mix through the expert layer's
    grouped-matmul kernel, beside the weights of 16 held experts."""
    from repro.serve import ServeEngine

    cfg = _served("olmoe-1b-7b")
    engine = ServeEngine(cfg, params=None, cache_len=1536)
    params = _on(one_chip, models.abstract(cfg))
    batch = _on(one_chip, {"tokens": jax.ShapeDtypeStruct((1, 1024), jnp.int32)})
    compiled = engine._prefill.lower(params, batch).compile()
    _fits(compiled)
    assert "moe_gmm" in compiled.as_text()


@pytest.mark.parametrize("arch", sorted(SLOT_POOLS))
def test_slot_decode_step_compiles_for_v5e(one_chip, arch):
    """The served decode program: SlotScheduler's step over the slot pool,
    the family's cache at batch = slots (every array leaf ``(L, slots,
    ...)``, ``pos`` one per slot). The step updates the donated pool in
    place: its scratch stays under one layer's slice of the pool, and the
    pool is aliased from input to output, so nothing copies it whole. An
    attention model's step hands the stacked K and V to the
    decode-attention kernel, so no instruction holds one layer's slice of
    them; mamba2's step hands the stacked SSM state to the SSM-state
    kernel, so no fusion computes the whole pool's state or one layer's
    slice of it (the kernel's aliased output and the layer loop's carry
    hold the pool, as they must)."""
    from repro.serve import ServeEngine
    from repro.serve.scheduler import SlotScheduler

    slots, cache_len = SLOT_POOLS[arch]
    cfg = _served(arch)
    engine = ServeEngine(cfg, params=None, cache_len=cache_len)
    sched = SlotScheduler(engine, max_batch=slots)
    pool, _ = models.cache_spec(cfg, slots, cache_len)
    pool["pos"] = jax.ShapeDtypeStruct((slots,), jnp.int32)
    key = jax.random.PRNGKey(0)
    args = _on(one_chip, (
        models.abstract(cfg),
        pool,
        jax.ShapeDtypeStruct((slots, 1, 1), jnp.int32),
        jax.ShapeDtypeStruct((slots, 16), jnp.int32),
        jax.ShapeDtypeStruct((slots,), jnp.int32),
        jax.ShapeDtypeStruct((slots,) + key.shape, key.dtype),
        jax.ShapeDtypeStruct((slots,), jnp.bool_),
    ))
    compiled = sched._step_fn.lower(*args).compile()
    _fits(compiled)
    layers = [a for a in pool.values() if a.ndim > 1]
    pool_bytes = sum(a.size * a.dtype.itemsize for a in pool.values())
    layer_bytes = sum(a.size * a.dtype.itemsize // a.shape[0] for a in layers)
    mem = compiled.memory_analysis()
    assert mem.temp_size_in_bytes < layer_bytes, (mem.temp_size_in_bytes,
                                                   layer_bytes)
    assert mem.alias_size_in_bytes >= pool_bytes, (mem.alias_size_in_bytes,
                                                   pool_bytes)
    hlo = compiled.as_text()
    if "k" not in pool:
        layers, _, heads, p, n = pool["ssm"].shape
        for dims in (f"{layers},{slots},{heads},{p},{n}",
                     f"{slots},{heads},{p},{n}"):
            assert not re.search(rf"= f32\[{dims}\]\S* fusion\(", hlo), dims
        assert "ssm_decode" in hlo and "tpu_custom_call" in hlo
        return
    _, _, seq_len, kv, hd = pool["k"].shape
    dims = f"{slots},{seq_len},{kv},{hd}"
    assert not re.search(rf"= \S+\[(1,)?{dims}\]", hlo), dims
    assert "decode_attention" in hlo and "tpu_custom_call" in hlo


def _kernel_cases():
    from repro.kernels.decode_attention import (
        block_len,
        decode_attention_kernel,
    )
    from repro.kernels.flash_attention import flash_attention_bhsd
    from repro.kernels.moe_gmm import moe_gmm
    from repro.kernels.paged_reloc_copy import paged_reloc_copy
    from repro.kernels.rmsnorm import rmsnorm_2d
    from repro.kernels.ssm_decode import ssm_decode_kernel

    bf16, f32, i32 = jnp.bfloat16, jnp.float32, jnp.int32
    sds = jax.ShapeDtypeStruct
    return {
        # starcoder2-3b attention: 24 query heads over 2 KV heads
        "flash_attention": (flash_attention_bhsd, (
            sds((1, 24, 2048, 128), bf16),
            sds((1, 2, 2048, 128), bf16),
            sds((1, 2, 2048, 128), bf16),
        )),
        # starcoder2-3b's slot pool: 32 rows of 4096 positions, 24 query
        # heads over 2 KV heads, one of 30 layers read
        "decode_attention": (
            jax.jit(functools.partial(decode_attention_kernel,
                                      block=block_len(4096, 256, 2))), (
                sds((32, 24, 128), bf16),
                sds((30, 32, 4096, 2, 128), bf16),
                sds((30, 32, 4096, 2, 128), bf16),
                sds((), i32),
                sds((32,), i32),
            )),
        "rmsnorm": (rmsnorm_2d, (sds((4096, 1024), bf16), sds((1024,), bf16))),
        # olmoe's down projection over 16 held experts, 48 slots x top-8
        "moe_gmm": (moe_gmm, (
            sds((384, 1024), bf16),
            sds((16, 1024, 2048), bf16),
            sds((16,), i32),
        )),
        # mamba2-370m's slot pool: 64 rows of 32 heads x 64 x 128 state,
        # one of 48 layers stepped
        "ssm_decode": (ssm_decode_kernel, (
            sds((48, 64, 32, 64, 128), f32),
            sds((), i32),
            sds((64,), jnp.bool_),
            sds((64, 32), f32),
            sds((64, 32, 64), f32),
            sds((64, 1, 128), f32),
            sds((64, 1, 128), f32),
        )),
        "paged_reloc_copy": (paged_reloc_copy, (
            sds((4096, 8, 128), i32),
            sds((4096, 8, 128), i32),
            sds((4096,), i32),
            sds((4096,), i32),
        )),
    }


@pytest.mark.parametrize("kernel", ["flash_attention", "rmsnorm", "paged_reloc_copy",
                                    "moe_gmm", "decode_attention",
                                    "ssm_decode"])
def test_kernel_lowers_to_tpu_custom_call(one_chip, kernel):
    fn, shapes = _kernel_cases()[kernel]
    compiled = fn.lower(*_on(one_chip, shapes)).compile()
    assert "tpu_custom_call" in compiled.as_text()
