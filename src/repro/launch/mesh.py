"""Production meshes.

Defined as FUNCTIONS (never module-level constants) so importing this module
never touches jax device state — required because the dry-run pins the fake
device count via XLA_FLAGS before jax initializes.

Single pod: (data=16, model=16) = 256 chips (TPU v5e pod).
Multi-pod:  (pod=2, data=16, model=16) = 512 chips; the ``pod`` axis carries
only data parallelism, so the sole cross-pod (DCN-ish) collective is the
gradient all-reduce.
"""

from __future__ import annotations

import jax
from jax.sharding import AxisType

# Flags a real TPU deployment sets for compute/communication overlap; the
# CPU dry-run ignores them but records them here as part of the launch
# configuration (DESIGN.md §6, "distributed-optimization tricks").
TPU_PERF_XLA_FLAGS = " ".join(
    [
        "--xla_tpu_enable_latency_hiding_scheduler=true",   # overlap FSDP
        "--xla_tpu_enable_async_collective_fusion=true",    # async AG/AR
        "--xla_tpu_enable_async_collective_fusion_fuse_all_gather=true",
        "--xla_tpu_overlap_compute_collective_tc=true",
        "--xla_enable_async_all_gather=true",
        "--xla_enable_async_collective_permute=true",
    ]
)


def _mesh(shape, axes):
    # Auto axes: models place activations with with_sharding_constraint
    # (dist.context.constrain), which refers to Auto axes only
    return jax.make_mesh(shape, axes, axis_types=(AxisType.Auto,) * len(axes))


def make_production_mesh(*, multi_pod: bool = False):
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return _mesh(shape, axes)


def make_local_mesh():
    """1-device mesh with the production axis names (tests/smoke runs)."""
    return _mesh((1, 1), ("data", "model"))


def mesh_from_spec(spec: str):
    """"pod" -> 16x16; "multipod" -> 2x16x16; "AxB[xC]" -> custom (tests)."""
    if spec == "pod":
        return make_production_mesh(multi_pod=False)
    if spec == "multipod":
        return make_production_mesh(multi_pod=True)
    if spec == "local":
        return make_local_mesh()
    dims = tuple(int(x) for x in spec.split("x"))
    axes = ("pod", "data", "model")[-len(dims):]
    return _mesh(dims, axes)
