"""One chip per process: worker placement, device identity, compile cache.

A TPU chip belongs to one process at a time. A parent that spawns serving
workers therefore stays off JAX's backends (importing ``jax`` is fine;
creating an array or asking for devices takes the chip), and each worker is
pinned to one chip through libtpu's per-process settings before it starts.
Four workers on a four-chip host then hold four different chips instead of
all reaching for the whole host, and libtpu lets them load side by side
because each one's chip bounds are a subset of the host's.

JAX is imported lazily: nothing here initialises a backend except
``device_report``, which a worker calls once it owns its chip.
"""

from __future__ import annotations

import contextlib
import os
import re
import socket
import sys
from pathlib import Path

REPO_ROOT = Path(__file__).resolve().parents[3]


def compile_cache_dir() -> str:
    """Place JAX's persistent compilation cache and return its directory.

    ``JAX_COMPILATION_CACHE_DIR``, when set, is used as it is: JAX reads it
    at import and nothing else is set here. Otherwise the cache goes to the
    fixed ``<repo>/.jax_cache`` (a cache whose directory moves between runs
    never hits) and the variable is exported so spawned workers inherit it.
    """
    path = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if path:
        return path
    path = os.environ["JAX_COMPILATION_CACHE_DIR"] = str(REPO_ROOT / ".jax_cache")
    import jax

    jax.config.update("jax_compilation_cache_dir", path)
    return path


def jax_backend_initialized() -> bool:
    """True once this process has initialised any JAX backend (and so may
    hold the chip). Never initialises one itself."""
    if "jax" not in sys.modules:
        return False
    from jax._src import xla_bridge

    return xla_bridge.backends_are_initialized()


def _free_local_port() -> int:
    with socket.socket(socket.AF_INET, socket.SOCK_STREAM) as s:
        s.bind(("localhost", 0))
        return s.getsockname()[1]


def chip_env(index: int) -> dict[str, str]:
    """libtpu settings that give a process chip ``index`` and nothing else:
    a one-chip slice of its own, with its own local port."""
    port = _free_local_port()
    return {
        "TPU_VISIBLE_CHIPS": str(index),
        "TPU_CHIPS_PER_PROCESS_BOUNDS": "1,1,1",
        "TPU_PROCESS_BOUNDS": "1,1,1",
        "TPU_PROCESS_PORT": str(port),
        "TPU_PROCESS_ADDRESSES": f"localhost:{port}",
    }


@contextlib.contextmanager
def pinned_to_chip(index: int):
    """A process started inside this block sees chip ``index`` only.

    Spawned processes copy the parent's environment when they start, so
    the settings are in place before the worker imports JAX; the parent's
    own environment is restored on exit. On a host without a TPU the
    settings are inert.
    """
    env = chip_env(index)
    saved = {k: os.environ.get(k) for k in env}
    os.environ.update(env)
    try:
        yield env
    finally:
        for k, v in saved.items():
            if v is None:
                os.environ.pop(k, None)
            else:
                os.environ[k] = v


def _chip_device_files() -> list[str]:
    """The TPU device nodes this process holds open, as the OS sees them."""
    held = set()
    for fd in Path("/proc/self/fd").iterdir():
        try:
            target = os.readlink(fd)
        except OSError:          # closed between listing and reading
            continue
        if re.fullmatch(r"/dev/(accel\d+|vfio/\d+)", target):
            held.add(target)
    return sorted(held)


def device_report() -> dict:
    """The device this process computes on, as JAX reports it, plus the
    chip device nodes the process holds (which chip it really owns)."""
    import jax

    devices = jax.devices()
    d = devices[0]
    return {
        "platform": d.platform,
        "kind": d.device_kind,
        "count": len(devices),
        "id": d.id,
        "hw_id": d.local_hardware_id,
        "files": _chip_device_files(),
    }
