"""AOT compile cache — the compute-side relocation table.

The second late-binding tax an ML job pays at startup is JIT tracing +
XLA compilation. Stable linking's discipline applies verbatim: the program
(architecture x shape x mesh) cannot change during an epoch, so its compiled
executable is materialized at end_mgmt and *loaded* at job start.

Keys are content hashes over (program key, mesh key, world hash). The store
uses ``jax.experimental.serialize_executable``; where a serialized
executable cannot round-trip, the cache falls back to memory plus
recompilation, and ``CompileStats.cache_error`` says why.

jax is imported lazily — core/ stays importable without it.
"""

from __future__ import annotations

import hashlib
import pickle
import time
from dataclasses import dataclass, field
from pathlib import Path
from typing import Any, Callable, Optional


def cache_key(*parts: str) -> str:
    h = hashlib.blake2b(digest_size=16)
    for p in parts:
        h.update(p.encode())
        h.update(b"\x00")
    return h.hexdigest()


@dataclass
class CompileStats:
    key: str = ""
    source: str = ""          # "disk" | "memory" | "compiled"
    lower_s: float = 0.0
    compile_s: float = 0.0
    deserialize_s: float = 0.0
    cache_error: str = ""     # why the disk artifact was not read or written


@dataclass
class CompileCache:
    root: Path
    memory: dict[str, Any] = field(default_factory=dict)

    def path(self, key: str) -> Path:
        return Path(self.root) / f"{key[:32]}.jaxexe"

    def get_or_compile(
        self,
        key: str,
        lower_fn: Callable[[], Any],
        *,
        stats: Optional[CompileStats] = None,
    ):
        """Return a compiled executable for ``key``.

        ``lower_fn`` must return a ``jax.stages.Lowered`` (called only on
        cache miss). A disk artifact that fails to deserialize is compiled
        again, and an executable that fails to serialize stays in memory;
        either failure is recorded in ``stats.cache_error``.
        """
        stats = stats if stats is not None else CompileStats()
        stats.key = key
        if key in self.memory:
            stats.source = "memory"
            return self.memory[key], stats

        p = self.path(key)
        if p.exists():
            try:
                from jax.experimental import serialize_executable as se

                t0 = time.perf_counter()
                payload = pickle.loads(p.read_bytes())
                compiled = se.deserialize_and_load(
                    payload["serialized"], payload["in_tree"], payload["out_tree"]
                )
                stats.deserialize_s = time.perf_counter() - t0
                stats.source = "disk"
                self.memory[key] = compiled
                return compiled, stats
            except Exception as e:  # stale/incompatible artifact
                stats.cache_error = f"deserialize: {type(e).__name__}: {e}"

        t0 = time.perf_counter()
        lowered = lower_fn()
        stats.lower_s = time.perf_counter() - t0
        t1 = time.perf_counter()
        compiled = lowered.compile()
        stats.compile_s = time.perf_counter() - t1
        stats.source = "compiled"
        self.memory[key] = compiled
        try:
            from jax.experimental import serialize_executable as se

            serialized, in_tree, out_tree = se.serialize(compiled)
            tmp = p.with_suffix(".tmp")
            tmp.write_bytes(
                pickle.dumps(
                    {
                        "serialized": serialized,
                        "in_tree": in_tree,
                        "out_tree": out_tree,
                    }
                )
            )
            tmp.rename(p)
        except Exception as e:  # unsupported on this backend: memory-only
            stats.cache_error = f"serialize: {type(e).__name__}: {e}"
        return compiled, stats
