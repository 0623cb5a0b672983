"""Batched serving engine: prefill + greedy decode over a KV/SSM cache.

Startup follows the stable-linking epoch path (table-driven weight load +
AOT compile cache) exactly like the trainer; request batches share one
cache. Greedy sampling keeps tests deterministic; the decode step is the
same jitted ``serve_step`` the dry-run lowers for decode shapes.

``ServeEngine.from_workspace`` is the epoch-resident spin-up path: params
are loaded through the process-wide ``EpochCache`` (default strategy
``stable-mmap-cached``), so N replicas constructed in one process read
their host-side weights from ONE shared read-only arena mapping — replica
spin-up after the first is a cache hit, not a remap.

``ServeEngine.spawn_fleet`` is the cross-PROCESS variant: it spawns N real
worker processes that load the same app via the ``stable-shm`` strategy, so
the whole machine shares one physical arena copy (at most one worker fills
the shm segment; everyone else attaches — ``repro.core.shm_arena``).
"""

from __future__ import annotations

import threading
import time
from dataclasses import dataclass, field

import jax
import jax.numpy as jnp
import numpy as np

from repro import models
from repro.core import spans
from repro.core.errors import AdoptDeadlineError

from . import faults


@dataclass
class ServeStats:
    prefill_s: float = 0.0
    decode_s: float = 0.0
    tokens_out: int = 0

    @property
    def tok_per_s(self) -> float:
        return self.tokens_out / self.decode_s if self.decode_s else 0.0


@dataclass
class FleetReport:
    """What one ``ServeEngine.spawn_fleet`` actually did, per worker."""

    processes: int
    strategy: str
    wall_s: float = 0.0
    workers: list = field(default_factory=list)   # one result dict each
    restarts: int = 0                # supervised workers respawned after death
    rerouted_requests: int = 0       # in-flight requests re-routed off a corpse

    @property
    def fills(self) -> int:
        """Workers that had to publish (fill) the shm segment — the
        exclusive-create protocol bounds this at 1 per segment, 0 when the
        segment was already warm. Failed workers never count as fills."""
        return sum(
            1
            for w in self.workers
            if not w.get("failed") and not w.get("shm_attached")
        )

    @property
    def attaches(self) -> int:
        return len(self.workers) - self.fills - self.failed

    @property
    def failed(self) -> int:
        """Workers that crashed (structured error records from
        ``run_fleet``: exit code + traceback excerpt, surfaced the moment
        the process dies instead of riding out the join timeout)."""
        return sum(1 for w in self.workers if w.get("failed"))

    @property
    def errors(self) -> list:
        """The failed workers' error records, ready for a log line."""
        return [
            {
                "pid": w.get("pid"),
                "exit_code": w.get("exit_code"),
                "error": w.get("error"),
                "traceback": w.get("traceback", ""),
            }
            for w in self.workers
            if w.get("failed")
        ]

    @property
    def segments(self) -> set:
        return {w.get("segment") for w in self.workers}

    def summary(self) -> dict:
        return {
            "processes": self.processes,
            "strategy": self.strategy,
            "wall_s": self.wall_s,
            "fills": self.fills,
            "attaches": self.attaches,
            "failed": self.failed,
            "errors": self.errors,
            "segments": sorted(s for s in self.segments if s),
            "pids": [w.get("pid") for w in self.workers],
            # honest even at zero: a fleet that never needed the supervisor
            # reports restarts=0, not a missing key
            "restarts": self.restarts,
            "rerouted_requests": self.rerouted_requests,
        }


class ServeEngine:
    def __init__(self, cfg, params, *, impl: str = "chunked", cache_len: int = 0):
        self.cfg = cfg
        self.params = params
        self.impl = impl
        self.cache_len = cache_len

        def _prefill(params, batch):
            return models.prefill(
                cfg, params, batch, impl=impl,
                cache_len=cache_len or None,
            )

        def _decode(params, cache, tokens):
            logits, cache = models.decode_step(cfg, params, cache, tokens)
            nxt = jnp.argmax(logits[:, -1], axis=-1).astype(jnp.int32)
            return nxt[:, None], cache

        self._prefill = jax.jit(_prefill)
        self._decode = jax.jit(_decode, donate_argnums=(1,))
        # set by from_workspace: the LoadStats of the epoch load that
        # produced self.params (None for hand-built params)
        self.load_stats = None
        # the slot scheduler's jitted programs, by sampling setting
        # (``scheduler.SlotScheduler``): every serve loop shares them
        self.slot_programs: dict = {}

    @classmethod
    def from_workspace(
        cls,
        cfg,
        ws,
        app_name: str,
        *,
        strategy: str = "stable-mmap-cached",
        impl: str = "chunked",
        cache_len: int = 0,
        param_builder=None,
    ) -> "ServeEngine":
        """Spin up a replica through the stable-linking epoch path.

        Loads ``app_name`` from the workspace with ``strategy`` (default:
        the epoch-resident cached load, so every same-process replica
        shares one arena mapping and spin-ups after the first are O(1)
        cache hits), lifts the tensors to device arrays, and returns the
        wired engine. ``param_builder(image) -> params`` overrides the
        default 1:1 symbol->param lift for models that need restructuring
        (e.g. stacking per-layer fragments); ``engine.load_stats`` carries
        the load's ``LoadStats`` for observability.
        """
        image = ws.load(app_name, strategy=strategy)
        # jnp.asarray copies host->device; the host source stays the one
        # shared mapping, so N replicas never duplicate it on host (lazy
        # images fault each symbol in on first access instead)
        params = cls._lift_params(image, param_builder)
        engine = cls(cfg, params, impl=impl, cache_len=cache_len)
        engine.load_stats = image.stats
        return engine

    @staticmethod
    def _lift_params(image, param_builder=None):
        """The image's tensors as device arrays; returns once every byte
        is on the device, so that the ``serve.lift`` span ends there."""
        with spans.span("serve.lift"):
            if param_builder is not None:
                params = param_builder(image)
            elif hasattr(image, "tensors"):
                params = {n: jnp.asarray(a) for n, a in image.tensors.items()}
            else:
                params = {n: jnp.asarray(image[n]) for n in image.keys()}
            return jax.block_until_ready(params)

    def _reload(self, ws, app_name, strategy, param_builder):
        """The wedgeable half of an epoch reload: load + lift (the caller
        refreshes first, on its own thread — a deadline-abandoned reload
        must not mutate workspace state). The fault hook at the top is
        what the chaos tier wedges/slows; returns (image, params) without
        touching ``self`` so an abandoned reload can never clobber the
        engine after a rollback already re-adopted the old weights."""
        faults.on_adopt_reload()
        image = ws.load(app_name, strategy=strategy)
        return image, self._lift_params(image, param_builder)

    def adopt_epoch(
        self,
        ws,
        app_name: str,
        *,
        strategy: str = "stable-mmap-cached",
        param_builder=None,
        deadline_s: float = 0.0,
    ):
        """Flip this engine onto a newly committed generation (blue/green).

        The write half of the ``ws.epoch_watch()`` handshake, called at a
        request boundary (no slot in flight): adopt the sibling commit
        (``ws.refresh()`` — token-bumps the epoch caches, retiring the old
        generation's entries without evicting pinned ones), reload the app
        from generation N+1, and swap ``self.params``. The jitted prefill/
        decode programs take params as arguments, so a same-shape roll
        recompiles nothing — the next admitted request simply decodes
        against the new weights. Returns the reloaded image (its
        ``tensors`` digest is what rollover tests verify against an
        independent fresh load of N+1).

        ``deadline_s > 0`` bounds how long a flip may wedge: the reload
        runs on a daemon thread and, if it has not finished inside the
        deadline, the engine **auto-rolls-back** — ``abort_adopt`` adopts
        the still-live previous generation as a NEW generation (so sibling
        watchers converge on it too), re-lifts the old weights, and this
        call raises :class:`repro.core.errors.AdoptDeadlineError` with
        ``rolled_back_to`` set. The serve loop treats that exception as
        "resume admission on the weights we already have": a wedged roll
        costs bounded stall, never a hung fleet. The abandoned reload
        thread only ever touches its local ``(image, params)`` pair, which
        is discarded.
        """
        with spans.span("serve.adopt"):
            return self._adopt(ws, app_name, strategy, param_builder,
                               deadline_s)

    def _adopt(self, ws, app_name, strategy, param_builder, deadline_s):
        ws.refresh()
        if deadline_s and deadline_s > 0:
            box: dict = {}

            def _run():
                try:
                    box["result"] = self._reload(
                        ws, app_name, strategy, param_builder
                    )
                except BaseException as e:   # surfaced below, not swallowed
                    box["error"] = e

            t = threading.Thread(
                target=_run, name="adopt-epoch-reload", daemon=True
            )
            t.start()
            t.join(deadline_s)
            if t.is_alive():
                gen = self.abort_adopt(
                    ws, app_name, strategy=strategy, param_builder=param_builder
                )
                raise AdoptDeadlineError(
                    f"adopt_epoch for {app_name!r} exceeded its "
                    f"{deadline_s:.3f}s deadline; rolled back to "
                    f"generation {gen}",
                    rolled_back_to=gen,
                )
            if "error" in box:
                raise box["error"]
            image, params = box["result"]
        else:
            image, params = self._reload(ws, app_name, strategy, param_builder)
        self.params = params
        self.load_stats = image.stats
        return image

    def abort_adopt(
        self,
        ws,
        app_name: str,
        *,
        strategy: str = "stable-mmap-cached",
        param_builder=None,
    ) -> int:
        """Abandon a wedged flip: roll the *store* back, then re-adopt.

        ``ws.rollback_epoch()`` re-publishes the newest retained world as a
        brand-new generation (monotone ``epoch_gen``, ``rolled_back_from``
        marker in state), so every sibling's EpochWatch converges on the
        rollback exactly like a commit. This engine then reloads through
        the normal path — byte-identical to what it served before the flip
        started — and returns the new generation number. The abort reload
        deliberately bypasses the ``faults.on_adopt_reload`` hook: a
        wedge-on-adopt plan must not be able to wedge the rollback that
        rescues the fleet from it.
        """
        gen = ws.rollback_epoch()
        ws.refresh()
        image = ws.load(app_name, strategy=strategy)
        self.params = self._lift_params(image, param_builder)
        self.load_stats = image.stats
        return gen

    @classmethod
    def spawn_fleet(
        cls,
        ws,
        app_name: str,
        *,
        processes: int = 2,
        strategy: str = "stable-shm",
        cfg=None,
        max_new: int = 0,
        timeout: float = 180.0,
        store_url: str | None = None,
    ) -> FleetReport:
        """Spawn a true multi-process serving fleet over one workspace.

        Each of the ``processes`` workers is a real OS process (spawn
        context — jax state is never forked) that opens the workspace at
        ``ws.root`` and loads ``app_name`` with ``strategy`` (default
        ``stable-shm``): the first worker on the machine publishes the
        baked arena into a named shm segment, every other replica attaches
        to that one physical copy instead of re-mapping. With ``cfg`` (a
        ``ModelConfig``, handed to every worker as it is) set, each worker
        additionally constructs a full ``ServeEngine`` on its own chip and
        greedy-decodes ``max_new`` tokens, proving end-to-end serving from
        the shared segment. Returns a ``FleetReport`` (fills/attaches per
        the one-fill-per-machine contract, per-worker load stats and
        tensor digests for byte-identity checks).

        ``store_url`` hands every worker a served arena store
        (``repro.launch.store``) to fetch missing bakes from — pair it
        with ``strategy="stable-remote"`` for the download-then-publish
        fleet warm-start.
        """
        from repro.core.shm_arena import run_fleet

        t0 = time.perf_counter()
        workers = run_fleet(
            ws.root,
            app_name,
            processes=processes,
            strategy=strategy,
            cfg=cfg,
            max_new=max_new,
            timeout=timeout,
            store_url=store_url,
        )
        return FleetReport(
            processes=processes,
            strategy=strategy,
            wall_s=time.perf_counter() - t0,
            workers=workers,
        )

    def generate(
        self,
        prompts: np.ndarray,
        max_new_tokens: int,
        *,
        host_sync: bool = False,
    ) -> tuple[np.ndarray, ServeStats]:
        """prompts: (B, S) int32 -> (B, max_new_tokens) greedy continuations.

        The decode loop accumulates tokens DEVICE-side and pays one host
        transfer after the final step. ``host_sync=True`` restores the old
        behaviour (``np.asarray`` per iteration, blocking the host on the
        device every step) — kept only so ``benchmarks/serve_load.py`` can
        report the before/after cost of that per-step sync."""
        stats = ServeStats()
        batch = {"tokens": jnp.asarray(prompts, jnp.int32)}
        if self.cfg.is_encdec:
            # modality stub: frames derived deterministically from prompts
            rng = np.random.default_rng(0)
            batch["frames"] = jnp.asarray(
                rng.standard_normal(
                    (prompts.shape[0], prompts.shape[1], self.cfg.d_model)
                ),
                jnp.dtype(self.cfg.dtype),
            )
        t0 = time.perf_counter()
        logits, cache = self._prefill(self.params, batch)
        tok = jnp.argmax(logits[:, -1], axis=-1).astype(jnp.int32)[:, None]
        jax.block_until_ready(tok)
        stats.prefill_s = time.perf_counter() - t0

        out = [tok]
        t1 = time.perf_counter()
        if host_sync:
            # legacy path: one blocking device->host round-trip per token
            host = [np.asarray(tok)]
            for _ in range(max_new_tokens - 1):
                tok, cache = self._decode(self.params, cache, tok)
                host.append(np.asarray(tok))
            jax.block_until_ready(tok)
            stats.decode_s = time.perf_counter() - t1
            stats.tokens_out = prompts.shape[0] * max_new_tokens
            return np.concatenate(host, axis=1), stats
        for _ in range(max_new_tokens - 1):
            tok, cache = self._decode(self.params, cache, tok)
            out.append(tok)
        jax.block_until_ready(tok)
        stats.decode_s = time.perf_counter() - t1
        stats.tokens_out = prompts.shape[0] * max_new_tokens
        return np.asarray(jnp.concatenate(out, axis=1)), stats

    def serve_loop(
        self,
        source,
        sink,
        *,
        max_batch: int = 4,
        max_queue: int = 16,
        max_new_cap: int = 0,
        epoch_watch=None,
        on_epoch=None,
        temperature: float = 0.0,
        top_k: int = 0,
        sampling_seed: int = 0,
        on_delta=None,
        priority_aging_s: float = 0.05,
    ):
        """Continuous batching: admit requests into open decode slots.

        Unlike ``generate`` (a static batch that starts and finishes
        together), this runs a fixed pool of ``max_batch`` slots, each
        holding one request's private cache row, admitted and retired
        independently at every decode step — the serving-tier loop the shm
        traffic plane (``repro.serve.traffic``) drives. ``source()``
        yields ``scheduler.Request | None | scheduler.STOP``; finished
        ``scheduler.Completion``s go to ``sink``. Requires a positive
        ``cache_len`` (slot K/V rows need decode headroom past the
        prompt). Returns a ``scheduler.ServeLoopReport``.

        ``temperature``/``top_k``/``sampling_seed`` switch the vmapped
        decode step from greedy argmax to temperature (optionally top-k)
        sampling with per-request PRNG keys; ``on_delta`` streams every
        decoded token as a ``scheduler.TokenDelta`` the step it is
        sampled; ``priority_aging_s`` bounds priority-class starvation.
        """
        from . import scheduler

        if self.cache_len <= 0 and self.cfg.family not in ("ssm",):
            raise ValueError(
                "serve_loop needs an engine built with cache_len > "
                "prompt_len + max_new_tokens (slot K/V rows need decode "
                "headroom)"
            )
        return scheduler.run_serve_loop(
            self,
            source,
            sink,
            max_batch=max_batch,
            max_queue=max_queue,
            max_new_cap=max_new_cap,
            epoch_watch=epoch_watch,
            on_epoch=on_epoch,
            temperature=temperature,
            top_k=top_k,
            sampling_seed=sampling_seed,
            on_delta=on_delta,
            priority_aging_s=priority_aging_s,
        )
