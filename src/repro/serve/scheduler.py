"""Continuous batching: admit requests into open decode slots mid-flight.

``ServeEngine.generate`` runs a *static* batch — every sequence starts and
ends together, so a 4-slot batch serving one straggler wastes 3 slots for
the whole tail. This module replaces that with the standard serving-tier
discipline: a fixed pool of ``max_batch`` decode slots, each holding one
request's private cache row (KV for transformers, conv/ssm state for
mamba2/hybrid — the per-request ``InferenceCache`` idiom), admitted and
retired independently at every decode step.

The trick that keeps this jit-friendly across all three model families:
every family's decode cache is a pytree whose array leaves carry batch at
axis 1 (``(L, B, ...)``) with a scalar ``pos``. A slot is a B=1 cache; the
pool stacks slot caches on a NEW leading axis (``(slots, L, 1, ...)``,
``pos`` becomes ``(slots,)``) and one ``jax.vmap`` of ``models.decode_step``
advances every slot in a single compiled dispatch — per-slot positions,
per-slot RoPE phases, per-slot ring-buffer writes all fall out of the vmap.
Admission splices a freshly prefilled B=1 cache into its slot with
``dynamic_update_slice`` (donated, so it is an in-place row write on the
device buffer).

Host/device contract (this is where PR 6's satellite fix generalizes):
the decode loop never syncs per step. Sampled tokens are scattered into a
device-side ``out_buf`` at per-slot step indices; the host mirrors the
step counters deterministically (it issued the steps, so it knows them)
and pays exactly ONE device sync per *completed* request — fetching that
request's finished row.

Crash/queue policy: ``max_queue`` bounds accepted-but-unadmitted requests
(the backpressure signal the shm rings surface to the dispatcher), and the
loop drains queue + in-flight slots after the source signals STOP.
"""

from __future__ import annotations

import threading
import time
from dataclasses import dataclass, field, replace

import jax
import jax.numpy as jnp
import numpy as np

from repro import models
from repro.core import spans
from repro.core.errors import EpochAdoptError

from . import faults

#: Source sentinel: no more requests will ever arrive; drain and return.
STOP = object()


@dataclass(frozen=True)
class Request:
    """One unit of traffic: a prompt and how far to decode it.

    ``enqueued_ts`` is the dispatcher's ``time.monotonic()`` stamp —
    ``None`` (not ``0.0``: zero is a representable clock reading) means no
    dispatcher clock exists and the serve loop rebases the deadline to its
    own acceptance time. ``priority`` is an admission class: higher admits
    first, FIFO within a class, and waiting requests age upward so a low
    class is starvation-bounded rather than starved.
    """

    rid: int
    prompt: np.ndarray               # (S,) int32
    max_new_tokens: int
    enqueued_ts: float | None = None  # dispatcher clock; None = no clock
    deadline_s: float = 0.0          # seconds after enqueue; 0 = no deadline
    priority: int = 0                # admission class; higher admits first

    def expired(self, now: float) -> bool:
        """Past its deadline (measured from enqueue; every stamp in the
        serving tier is ``time.monotonic()`` = CLOCK_MONOTONIC on Linux,
        the system-wide clock that makes a dispatcher-stamped enqueue
        comparable inside a worker process)."""
        return (
            self.deadline_s > 0.0
            and self.enqueued_ts is not None
            and now - self.enqueued_ts > self.deadline_s
        )


@dataclass(frozen=True)
class TokenDelta:
    """One streamed decode increment: ``tokens`` are sequence positions
    ``seq .. seq + len(tokens) - 1`` of request ``rid``'s continuation.
    The consumer reassembles deltas by ``seq`` — arrival order is already
    correct on one ring, but a re-routed request restarts at seq 0."""

    rid: int
    seq: int
    tokens: tuple                    # ints; a span, usually length 1


@dataclass
class Completion:
    """A finished request: its continuation + latency breakdown."""

    rid: int
    tokens: np.ndarray               # (max_new_tokens,) int32
    admitted_ts: float
    finished_ts: float
    enqueued_ts: float | None = None
    status: str = "ok"               # "ok" | "deadline" (expired, partial)

    @property
    def latency_s(self) -> float:
        """Queue-to-finish when the enqueue time is known, else
        admit-to-finish."""
        start = (
            self.enqueued_ts if self.enqueued_ts is not None
            else self.admitted_ts
        )
        return self.finished_ts - start


@dataclass
class ServeLoopReport:
    """What one ``serve_loop`` invocation did."""

    completed: int = 0
    admitted: int = 0
    steps: int = 0                   # batched decode dispatches
    tokens_out: int = 0
    peak_active: int = 0
    peak_queue: int = 0
    rejected: int = 0                # source offers refused (queue full)
    wall_s: float = 0.0
    rollovers: int = 0               # epoch flips taken at a request boundary
    rollover_stall_s: float = 0.0    # commit noticed -> flip complete, summed
    coalesced_rollovers: int = 0     # commits superseded before their flip
    rollover_aborts: int = 0         # flips that deadlined and rolled back
    deadline_expired: int = 0        # requests retired with a DEADLINE frame
    admitted_by_priority: dict = field(default_factory=dict)  # class -> count
    priority_aged: int = 0           # admissions that out-ranked a higher class
    deltas_out: int = 0              # streamed TokenDelta frames emitted
    # programs lowered on the loop's thread while it ran, by the innermost
    # span open at the time ("serve_loop" where none was): what recompiled
    # while serving
    programs_lowered: dict = field(default_factory=dict)

    def summary(self) -> dict:
        return {
            "completed": self.completed,
            "admitted": self.admitted,
            "steps": self.steps,
            "tokens_out": self.tokens_out,
            "peak_active": self.peak_active,
            "peak_queue": self.peak_queue,
            "rejected": self.rejected,
            "wall_s": self.wall_s,
            "rollovers": self.rollovers,
            "rollover_stall_s": self.rollover_stall_s,
            "coalesced_rollovers": self.coalesced_rollovers,
            "rollover_aborts": self.rollover_aborts,
            "deadline_expired": self.deadline_expired,
            "admitted_by_priority": dict(self.admitted_by_priority),
            "priority_aged": self.priority_aged,
            "deltas_out": self.deltas_out,
            "programs_lowered": dict(self.programs_lowered),
        }


@dataclass
class _Slot:
    """Host-side mirror of one device slot (the scheduler's bookkeeping)."""

    request: Request
    admitted_ts: float
    steps_done: int                  # tokens already in out_buf for this slot
    first_token: int = -1            # prefill's token, host-side iff streaming


def _programs(cfg, temp: float, top_k_n: int):
    """The slot scheduler's sampler and its two jitted programs: ``_step``
    (vmap-advance every slot one token) and ``_admit`` (splice one B=1
    cache row in)."""

    def _pick(logits, key, pos):
        # greedy vs sampled is a Python-static branch: temperature is
        # a constructor constant baked into the compiled program
        if temp <= 0.0:
            return jnp.argmax(logits, axis=-1).astype(jnp.int32)
        lg = logits / temp
        if top_k_n > 0:
            kth = jax.lax.top_k(lg, top_k_n)[0][..., -1]
            lg = jnp.where(lg < kth, -jnp.inf, lg)
        return jax.random.categorical(
            jax.random.fold_in(key, pos), lg
        ).astype(jnp.int32)

    def _step(params, cache, toks, out_buf, steps, keys, active):
        def one(c, t, k, s):
            logits, c = models.decode_step(cfg, params, c, t)
            return _pick(logits[0, -1], k, s), c

        nxt, cache = jax.vmap(one)(cache, toks, keys, steps)
        nxt = jnp.where(active, nxt, 0)
        row = jnp.arange(out_buf.shape[0])
        idx = jnp.clip(steps, 0, out_buf.shape[1] - 1)
        out_buf = out_buf.at[row, idx].set(
            jnp.where(active, nxt, out_buf[row, idx])
        )
        steps = steps + active.astype(jnp.int32)
        return cache, nxt[:, None, None], out_buf, steps, keys

    def _admit(cache, toks, out_buf, steps, keys, row_cache, tok0,
               row_key, idx):
        cache = jax.tree_util.tree_map(
            lambda s, r: jax.lax.dynamic_update_slice_in_dim(
                s, r[None].astype(s.dtype), idx, 0
            ),
            cache,
            row_cache,
        )
        zrow = jnp.zeros((1, out_buf.shape[1]), jnp.int32)
        zrow = zrow.at[0, 0].set(tok0)
        out_buf = jax.lax.dynamic_update_slice_in_dim(out_buf, zrow, idx, 0)
        steps = jax.lax.dynamic_update_slice_in_dim(
            steps, jnp.ones((1,), jnp.int32), idx, 0
        )
        toks = jax.lax.dynamic_update_slice(
            toks, tok0.reshape(1, 1, 1).astype(jnp.int32), (idx, 0, 0)
        )
        keys = jax.lax.dynamic_update_slice_in_dim(
            keys, row_key[None].astype(keys.dtype), idx, 0
        )
        return cache, toks, out_buf, steps, keys

    # donate the stacked state: both programs are in-place row updates
    return (
        _pick,
        jax.jit(_step, donate_argnums=(1, 2, 3, 4, 5)),
        jax.jit(_admit, donate_argnums=(0, 1, 2, 3, 4)),
    )


class SlotScheduler:
    """The device half of continuous batching for one ``ServeEngine``.

    Owns the stacked slot state (caches, next-token feeds, ``out_buf``,
    per-slot PRNG keys, step counters) and the two jitted programs that
    mutate it: ``_step`` (vmap-advance every slot one token) and ``_admit``
    (splice one B=1 cache row in). Built lazily on first admission so the
    slot template matches whatever cache pytree the model family actually
    produces.

    Sampling: ``temperature > 0`` replaces greedy argmax with temperature
    (optionally top-k) sampling *inside* the vmapped step. Token ``i`` of
    request ``rid`` is drawn with ``fold_in(fold_in(base, rid), i)`` where
    ``base = PRNGKey(sampling_seed)`` — a pure function of (seed, rid, i),
    so a mid-flight admitted row never reuses a sibling slot's key stream,
    a re-routed request replays the identical continuation on another
    worker, and streaming vs non-streaming modes are byte-identical. The
    per-request key is spliced into the stacked ``keys`` state by the same
    donated ``_admit`` program that splices the cache row.
    """

    def __init__(
        self,
        engine,
        *,
        max_batch: int,
        max_new_cap: int = 0,
        temperature: float = 0.0,
        top_k: int = 0,
        sampling_seed: int = 0,
        stream: bool = False,
    ):
        if max_batch < 1:
            raise ValueError("max_batch must be >= 1")
        self.engine = engine
        self.slots = max_batch
        self.max_new_cap = max_new_cap   # out_buf width; 0 = first admit's
        self.temperature = float(temperature)
        self.top_k = int(top_k)
        self.stream = stream
        self._base_key = jax.random.PRNGKey(sampling_seed)
        self._state = None           # (cache, toks, out_buf, steps, keys)
        self.active = np.zeros(max_batch, dtype=bool)
        self.slot_meta: list[_Slot | None] = [None] * max_batch

        # one set of programs per engine and sampling setting: a later loop
        # on the same engine reuses them instead of lowering them again
        key = (self.temperature, self.top_k)
        if key not in engine.slot_programs:
            engine.slot_programs[key] = _programs(engine.cfg, *key)
        self._pick, self._step_fn, self._admit_fn = engine.slot_programs[key]

    def _request_key(self, rid: int):
        """The per-request PRNG key: fold the 64-bit rid into the base in
        two 32-bit halves (warmup rids exceed uint32)."""
        k = jax.random.fold_in(self._base_key, rid & 0xFFFFFFFF)
        return jax.random.fold_in(k, (rid >> 32) & 0xFFFFFFFF)

    # --------------------------------------------------------------- state
    @property
    def free_slots(self) -> list[int]:
        return [i for i in range(self.slots) if not self.active[i]]

    @property
    def n_active(self) -> int:
        return int(self.active.sum())

    def _init_state(self, row_cache, max_new_cap: int) -> None:
        self.max_new_cap = max_new_cap
        cache = jax.tree_util.tree_map(
            lambda r: jnp.zeros((self.slots,) + np.shape(r), r.dtype),
            row_cache,
        )
        self._state = (
            cache,
            jnp.zeros((self.slots, 1, 1), jnp.int32),
            jnp.zeros((self.slots, max_new_cap), jnp.int32),
            jnp.zeros((self.slots,), jnp.int32),
            jnp.zeros((self.slots,) + self._base_key.shape,
                      self._base_key.dtype),
        )

    # ------------------------------------------------------------ protocol
    def admit(self, req: Request, now: float) -> int:
        """Prefill ``req`` and splice its cache into a free slot.

        Returns the slot index. The prefill is the engine's own jitted
        closure, so requests with equal prompt lengths share one compiled
        prefill program."""
        free = self.free_slots
        if not free:
            raise RuntimeError("admit called with no free slot")
        idx = free[0]
        eng = self.engine
        with spans.span("serve.admit.prefill"):
            batch = {"tokens": jnp.asarray(req.prompt, jnp.int32)[None]}
            if eng.cfg.is_encdec:
                rng = np.random.default_rng(0)
                batch["frames"] = jnp.asarray(
                    rng.standard_normal(
                        (1, req.prompt.shape[0], eng.cfg.d_model)
                    ),
                    jnp.dtype(eng.cfg.dtype),
                )
            logits, row_cache = eng._prefill(eng.params, batch)
        with spans.span("serve.admit.sample"):
            row_key = self._request_key(req.rid)
            tok0 = self._pick(logits[0, -1], row_key, 0)
        with spans.span("serve.admit.splice"):
            if self._state is None:
                self._init_state(
                    row_cache, self.max_new_cap or max(req.max_new_tokens, 8)
                )
            if req.max_new_tokens > self.max_new_cap:
                raise ValueError(
                    f"request {req.rid} wants {req.max_new_tokens} tokens "
                    f"but this loop's out_buf holds {self.max_new_cap}; "
                    "admit the longest request first or pass max_new_cap "
                    "to serve_loop"
                )
            cache, toks, out_buf, steps, keys = self._state
            self._state = self._admit_fn(
                cache, toks, out_buf, steps, keys, row_cache, tok0, row_key,
                jnp.int32(idx),
            )
        self.active[idx] = True
        meta = _Slot(request=req, admitted_ts=now, steps_done=1)
        if self.stream:
            # streaming pays one extra scalar sync per ADMIT (not per
            # step) so the prefill token can ride the first PARTIAL frame
            with spans.span("serve.admit.sync"):
                meta.first_token = int(tok0)
        self.slot_meta[idx] = meta
        return idx

    def step(self) -> list[TokenDelta] | None:
        """Advance every active slot one token (one compiled dispatch).

        Returns the per-slot token deltas when streaming (one host sync
        of the (slots,) next-token feed — the per-token cost streaming
        inherently pays), else None (no sync; tokens stay device-side
        until ``pop_finished``)."""
        cache, toks, out_buf, steps, keys = self._state
        with spans.span("serve.step.dispatch"):
            cache, toks, out_buf, steps, keys = self._step_fn(
                self.engine.params, cache, toks, out_buf, steps, keys,
                jnp.asarray(self.active),
            )
        self._state = (cache, toks, out_buf, steps, keys)
        deltas: list[TokenDelta] | None = None
        if self.stream:
            with spans.span("serve.step.sync"):
                feed = np.asarray(toks)      # (slots, 1, 1): just-sampled
            deltas = [
                TokenDelta(
                    rid=meta.request.rid,
                    seq=meta.steps_done,     # tokens already out = position
                    tokens=(int(feed[i, 0, 0]),),
                )
                for i, meta in enumerate(self.slot_meta)
                if meta is not None
            ]
        for meta in self.slot_meta:
            if meta is not None:
                meta.steps_done += 1
        return deltas

    def pop_finished(self, now: float) -> list[Completion]:
        """Retire every slot whose host-mirrored step count hit its target.

        The ONE host sync per request happens here: fetching the finished
        ``out_buf`` row."""
        done: list[Completion] = []
        out_buf = self._state[2] if self._state is not None else None
        for idx, meta in enumerate(self.slot_meta):
            if meta is None:
                continue
            want = meta.request.max_new_tokens
            if meta.steps_done >= want:
                row = np.asarray(out_buf[idx])[:want]
                done.append(
                    Completion(
                        rid=meta.request.rid,
                        tokens=row,
                        admitted_ts=meta.admitted_ts,
                        finished_ts=now,
                        enqueued_ts=meta.request.enqueued_ts,
                    )
                )
                self.active[idx] = False
                self.slot_meta[idx] = None
        return done

    def expire(self, now: float) -> list[Completion]:
        """Retire every in-flight slot whose request blew its deadline.

        The slot's partial row comes back in a ``status="deadline"``
        completion — the request is *answered* (a structured DEADLINE
        frame on the wire), never silently dropped, and its slot frees
        immediately instead of decoding tokens nobody is waiting for.
        """
        done: list[Completion] = []
        out_buf = self._state[2] if self._state is not None else None
        for idx, meta in enumerate(self.slot_meta):
            if meta is None or not meta.request.expired(now):
                continue
            got = min(meta.steps_done, self.max_new_cap)
            row = (
                np.asarray(out_buf[idx])[:got]
                if out_buf is not None
                else np.zeros((0,), np.int32)
            )
            done.append(
                Completion(
                    rid=meta.request.rid,
                    tokens=row,
                    admitted_ts=meta.admitted_ts,
                    finished_ts=now,
                    enqueued_ts=meta.request.enqueued_ts,
                    status="deadline",
                )
            )
            self.active[idx] = False
            self.slot_meta[idx] = None
        return done


def run_serve_loop(
    engine,
    source,
    sink,
    *,
    max_batch: int = 4,
    max_queue: int = 16,
    max_new_cap: int = 0,
    idle_sleep_s: float = 0.0005,
    epoch_watch=None,
    on_epoch=None,
    watch_interval_s: float = 0.02,
    temperature: float = 0.0,
    top_k: int = 0,
    sampling_seed: int = 0,
    on_delta=None,
    priority_aging_s: float = 0.05,
) -> ServeLoopReport:
    """Drive continuous batching until the source signals ``STOP``.

    ``source()`` is polled for ``Request | None | STOP`` whenever the
    accepted-queue has room (None = nothing right now; the loop keeps
    decoding). Each ``Completion`` is handed to ``sink`` the step its
    request finishes. ``max_queue`` bounds requests accepted but not yet
    admitted — when full, the source simply isn't polled, which a
    ring-backed source surfaces to the dispatcher as backpressure.

    **Blue/green rollover** (``epoch_watch`` + ``on_epoch``): between
    decode steps the loop polls ``epoch_watch.poll()`` (a throttled
    two-int stat probe; ``link.workspace.EpochWatch``). When a sibling
    process's commit lands generation N+1, the loop stops *admitting* —
    traffic keeps being accepted into the queue, nothing is dropped — and
    lets every in-flight slot finish on generation N. At the first empty
    request boundary it calls ``on_epoch(change)`` (typically
    ``engine.adopt_epoch``) to swap the params, then resumes admission:
    every later request decodes against N+1. The report counts
    ``rollovers`` and the summed ``rollover_stall_s`` (commit noticed ->
    flip complete).

    Hardening semantics (the chaos tier's contract):

    * **Coalescing** — the watch keeps polling while a flip is pending,
      so back-to-back commits landing mid-drain collapse into ONE flip to
      the newest generation (``coalesced_rollovers`` counts the commits
      superseded on the way).
    * **Abort** — if ``on_epoch`` raises ``EpochAdoptError`` (e.g.
      ``engine.adopt_epoch(deadline_s=...)`` deadlined and auto-rolled
      back), the loop counts a ``rollover_abort`` and resumes admission
      immediately on the generation the engine already re-adopted.
    * **Deadlines** — a ``Request.deadline_s`` bounds queue-to-finish;
      expired requests (queued or in-flight) are retired with a
      ``status="deadline"`` completion carrying whatever partial row they
      earned — a structured DEADLINE frame, never a silent drop.

    **Priority admission**: the accepted queue admits by priority class
    (higher first), FIFO within a class. Starvation is bounded by aging —
    a request's effective priority gains one class per ``priority_aging_s``
    it has waited, so a saturating high-priority stream delays a low
    request by at most ``(gap) * priority_aging_s``, never forever.
    ``admitted_by_priority`` counts admissions per static class and
    ``priority_aged`` counts admissions that out-ranked a queued higher
    static class purely through age.

    **Streaming** (``on_delta``): when given, every decoded token is
    surfaced as a ``TokenDelta(rid, seq, tokens)`` the step it is sampled
    (the prefill token as seq 0 at admission), in seq order per request —
    the per-token frames the traffic plane forwards as PARTIAL frames.

    **Sampling**: ``temperature``/``top_k``/``sampling_seed`` select
    temperature (optionally top-k) sampling in the vmapped decode step;
    per-request PRNG keys are derived as ``fold_in(base, rid)`` so
    continuations are reproducible regardless of batch composition.
    All timestamps are ``time.monotonic()`` — the system-wide
    CLOCK_MONOTONIC that makes dispatcher-stamped enqueue times
    comparable here, in a different process.
    """
    report = ServeLoopReport()
    sched = SlotScheduler(
        engine, max_batch=max_batch, max_new_cap=max_new_cap,
        temperature=temperature, top_k=top_k, sampling_seed=sampling_seed,
        stream=on_delta is not None,
    )
    queue: list[tuple[Request, int, float]] = []  # (req, arrival, accepted_ts)
    arrivals = 0
    draining = False
    pending_epoch = None             # EpochChange waiting for the boundary
    next_watch = 0.0
    stall_t0 = 0.0
    t0 = time.monotonic()
    t0_ns = time.monotonic_ns()

    def _pick_next(now: float) -> Request:
        """Priority-then-FIFO with aging: highest effective class wins,
        oldest arrival breaks ties within a class."""
        best = None
        for entry in queue:
            req, arrival, accepted = entry
            eff = req.priority
            if priority_aging_s > 0:
                eff += int((now - accepted) / priority_aging_s)
            key = (eff, -arrival)
            if best is None or key > best[0]:
                best = (key, entry)
        _, entry = best
        queue.remove(entry)
        req = entry[0]
        if any(q.priority > req.priority for q, _, _ in queue):
            report.priority_aged += 1
        by = report.admitted_by_priority
        by[req.priority] = by.get(req.priority, 0) + 1
        return req

    while True:
        # 0) rollover handshake: notice a landed commit (throttled), flip
        # at a request boundary — never mid-decode for any in-flight slot
        # Polling CONTINUES while a flip is pending: back-to-back commits
        # landing mid-drain coalesce to the newest generation (one flip,
        # counted per superseded commit), instead of queueing stale flips.
        now = time.monotonic()
        if epoch_watch is not None and now >= next_watch:
            next_watch = now + watch_interval_s
            change = epoch_watch.poll()
            if change is not None:
                if pending_epoch is None:
                    stall_t0 = now
                else:
                    report.coalesced_rollovers += 1
                pending_epoch = change
        if pending_epoch is not None and sched.n_active == 0:
            if on_epoch is not None:
                try:
                    on_epoch(pending_epoch)
                except EpochAdoptError:
                    # deadline fired and the engine already rolled back to
                    # the still-live generation: resume admission on the
                    # weights we have — a wedged flip never hangs the loop
                    report.rollover_aborts += 1
            report.rollovers += 1
            report.rollover_stall_s += time.monotonic() - stall_t0
            pending_epoch = None

        # 1) accept traffic while there is queue room (rollover included:
        # requests queue up during the drain instead of being dropped)
        if not draining and len(queue) < max_queue:
            with spans.span("serve.accept"):
                while not draining and len(queue) < max_queue:
                    got = source()
                    if got is None:
                        break
                    if got is STOP:
                        draining = True
                        break
                    now = time.monotonic()
                    if got.deadline_s > 0 and got.enqueued_ts is None:
                        # local source with no dispatcher clock (None, NOT
                        # a zero reading — 0.0 is a representable
                        # monotonic stamp): the deadline counts from
                        # acceptance, or it could never fire
                        got = replace(got, enqueued_ts=now)
                    queue.append((got, arrivals, now))
                    arrivals += 1
        report.peak_queue = max(report.peak_queue, len(queue))

        # 1b) deadline sweep — queued requests first (they expire without
        # ever costing a prefill), then in-flight slots (freed with their
        # partial row). Either way the caller gets a structured DEADLINE
        # completion; nothing is silently dropped.
        now = time.monotonic()
        if queue:
            still = []
            for entry in queue:
                req = entry[0]
                if req.expired(now):
                    report.deadline_expired += 1
                    sink(
                        Completion(
                            rid=req.rid,
                            tokens=np.zeros((0,), np.int32),
                            admitted_ts=now,
                            finished_ts=now,
                            enqueued_ts=req.enqueued_ts,
                            status="deadline",
                        )
                    )
                else:
                    still.append(entry)
            queue = still
        for comp in sched.expire(now):
            report.deadline_expired += 1
            sink(comp)

        # 2) admit into free slots (prefill interleaves with decode here);
        # held back while a generation flip waits for in-flight slots
        now = time.monotonic()
        while pending_epoch is None and queue and sched.free_slots:
            req = _pick_next(now)
            with spans.span("serve.admit", rid=req.rid):
                idx = sched.admit(req, now)
                report.admitted += 1
                if on_delta is not None:
                    meta = sched.slot_meta[idx]
                    on_delta(
                        TokenDelta(rid=req.rid, seq=0,
                                   tokens=(meta.first_token,))
                    )
                    report.deltas_out += 1
        report.peak_active = max(report.peak_active, sched.n_active)

        # 3) advance every active slot one token
        n_active = sched.n_active
        if n_active:
            with spans.span("serve.step", n_active=n_active):
                faults.on_decode_step(report.steps + 1)
                deltas = sched.step()
                report.steps += 1
                if on_delta is not None and deltas:
                    with spans.span("serve.step.emit"):
                        for d in deltas:
                            on_delta(d)
                    report.deltas_out += len(deltas)

                # 4) retire finished requests (one host sync each)
                with spans.span("serve.step.retire"):
                    for comp in sched.pop_finished(time.monotonic()):
                        report.completed += 1
                        report.tokens_out += comp.tokens.shape[0]
                        sink(comp)
        elif queue:
            continue
        elif draining:
            break
        else:
            time.sleep(idle_sleep_s)

    report.wall_s = time.monotonic() - t0
    report.programs_lowered = _lowered_since(t0_ns)
    return report


def _lowered_since(t0_ns: int) -> dict:
    """Programs this thread lowered since ``t0_ns``, by the innermost span
    open at the time."""
    me = threading.get_ident()
    out: dict[str, int] = {}
    for e in spans.compile_events():
        if e.kind == "lower" and e.thread == me and e.t1_ns >= t0_ns:
            name = e.span or "serve_loop"
            out[name] = out.get(name, 0) + 1
    return out
