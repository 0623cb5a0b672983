"""The traffic plane: Poisson load over shm rings into a serving fleet.

This is the subsystem that finally makes the ``spawn_fleet`` workers *serve
something*. Topology: one front-end dispatcher process and N workers, each
worker owning a private SPSC ring pair (``core.shm_ring``) —

    dispatcher --- <session>/req/<i> --->  worker i   (dispatcher-owned)
    dispatcher <-- <session>/rsp/<i> ----  worker i   (worker-owned)

so every shared cursor has exactly one writer and the whole request path is
two fixed-slot shm copies, no pipes, no pickling on the hot path. Ring
ownership is split deliberately: a SIGKILLed dispatcher leaves request
rings with a dead owner pid, a SIGKILLed worker leaves its response ring
with a dead owner pid — either way the next ``ws.gc()`` reclaims the
segment (``core.shm_arena.gc_segments``), which is the acceptance bar for
this subsystem.

Each worker is pinned to its own chip (``core.chips.pinned_to_chip``:
worker i holds chip i), loads the app through the stable-linking epoch
path (default ``stable-shm``: one physical arena copy machine-wide), builds
a ``ServeEngine`` for the ``ModelConfig`` the dispatcher hands it, and
runs ``engine.serve_loop`` — the continuous-batching scheduler — with its
rings as source and sink. The dispatcher drives
Poisson arrivals, round-robins requests across workers (ring-full = the
scheduler's ``max_queue`` backpressure, surfaced as a routing decision),
and measures what serving people actually report: sustained req/s, tok/s,
and p50/p99 end-to-end latency on the *dispatcher's* clock (enqueue time
rides the wire and comes back in the completion, so latency needs no
cross-process clock agreement beyond CLOCK_MONOTONIC being system-wide).

Wire format (fixed little-endian structs + int32 token payloads):

    request    <qiiddi> rid, max_new, n_tokens, enqueued_ts, deadline_s,
               priority + tokens (deadline_s: seconds from enqueue; 0 =
               none; enqueued_ts: the dispatcher's time.monotonic() stamp,
               NaN = no dispatcher clock — NaN, not 0.0, because zero is a
               representable clock reading that must rebase nothing)
    completion <qiiddd> rid, status, n_tokens, admitted, finished,
               enqueued + tokens (status: 0 ok, 1 DEADLINE — the request
               expired and came back with its partial row, never dropped;
               2 PARTIAL — a streamed token span: the ``admitted`` field
               carries the span's starting seq, the payload its tokens)
    rid sentinels: -1 STOP (drain and exit), -2 worker READY (engine
    built; ``admitted`` = spin-up seconds, payload = JSON
    ``core.chips.device_report``: platform, kind, count, device id and the
    chip device nodes held), -3 worker ERROR
    (payload = utf-8 traceback excerpt, surfaced in the report instead of
    a silent join timeout), -4 worker ADOPTED (blue/green flip complete;
    payload = JSON {worker, epoch_gen, digest} where digest content-hashes
    the tensors the worker now serves — the dispatcher verifies it against
    an independent load of the new generation).

**Streaming** (``run_traffic(..., stream=True)``): workers run the serve
loop with an ``on_delta`` sink, so every decoded token leaves as a
PARTIAL frame (rid + seq + span) the step it is sampled — the prefill
token as seq 0 at admission. The dispatcher reassembles spans by seq
(idempotent under duplicate delivery, so a re-routed request's replayed
stream is absorbed, not double-counted), records time-to-first-token per
request (``ttft_p50_s``/``ttft_p99_s``), and at completion verifies the
reassembled sequence against the completion frame's authoritative row:
gaps, duplicates, and mismatches are counted separately in the report
and are all zero in a healthy run. Per-request sampling keys are derived
from the rid, so a re-routed request re-streams byte-identical spans.

**MPMC rings** (``run_traffic(..., mpmc=True)``): request rings are
created in ``core.shm_ring``'s multi-producer mode (bakery-lock reserve ->
write -> publish) instead of SPSC — the topology that lets several
dispatcher processes feed one worker. The single-dispatcher drive is
unchanged; it just exercises the claim path end to end.

**Supervision** (``run_traffic(..., supervise=True)``): the dispatcher
doubles as a supervisor. A worker that dies — SIGKILL included — is
detected through its response ring's owner record (``core.shm_ring.
ring_owner_alive``: the dead pid is right there in shm, no waitpid race),
its in-flight requests are re-routed to surviving workers (request frames
are retained by rid, so the re-sent frame carries the ORIGINAL enqueue
time — re-routed latency is honest end-to-end), and the worker is
respawned with capped exponential backoff onto the SAME request ring: the
pop cursor lives in the shared header, so frames the corpse never popped
are simply consumed by its replacement. Duplicate completions (a frame
both replayed from the ring and re-routed) are deduped by rid. Respawned
workers get no fault plan — a chaos kill fires once.

**Blue/green rollover under load** (``run_traffic(..., rollover_at=...,
rollover_fn=...)``): after request ``rollover_at`` is sent, the dispatcher
runs ``rollover_fn`` — typically a management transaction republishing the
model and committing generation N+1. Each worker's serve loop notices the
commit via ``ws.epoch_watch()`` between requests, lets in-flight slots
finish on N, flips via ``engine.adopt_epoch`` at the empty request
boundary, pushes its ADOPTED frame, and keeps serving — zero requests
dropped, and the report segregates latencies measured while the flip was
in progress (``rollover_p99_s``) from steady state.
"""

from __future__ import annotations

import json
import math
import multiprocessing as mp
import struct
import time
import uuid
from dataclasses import dataclass, field

import numpy as np

from repro.core.chips import pinned_to_chip
from repro.core.shm_ring import ShmRing, ShmRingError, ring_owner_alive

# rid, max_new, n_toks, enqueued (NaN = no clock), deadline, priority
_REQ_HDR = struct.Struct("<qiiddi")
_RSP_HDR = struct.Struct("<qiiddd")  # rid, status, n_toks, admitted, fin, enq
_ST_OK = 0
_ST_DEADLINE = 1
_ST_PARTIAL = 2                      # streamed span; `admitted` carries seq
_STATUS_NAMES = {_ST_OK: "ok", _ST_DEADLINE: "deadline",
                 _ST_PARTIAL: "partial"}
_STATUS_CODES = {v: k for k, v in _STATUS_NAMES.items()}
_RID_STOP = -1
_RID_READY = -2
_RID_ERROR = -3
_RID_ADOPTED = -4                        # worker flipped to a new epoch_gen
_RID_WARM = 1 << 40                      # rids >= this are warmup traffic

RING_SLOTS = 64                          # per ring; queue depth per worker


# ------------------------------------------------------------------- wire
def encode_request(rid: int, prompt: np.ndarray, max_new: int,
                   enqueued_ts: float | None, deadline_s: float = 0.0,
                   priority: int = 0) -> bytes:
    toks = np.ascontiguousarray(prompt, dtype="<i4")
    enq = math.nan if enqueued_ts is None else enqueued_ts
    return (
        _REQ_HDR.pack(rid, max_new, toks.size, enq, deadline_s, priority)
        + toks.tobytes()
    )


def decode_request(data: bytes):
    rid, max_new, n, enq, deadline, priority = _REQ_HDR.unpack_from(data)
    if rid == _RID_STOP:
        return rid, None, 0, None, 0.0, 0
    toks = np.frombuffer(data, dtype="<i4", count=n, offset=_REQ_HDR.size)
    enq = None if math.isnan(enq) else enq
    return rid, toks.astype(np.int32), max_new, enq, deadline, priority


def encode_completion(rid: int, tokens: np.ndarray, admitted: float,
                      finished: float, enqueued: float | None,
                      status: str = "ok") -> bytes:
    toks = np.ascontiguousarray(tokens, dtype="<i4")
    enq = math.nan if enqueued is None else enqueued
    return (
        _RSP_HDR.pack(
            rid, _STATUS_CODES.get(status, _ST_OK), toks.size,
            admitted, finished, enq,
        )
        + toks.tobytes()
    )


def encode_partial(rid: int, seq: int, tokens, ts: float = 0.0) -> bytes:
    """One streamed span: tokens at positions seq..seq+len-1 of rid's
    continuation. The seq rides the `admitted` field (exact for any seq a
    ring could carry), the worker's push stamp rides `finished`."""
    toks = np.ascontiguousarray(tokens, dtype="<i4")
    return (
        _RSP_HDR.pack(rid, _ST_PARTIAL, toks.size, float(seq), ts, math.nan)
        + toks.tobytes()
    )


def _encode_blob(rid: int, blob: bytes, value: float = 0.0) -> bytes:
    return _RSP_HDR.pack(rid, _ST_OK, len(blob), value, 0.0, 0.0) + blob


def decode_completion(data: bytes):
    rid, status, n, admitted, finished, enq = _RSP_HDR.unpack_from(data)
    if rid < 0:
        blob = data[_RSP_HDR.size:_RSP_HDR.size + n]
        return rid, blob, admitted, 0.0, 0.0, "ok"
    toks = np.frombuffer(data, dtype="<i4", count=n, offset=_RSP_HDR.size)
    name = _STATUS_NAMES.get(status, "ok")
    enq = None if math.isnan(enq) else enq
    return rid, toks.astype(np.int32), admitted, finished, enq, name


def _push_blocking(ring: ShmRing, data: bytes, *, timeout: float) -> None:
    deadline = time.monotonic() + timeout
    while not ring.push(data):
        if time.monotonic() >= deadline:
            raise ShmRingError(
                f"ring {ring.name} stayed full for {timeout:.0f}s"
            )
        time.sleep(0.0005)


def req_channel(session: str, widx: int) -> str:
    return f"{session}/req/{widx}"


def rsp_channel(session: str, widx: int) -> str:
    return f"{session}/rsp/{widx}"


def ring_slot_bytes(prompt_len: int, max_new: int) -> int:
    """One slot must hold the largest frame either direction carries."""
    return max(
        _REQ_HDR.size + 4 * prompt_len,
        _RSP_HDR.size + 4 * max_new,
        _RSP_HDR.size + 2048,            # error tracebacks
    )


# ----------------------------------------------------------------- worker
def _traffic_worker(
    root,
    app_name: str,
    cfg,
    strategy: str,
    session: str,
    widx: int,
    cache_len: int,
    max_batch: int,
    max_new_cap: int,
    slot_bytes: int,
    fault_plan: dict | None = None,
    adopt_deadline_s: float = 0.0,
    stream: bool = False,
    temperature: float = 0.0,
    top_k: int = 0,
    sampling_seed: int = 0,
) -> None:
    """One serving worker: epoch-path engine + serve_loop over the rings.

    Module-level so the spawn context can pickle it; ``cfg`` is the
    dispatcher's ``ModelConfig``, pickled along. The response ring is
    created FIRST (before the expensive engine build) so the dispatcher's
    attach never races jit compilation; READY (with the spin-up time as
    payload) is pushed only after the engine exists. Any failure is
    pushed as an ERROR frame before re-raising, so the dispatcher learns
    the traceback the moment the process dies instead of at join timeout.

    ``fault_plan`` is a ``faults.FaultPlan`` as a dict (spawn-picklable);
    it arms only if its ``worker`` field matches ``widx`` (or is -1).
    ``adopt_deadline_s > 0`` bounds every blue/green flip: a wedged reload
    deadlines, auto-rolls-back, and the serve loop resumes admission.
    """
    import traceback as _tb

    from repro.core.chips import device_report
    from repro.link import Workspace

    from . import faults
    from .engine import ServeEngine
    from .scheduler import STOP, Request

    faults.install_for_worker(fault_plan, widx)
    ws = Workspace.open(root)
    rsp = ShmRing.create(
        ws.registry, rsp_channel(session, widx),
        slots=RING_SLOTS, slot_bytes=slot_bytes,
    )
    try:
        t0 = time.monotonic()
        engine = ServeEngine.from_workspace(
            cfg, ws, app_name, strategy=strategy, cache_len=cache_len
        )
        req = ShmRing.attach(
            ws.registry, req_channel(session, widx), timeout=60.0
        )
        _push_blocking(
            rsp,
            _encode_blob(
                _RID_READY, json.dumps(device_report()).encode(),
                time.monotonic() - t0,
            ),
            timeout=30.0,
        )

        def source():
            data = req.pop()
            if data is None:
                return None
            rid, toks, max_new, enq, deadline, priority = decode_request(data)
            if rid == _RID_STOP:
                return STOP
            return Request(
                rid=rid, prompt=toks, max_new_tokens=max_new,
                enqueued_ts=enq, deadline_s=deadline, priority=priority,
            )

        def sink(comp):
            _push_blocking(
                rsp,
                encode_completion(
                    comp.rid, comp.tokens, comp.admitted_ts,
                    comp.finished_ts, comp.enqueued_ts,
                    status=getattr(comp, "status", "ok"),
                ),
                timeout=60.0,
            )

        on_delta = None
        if stream:
            frames_out = 0

            def on_delta(d):
                # every decoded token leaves the moment it is sampled: a
                # PARTIAL frame (rid + seq + span) ahead of the final
                # authoritative completion frame on the same SPSC ring
                nonlocal frames_out
                frames_out += 1
                frame = encode_partial(
                    d.rid, d.seq, list(d.tokens), time.monotonic()
                )
                _push_blocking(rsp, frame, timeout=60.0)
                if faults.on_stream_frame(frames_out):
                    _push_blocking(rsp, frame, timeout=60.0)

        # blue/green: notice sibling commits between requests; flip at an
        # empty request boundary and tell the dispatcher what we now serve
        watch = ws.epoch_watch()

        def on_epoch(change):
            import hashlib as _hashlib

            image = engine.adopt_epoch(
                ws, app_name, strategy=strategy,
                deadline_s=adopt_deadline_s,
            )
            h = _hashlib.blake2b(digest_size=16)
            tensors = getattr(image, "tensors", None) or {}
            for tname in sorted(tensors):
                h.update(
                    np.ascontiguousarray(tensors[tname])
                    .view(np.uint8)
                    .tobytes()
                )
            blob = json.dumps(
                {
                    "worker": widx,
                    "epoch_gen": change.epoch_gen,
                    "digest": h.hexdigest(),
                }
            ).encode()
            _push_blocking(rsp, _encode_blob(_RID_ADOPTED, blob), timeout=30.0)

        engine.serve_loop(
            source, sink, max_batch=max_batch, max_new_cap=max_new_cap,
            epoch_watch=watch, on_epoch=on_epoch,
            temperature=temperature, top_k=top_k,
            sampling_seed=sampling_seed, on_delta=on_delta,
        )
        req.close()
        rsp.close()
    except BaseException as e:
        try:
            blob = f"{e!r}\n{_tb.format_exc()}"[-2000:].encode()
            rsp.push(_encode_blob(_RID_ERROR, blob))
            rsp.close()
        except Exception:
            pass
        raise


# ------------------------------------------------------------- dispatcher
@dataclass
class TrafficReport:
    """What one ``run_traffic`` drive actually measured."""

    workers: int
    strategy: str
    arch: str
    rate_hz: float
    sent: int = 0
    completed: int = 0
    tokens_out: int = 0
    stalls: int = 0                     # send attempts deferred (all rings full)
    wall_s: float = 0.0                 # first send -> last completion
    latencies_s: list = field(default_factory=list)
    ready_s: list = field(default_factory=list)   # per-worker spin-up
    devices: list = field(default_factory=list)   # per-worker device report
    outputs: dict = field(default_factory=dict)   # rid -> completed tokens
    worker_errors: list = field(default_factory=list)
    # blue/green rollover (populated when run_traffic rolled mid-load):
    rollover_at: int | None = None      # request index the roll started after
    adoptions: list = field(default_factory=list)  # ADOPTED frames, decoded
    rollover_wall_s: float = 0.0        # commit start -> last worker adopted
    rollover_latencies_s: list = field(default_factory=list)  # during the flip
    steady_latencies_s: list = field(default_factory=list)    # outside it
    # supervision (populated when supervise=True saw a worker die):
    restarts: int = 0                   # workers respawned after death
    rerouted_requests: int = 0          # in-flight requests re-sent elsewhere
    deadline_expired: int = 0           # completions that came back DEADLINE
    kill_latencies_s: list = field(default_factory=list)  # rerouted req e2e
    # streaming (populated when stream=True):
    partial_frames: int = 0             # PARTIAL frames received
    ttft_s: list = field(default_factory=list)   # enqueue -> first PARTIAL
    stream_gaps: int = 0                # seqs missing at completion time
    stream_dup_frames: int = 0          # duplicate spans absorbed (not errors)
    stream_mismatches: int = 0          # reassembly != completion frame row
    stream_tokens: dict = field(default_factory=dict)  # rid -> reassembled

    @property
    def failed(self) -> int:
        return len(self.worker_errors)

    def ttft_quantile(self, q: float) -> float:
        if not self.ttft_s:
            return 0.0
        return float(np.percentile(np.asarray(self.ttft_s), q))

    @property
    def ttft_p50_s(self) -> float:
        """Median enqueue -> first streamed token (0.0 off-stream)."""
        return self.ttft_quantile(50.0)

    @property
    def ttft_p99_s(self) -> float:
        """p99 time-to-first-token: the streaming claim is this landing
        well under the full-completion p99 (a client starts reading at
        the prefill token, not at the last decode step)."""
        return self.ttft_quantile(99.0)

    @property
    def req_per_s(self) -> float:
        return self.completed / self.wall_s if self.wall_s else 0.0

    @property
    def tok_per_s(self) -> float:
        return self.tokens_out / self.wall_s if self.wall_s else 0.0

    def latency_quantile(self, q: float) -> float:
        if not self.latencies_s:
            return 0.0
        return float(np.percentile(np.asarray(self.latencies_s), q))

    @property
    def p50_s(self) -> float:
        return self.latency_quantile(50.0)

    @property
    def p99_s(self) -> float:
        return self.latency_quantile(99.0)

    def _rollover_quantile(self, q: float) -> float:
        if not self.rollover_latencies_s:
            return 0.0
        return float(np.percentile(np.asarray(self.rollover_latencies_s), q))

    def steady_quantile(self, q: float) -> float:
        """Latency quantile excluding the rollover window (equals the
        overall quantile when no roll happened)."""
        lats = self.steady_latencies_s or self.latencies_s
        if not lats:
            return 0.0
        return float(np.percentile(np.asarray(lats), q))

    @property
    def steady_p50_s(self) -> float:
        return self.steady_quantile(50.0)

    @property
    def steady_p99_s(self) -> float:
        return self.steady_quantile(99.0)

    @property
    def rollover_p50_s(self) -> float:
        """p50 of completions received while the generation flip was in
        progress (commit issued -> every worker adopted)."""
        return self._rollover_quantile(50.0)

    @property
    def rollover_p99_s(self) -> float:
        """p99 during the flip — the zero-downtime claim is this staying
        within ~2x the steady-state p99."""
        return self._rollover_quantile(99.0)

    @property
    def kill_p99_s(self) -> float:
        """p99 end-to-end latency of the requests a worker died holding.

        Measured from the ORIGINAL enqueue (the re-routed frame carries
        it), so this is the honest cost a client saw across the kill:
        detect + reroute + the surviving worker's service time. 0.0 when
        nothing was ever re-routed — reported anyway; an absent row and a
        zero row are different claims."""
        if not self.kill_latencies_s:
            return 0.0
        return float(np.percentile(np.asarray(self.kill_latencies_s), 99.0))

    def summary(self) -> dict:
        return {
            "workers": self.workers,
            "strategy": self.strategy,
            "arch": self.arch,
            "rate_hz": self.rate_hz,
            "sent": self.sent,
            "completed": self.completed,
            "tokens_out": self.tokens_out,
            "stalls": self.stalls,
            "failed_workers": self.failed,
            "worker_errors": self.worker_errors,
            "wall_s": round(self.wall_s, 4),
            "req_per_s": round(self.req_per_s, 2),
            "tok_per_s": round(self.tok_per_s, 1),
            "p50_latency_s": round(self.p50_s, 4),
            "p99_latency_s": round(self.p99_s, 4),
            "ready_s": [round(r, 3) for r in self.ready_s],
            "devices": self.devices,
            "rollover_at": self.rollover_at,
            "adoptions": self.adoptions,
            "rollover_wall_s": round(self.rollover_wall_s, 4),
            "rollover_completions": len(self.rollover_latencies_s),
            "rollover_p50_latency_s": round(self.rollover_p50_s, 4),
            "rollover_p99_latency_s": round(self.rollover_p99_s, 4),
            # supervision counters are honest zeros when nothing died
            "restarts": self.restarts,
            "rerouted_requests": self.rerouted_requests,
            "deadline_expired": self.deadline_expired,
            "kill_completions": len(self.kill_latencies_s),
            "kill_p99_latency_s": round(self.kill_p99_s, 4),
            # streaming counters are honest zeros when stream=False
            "partial_frames": self.partial_frames,
            "ttft_p50_s": round(self.ttft_p50_s, 4),
            "ttft_p99_s": round(self.ttft_p99_s, 4),
            "stream_gaps": self.stream_gaps,
            "stream_dup_frames": self.stream_dup_frames,
            "stream_mismatches": self.stream_mismatches,
        }


def run_traffic(
    ws,
    app_name: str,
    *,
    cfg,
    workers: int = 2,
    n_requests: int = 16,
    rate_hz: float = 50.0,
    prompt_len: int = 12,
    max_new_tokens: int = 8,
    max_batch: int = 2,
    strategy: str = "stable-shm",
    cache_len: int = 0,
    seed: int = 0,
    timeout: float = 180.0,
    warmup_per_worker: int = 1,
    session: str | None = None,
    rollover_at: int | None = None,
    rollover_fn=None,
    request_deadline_s: float = 0.0,
    adopt_deadline_s: float = 0.0,
    supervise: bool = False,
    faults: dict | None = None,
    stream: bool = False,
    temperature: float = 0.0,
    top_k: int = 0,
    sampling_seed: int = 0,
    priorities=None,
    mpmc: bool = False,
) -> TrafficReport:
    """Drive a Poisson request load through a spawned serving fleet.

    Spawns ``workers`` real processes (spawn context — jax state never
    forks), worker i pinned to chip i, each building a ``ServeEngine`` for
    ``cfg`` and serving ``engine.serve_loop`` over its ring pair, and
    sends ``n_requests`` with exponential inter-arrival times at
    ``rate_hz``. Requests round-robin across workers; a full request ring
    routes to the next worker, and a fully-backpressured fleet defers the
    send (counted in ``stalls``). Returns a ``TrafficReport`` with
    sustained req/s, tok/s, and p50/p99 end-to-end latency; worker
    crashes surface as structured ``worker_errors`` records (exit code +
    traceback excerpt) rather than a join timeout. Each worker's READY
    frame lands in ``report.devices`` (the device it serves on), and each
    completed request's tokens in ``report.outputs``.

    The dispatcher itself never touches a JAX backend: a parent that held
    the chip would leave its workers none.

    ``warmup_per_worker`` requests are pushed to every worker and drained
    BEFORE the measured phase, so each worker's jit compilation (prefill +
    admit + batched step) happens off the clock — p50/p99 measure steady
    state, not the first-request compile.

    All ring segments are unlinked before returning — and if this process
    is SIGKILLed first, their records name a dead owner pid, so the next
    ``ws.gc()`` reclaims them.

    ``rollover_at``/``rollover_fn``: after request index ``rollover_at``
    is sent, ``rollover_fn()`` runs on the dispatcher — a management
    commit landing generation N+1 while the fleet serves N. Workers flip
    at request boundaries (see module docstring); completions received
    between the commit and the last worker's ADOPTED frame land in
    ``report.rollover_latencies_s`` (p99-during-rollover), and each
    adoption's tensors digest lands in ``report.adoptions`` for
    content-hash verification against the new generation.

    Hardening knobs (the chaos tier drives all four together):

    * ``request_deadline_s`` — every measured request carries this budget;
      a worker retires expired requests with a DEADLINE completion
      (``report.deadline_expired``) instead of dropping them.
    * ``adopt_deadline_s`` — bounds each worker's blue/green flip; a
      wedged reload auto-rolls-back (``engine.adopt_epoch(deadline_s=)``).
    * ``supervise`` — the dispatcher respawns dead workers (detected via
      the rsp-ring owner record) with capped exponential backoff and
      re-routes their in-flight requests to survivors; completions are
      deduped by rid, so a SIGKILL costs bounded p99
      (``report.kill_p99_s``) and zero lost requests.
    * ``faults`` — a ``serve.faults.FaultPlan`` as a dict, shipped to the
      targeted worker's process (respawned workers get none).

    Serving-surface knobs (the PR 10 streaming tier):

    * ``stream`` — workers push every decoded token as a PARTIAL frame;
      the dispatcher reassembles per-rid spans by seq, measures TTFT, and
      verifies the reassembly byte-for-byte against each completion frame
      (``stream_gaps``/``stream_dup_frames``/``stream_mismatches``).
    * ``temperature``/``top_k``/``sampling_seed`` — temperature (top-k)
      sampling in the workers' batched decode step; keys derive from the
      rid, so re-routes and stream-vs-batch modes stay byte-identical.
    * ``priorities`` — optional per-request admission classes (array of
      ints, indexed by request); higher classes admit first, aged so
      lower classes are starvation-bounded.
    * ``mpmc`` — create request rings in multi-producer mode (the
      claim-counter protocol that lets several dispatchers share one req
      ring) instead of SPSC.
    """
    cache_len = cache_len or (prompt_len + max_new_tokens + 4)
    session = session or f"traffic-{uuid.uuid4().hex[:8]}"
    slot_bytes = ring_slot_bytes(prompt_len, max_new_tokens)
    report = TrafficReport(
        workers=workers, strategy=strategy, arch=cfg.name, rate_hz=rate_hz
    )

    ctx = mp.get_context("spawn")
    req_rings = [
        ShmRing.create(
            ws.registry, req_channel(session, i),
            slots=RING_SLOTS, slot_bytes=slot_bytes,
            # mpmc: this dispatcher takes seat 0; additional dispatchers
            # would attach with their own producer seats
            producers=1 if mpmc else 0,
            producer_id=0 if mpmc else None,
        )
        for i in range(workers)
    ]
    def _worker_args(i: int, plan: dict | None):
        return (
            ws.root, app_name, cfg, strategy, session, i,
            cache_len, max_batch, max_new_tokens, slot_bytes,
            plan, adopt_deadline_s,
            stream, temperature, top_k, sampling_seed,
        )

    procs = [
        ctx.Process(
            target=_traffic_worker,
            args=_worker_args(i, faults),
            daemon=True,
        )
        for i in range(workers)
    ]
    for i, p in enumerate(procs):
        with pinned_to_chip(i):
            p.start()
    rsp_rings = [
        ShmRing.attach(ws.registry, rsp_channel(session, i), timeout=60.0)
        for i in range(workers)
    ]

    rng = np.random.default_rng(seed)
    prompts = rng.integers(
        0, cfg.vocab_size, (n_requests, prompt_len), dtype=np.int32
    )
    gaps = rng.exponential(1.0 / max(rate_hz, 1e-9), n_requests)
    alive = [True] * workers
    deadline = time.monotonic() + timeout
    first_send = last_recv = 0.0
    # supervision bookkeeping: every sent frame is retained by rid so a
    # dead worker's in-flight requests can be re-routed verbatim (original
    # enqueue time included), and completions are deduped by rid because a
    # frame can come back twice (ring replay by the respawn + re-route).
    sent_frames: dict[int, bytes] = {}
    owner: dict[int, int] = {}           # rid -> worker currently holding it
    done_rids: set[int] = set()
    rerouted_rids: set[int] = set()
    restarts_per = [0] * workers
    # streaming reassembly: per-rid spans keyed by seq (idempotent under
    # duplicate delivery), plus the dispatcher-side send stamp for TTFT
    send_ts: dict[int, float] = {}
    spans: dict[int, dict[int, np.ndarray]] = {}
    ttft_seen: set[int] = set()

    def _reap(i: int, blob: bytes | None) -> None:
        """Record worker i's death as a structured error, once."""
        if not alive[i]:
            return
        alive[i] = False
        report.worker_errors.append(
            {
                "worker": i,
                "pid": procs[i].pid,
                "exit_code": procs[i].exitcode,
                "error": (blob or b"").decode(errors="replace")[-2000:],
            }
        )

    warmed = 0
    roll_active = False      # commit issued, not every worker adopted yet
    roll_t0 = 0.0

    def _respawn(i: int) -> None:
        """Supervisor: worker ``i`` died. Confirm through the rsp-ring
        owner record (the dead pid sits in shm — no waitpid race), bring a
        replacement up with capped exponential backoff, and re-route every
        request the corpse was holding to surviving workers. The request
        ring is dispatcher-owned and its pop cursor lives in the shared
        header, so frames the corpse never popped are consumed by the
        replacement as-is; only popped-but-unanswered frames need the
        re-route, and rid dedup absorbs any overlap between the two."""
        if ring_owner_alive(ws.registry, rsp_channel(session, i)) is True:
            return               # record says the owner is alive: not dead
        alive[i] = False
        report.restarts += 1
        restarts_per[i] += 1
        victims = sorted(
            rid for rid, w in owner.items() if w == i and rid not in done_rids
        )
        try:                     # replacement re-creates the rsp ring
            rsp_rings[i].close()
            rsp_rings[i].unlink(ws.registry)
        except Exception:
            pass
        time.sleep(min(0.05 * (2 ** (restarts_per[i] - 1)), 1.0))
        p = ctx.Process(
            target=_traffic_worker, args=_worker_args(i, None), daemon=True
        )
        with pinned_to_chip(i):
            p.start()
        procs[i] = p
        rsp_rings[i] = ShmRing.attach(
            ws.registry, rsp_channel(session, i), timeout=60.0
        )
        alive[i] = True
        targets = [j for j in range(workers) if alive[j] and j != i] or [i]
        for n, rid in enumerate(victims):
            t = targets[n % len(targets)]
            _push_blocking(req_rings[t], sent_frames[rid], timeout=30.0)
            owner[rid] = t
            rerouted_rids.add(rid)
            report.rerouted_requests += 1
            # the survivor replays the request's WHOLE stream from seq 0
            # (rid-derived sampling keys make it byte-identical); drop the
            # corpse's partial spans so reassembly sees one clean pass
            spans.pop(rid, None)

    def _verify_stream(rid: int, final_row: np.ndarray) -> None:
        """At completion, check the reassembled stream against the
        completion frame's authoritative row: every seq present exactly
        once (gaps/dups counted separately) and byte-identical tokens."""
        sp = spans.pop(rid, {})
        flat: dict[int, int] = {}
        for s, arr in sp.items():
            for off, tok in enumerate(np.asarray(arr).tolist()):
                flat.setdefault(s + off, tok)
        want = int(final_row.size)
        missing = [i for i in range(want) if i not in flat]
        if missing:
            report.stream_gaps += len(missing)
            return
        rec = np.asarray([flat[i] for i in range(want)], np.int32)
        report.stream_tokens[rid] = rec
        if not np.array_equal(rec, np.asarray(final_row, np.int32)):
            report.stream_mismatches += 1

    def _drain() -> None:
        nonlocal last_recv, warmed, roll_active
        for i, ring in enumerate(rsp_rings):
            while True:
                data = ring.pop()
                if data is None:
                    break
                rid, payload, a, f, enq, status = decode_completion(data)
                if rid == _RID_READY:
                    report.ready_s.append(a)
                    report.devices.append(json.loads(payload.decode()))
                elif rid == _RID_ADOPTED:
                    report.adoptions.append(
                        json.loads(payload.decode(errors="replace"))
                    )
                    if roll_active and len(report.adoptions) >= sum(alive):
                        # every surviving worker now serves generation N+1
                        report.rollover_wall_s = time.monotonic() - roll_t0
                        roll_active = False
                elif rid == _RID_ERROR:
                    _reap(i, payload)
                elif status == "partial":
                    # streamed span: reassemble by seq. Late frames for a
                    # completed rid and duplicate seqs (re-route replay,
                    # dup-delivery faults) are absorbed idempotently.
                    if rid >= _RID_WARM or rid in done_rids:
                        continue
                    report.partial_frames += 1
                    seq = int(a)
                    sp = spans.setdefault(rid, {})
                    if seq in sp:
                        report.stream_dup_frames += 1
                    else:
                        sp[seq] = payload
                    if rid not in ttft_seen:
                        ttft_seen.add(rid)
                        st = send_ts.get(rid)
                        if st is not None:
                            report.ttft_s.append(time.monotonic() - st)
                elif rid >= _RID_WARM:
                    if rid not in done_rids:
                        done_rids.add(rid)
                        warmed += 1
                else:
                    if rid in done_rids:
                        continue     # duplicate: replayed AND re-routed
                    done_rids.add(rid)
                    owner.pop(rid, None)
                    now = time.monotonic()
                    last_recv = max(last_recv, now)
                    report.completed += 1
                    if status == "deadline":
                        # structured DEADLINE frame: answered, not served
                        report.deadline_expired += 1
                        spans.pop(rid, None)  # partial stream: unverifiable
                    else:
                        report.tokens_out += int(payload.size)
                        report.outputs[rid] = payload
                        if enq is not None:
                            report.latencies_s.append(now - enq)
                            if roll_active:
                                report.rollover_latencies_s.append(now - enq)
                            else:
                                report.steady_latencies_s.append(now - enq)
                        if stream:
                            _verify_stream(rid, payload)
                    if rid in rerouted_rids and enq is not None:
                        report.kill_latencies_s.append(now - enq)
            if alive[i] and not procs[i].is_alive() and procs[i].exitcode:
                if supervise:
                    _respawn(i)
                else:
                    _reap(i, None)

    try:
        # ---- warmup phase: compile every worker off the measured clock
        warm_expect = 0
        for w in range(workers):
            for j in range(warmup_per_worker):
                wrid = _RID_WARM + w * warmup_per_worker + j
                frame = encode_request(
                    wrid, prompts[(w + j) % n_requests], max_new_tokens, None,
                )
                _push_blocking(req_rings[w], frame, timeout=30.0)
                sent_frames[wrid] = frame
                owner[wrid] = w
                warm_expect += 1
        while warmed < warm_expect:
            _drain()
            if not any(alive):
                raise ShmRingError(
                    f"every worker died during warmup: {report.worker_errors}"
                )
            if time.monotonic() >= deadline:
                raise ShmRingError("fleet never finished warmup")
            time.sleep(0.002)

        # ---- send phase: Poisson arrivals, round-robin with backpressure
        nxt = 0
        for k in range(n_requests):
            if rollover_fn is not None and rollover_at is not None and k == rollover_at:
                # roll the world under live load: the commit lands here,
                # on the dispatcher, while workers keep serving gen N
                report.rollover_at = rollover_at
                roll_t0 = time.monotonic()
                roll_active = True
                rollover_fn()
            time.sleep(gaps[k])
            while True:
                _drain()
                targets = [
                    (nxt + d) % workers for d in range(workers)
                    if alive[(nxt + d) % workers]
                ]
                if not targets:
                    raise ShmRingError(
                        f"every worker died before request {k}: "
                        f"{report.worker_errors}"
                    )
                sent = False
                for t in targets:
                    stamp = time.monotonic()
                    frame = encode_request(
                        k, prompts[k], max_new_tokens, stamp,
                        request_deadline_s,
                        0 if priorities is None else int(priorities[k]),
                    )
                    if req_rings[t].push(frame):
                        sent_frames[k] = frame
                        send_ts[k] = stamp
                        owner[k] = t
                        nxt = (t + 1) % workers
                        sent = True
                        break
                if sent:
                    break
                report.stalls += 1
                if time.monotonic() >= deadline:
                    raise ShmRingError("fleet stayed backpressured past timeout")
                time.sleep(0.001)
            report.sent += 1
            if first_send == 0.0:
                first_send = time.monotonic()

        # ---- drain phase: STOP each worker, collect the tail
        stop_frame = _REQ_HDR.pack(_RID_STOP, 0, 0, 0.0, 0.0, 0)
        for i, ring in enumerate(req_rings):
            if not alive[i]:
                continue
            while not ring.push(stop_frame):   # backlogged worker: drain first
                _drain()
                if not alive[i] or time.monotonic() >= deadline:
                    break
                time.sleep(0.001)
        expect = report.sent
        while report.completed < expect and time.monotonic() < deadline:
            _drain()
            if report.completed >= expect:
                break
            if all(not p.is_alive() for p in procs):
                _drain()   # final sweep: workers are gone, rings may not be
                break
            time.sleep(0.001)
        for i, p in enumerate(procs):
            p.join(timeout=max(0.0, deadline - time.monotonic()))
            if p.is_alive():
                p.terminate()
                p.join(timeout=5.0)
            elif p.exitcode:
                _reap(i, None)
    finally:
        for ring in req_rings:
            ring.close()
            ring.unlink(ws.registry)
        for ring in rsp_rings:
            ring.close()
            ring.unlink(ws.registry)

    report.wall_s = max(last_recv - first_send, 1e-9) if first_send else 0.0
    return report
