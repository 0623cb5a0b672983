"""Serving-tier load benchmark: p50/p99 under Poisson traffic + rollover.

    PYTHONPATH=src python -m benchmarks.serve_load [--smoke] [--rollover | --chaos]

PRs 3-5 measured how fast an epoch *loads*; this harness measures what the
loaded fleet *does*: a dispatcher drives Poisson arrivals through shm
request/response rings (``repro.serve.traffic``) into ``workers`` real
processes, each running the continuous-batching ``engine.serve_loop`` over
a ``stable-shm`` arena (one physical weight copy machine-wide). Emits:

    serve/p50_latency, serve/p99_latency   us rows (end-to-end, steady
                                           state — workers are warmed off
                                           the clock first, and the
                                           rollover window is excluded)
    serve/req_per_s, serve/tok_per_s       derived rows (higher = better;
                                           perf_gate classifies them out
                                           of the microsecond sweep)

``--rollover`` is PR 7's blue/green measurement: a third of the way into
the arrival schedule the dispatcher commits a new weights generation via
``ws.management()`` while the fleet keeps serving. Every worker's
``ws.epoch_watch()`` notices the committed ``epoch_gen``, the serve loop
flips at a request boundary (``engine.adopt_epoch``), and each worker
reports an ADOPTED frame carrying a digest of the weights it now serves.
The harness asserts zero failed/dropped requests, byte-identity of every
adoption against an independent post-commit load, and that the old
generation's shm segments are reclaimed by ``ws.gc(drain=True)`` — then
emits:

    serve/rollover_p99_latency   us row: p99 of requests completed inside
                                 the rollover window (commit -> last
                                 worker adopted); the perf gate asserts
                                 it stays within 2x steady-state p99
    serve/rollover_stall         us row: wall time from commit to the
                                 whole fleet serving the new generation

``--chaos`` is PR 8's hardening measurement, two halves:

* **kill-a-worker tail** — a supervised fleet (``supervise=True``) serves
  the full schedule while a fault plan SIGKILLs worker 0 mid-decode
  (``die_at_step``). The dispatcher detects the death through the dead
  rsp-ring owner record, re-routes the in-flight frames verbatim
  (original enqueue timestamps, so the latency is honest), and respawns
  the worker with backoff. Emits ``serve/kill_p99_latency`` (p99 of the
  re-routed requests, measured from their ORIGINAL enqueue) plus
  ``serve/fleet_restarts`` and ``serve/fleet_rerouted`` counts.
* **rollback wall** — in-process: commit a v2 generation, wedge the
  reload via the fault hook, adopt with a deadline; the deadline fires,
  ``abort_adopt`` rolls the store forward to a generation that re-adopts
  the v1 world, and the engine is byte-identical to v1 again. Emits
  ``serve/rollback_wall``: wall time from the deadline firing to
  serving the rolled-back weights (the adopt call's total wall minus
  the deadline itself).

PR 10 turns the measured load into the full serving product: requests
ride MPMC request rings (``mpmc=True`` — the multi-dispatcher wire), the
fleet *streams* every token back as a PARTIAL frame, and decode runs
temperature/top-k sampling with per-request PRNG keys (tokens are a pure
function of (seed, rid, position), so streams reassemble byte-identical
to their completion rows — asserted here on every run). Emits:

    serve/ttft_p50, serve/ttft_p99   us rows: enqueue -> first streamed
                                     token. The streaming claim is
                                     ttft_p99 landing well under the
                                     full-completion p99; the perf gate
                                     asserts nonzero, finite, and
                                     bounded by the completion p99.

Rows are MERGED into ``BENCH_10.json`` (``run.py --smoke`` writes the load
rows first in CI; this harness adds the serving rows), and
``perf_gate.py`` gates the rollover, chaos, and TTFT rows against the
steady-state ones.
"""

from __future__ import annotations

import hashlib
import sys
import time

import numpy as np

BENCH_JSON = "BENCH_10.json"

ARCH = "mamba2-370m"          # constant-state decode: the serving workhorse


def _image_digest(image) -> str:
    """Same digest the traffic workers report in their ADOPTED frames:
    blake2b-16 over every tensor's contiguous bytes, in sorted name order."""
    h = hashlib.blake2b(digest_size=16)
    tensors = getattr(image, "tensors", None) or {}
    for name in sorted(tensors):
        h.update(np.ascontiguousarray(tensors[name]).view(np.uint8).tobytes())
    return h.hexdigest()


def run(
    cfg,
    *,
    workers: int = 2,
    n_requests: int = 32,
    rate_hz: float = 200.0,
    prompt_len: int = 12,
    max_new_tokens: int = 8,
    max_batch: int = 2,
    rollover: bool = False,
) -> None:
    from repro import models
    from repro.core import shm_arena
    from repro.launch.serve import publish_model
    from repro.serve import run_traffic

    from .common import emit, emit_value, fresh_workspace

    print("name,us_per_call,derived")
    ws = fresh_workspace()
    try:
        # numpy weights: this process spawns the serving workers, so it
        # must not take the chip
        app_name = publish_model(ws, cfg, models.init_params_np(cfg, 0))

        rollover_at = n_requests // 3 if rollover else None
        pre_roll_segments: list[str] = []

        def rollover_fn() -> None:
            # Snapshot the generation-N arena segments the fleet is serving
            # from RIGHT before the commit: after the drain gc these exact
            # names must be gone (rings are session conduits, not epoch
            # state — they are reclaimed by owner-death, not by drain).
            pre_roll_segments.extend(
                rec["name"]
                for rec in shm_arena.list_segments(ws.registry)
                if rec.get("kind") != "ring"
            )
            publish_model(ws, cfg, models.init_params_np(cfg, 1), version="v2")

        rep = run_traffic(
            ws,
            app_name,
            cfg=cfg,
            workers=workers,
            n_requests=n_requests,
            rate_hz=rate_hz,
            prompt_len=prompt_len,
            max_new_tokens=max_new_tokens,
            max_batch=max_batch,
            rollover_at=rollover_at,
            rollover_fn=rollover_fn if rollover else None,
            # PR 10: the measured load IS the streaming product — sampled
            # decode, per-token PARTIAL frames, MPMC request rings
            stream=True,
            temperature=0.7,
            top_k=40,
            sampling_seed=42,
            mpmc=True,
        )
        s = rep.summary()
        assert rep.completed == n_requests, f"lost requests: {s}"
        assert rep.failed == 0, f"worker crashes: {s}"
        assert rep.p99_s > 0 and np.isfinite(rep.p99_s), s
        # streaming contract, asserted on the measured run itself: every
        # request's spans reassembled complete and byte-identical
        assert rep.stream_gaps == 0, f"stream gaps: {s}"
        assert rep.stream_mismatches == 0, f"stream mismatches: {s}"
        assert len(rep.stream_tokens) == n_requests, s
        assert len(rep.ttft_s) == n_requests, s
        # per-request TTFT <= that request's full latency, so the p99s
        # are ordered too (pointwise domination orders order statistics)
        assert 0 < rep.ttft_p99_s <= rep.p99_s, s
        tag = (
            f"workers={workers};rate_hz={rate_hz};completed={rep.completed};"
            f"stalls={rep.stalls}"
        )
        # steady-state quantiles: identical to the overall quantiles when no
        # roll happened, rollover-window completions excluded when one did —
        # so this row stays comparable across trajectories either way
        emit("serve/p50_latency", rep.steady_p50_s, tag)
        emit("serve/p99_latency", rep.steady_p99_s, tag)
        emit("serve/ttft_p50", rep.ttft_p50_s,
             f"enqueue->first streamed token;{tag}")
        emit("serve/ttft_p99", rep.ttft_p99_s,
             f"enqueue->first streamed token;frames={rep.partial_frames}")
        emit_value("serve/req_per_s", rep.req_per_s, tag)
        emit_value("serve/tok_per_s", rep.tok_per_s, tag)
        emit_value("serve/fleet_ready_s", max(rep.ready_s or [0.0]),
                   "slowest worker spin-up (epoch load + first attach)")
        # supervision counters: honest rows even when zero — no fault was
        # injected in this mode, so a nonzero value here means a worker
        # really died (the --chaos pass overwrites these with its kill run)
        emit_value("serve/fleet_restarts", rep.restarts,
                   "supervisor respawns (0 expected: no fault injected)")
        emit_value("serve/fleet_rerouted", rep.rerouted_requests,
                   "in-flight re-routes (0 expected: no fault injected)")

        if rollover:
            _check_rollover(ws, app_name, rep, workers=workers,
                            pre_roll_segments=pre_roll_segments)
    finally:
        from .common import write_bench_json

        ws.close()
        print(f"wrote {write_bench_json(BENCH_JSON, merge=True)}")


def _check_rollover(ws, app_name, rep, *, workers, pre_roll_segments) -> None:
    """Assert the blue/green contract held under load, then emit the rows."""
    from .common import emit

    s = rep.summary()
    assert rep.rollover_at is not None, s
    assert len(rep.adoptions) == workers, (
        f"only {len(rep.adoptions)}/{workers} workers adopted the new "
        f"generation: {s}"
    )
    # every worker must be serving THIS committed generation...
    gens = {a["epoch_gen"] for a in rep.adoptions}
    assert gens == {ws.epoch_gen}, (
        f"adopted generations {gens} != committed {ws.epoch_gen}"
    )
    # ...and its weights must be byte-identical to an independent fresh
    # load of generation N+1 through a different strategy
    expect = _image_digest(ws.load(app_name, strategy="stable-mmap-cached"))
    digests = {a["digest"] for a in rep.adoptions}
    assert digests == {expect}, (
        f"worker weight digests {digests} != fresh-load digest {expect}"
    )
    assert rep.rollover_wall_s > 0, s
    assert rep.rollover_p99_s > 0 and np.isfinite(rep.rollover_p99_s), s

    # drain the two-generation window: generation N's arena segments (the
    # exact names snapshotted pre-commit) must be reclaimed, and the new
    # generation must still load afterwards
    assert pre_roll_segments, "rollover_fn never ran (no pre-roll snapshot)"
    g = ws.gc(drain=True)
    missed = [n for n in pre_roll_segments if n not in g.removed]
    assert not missed, f"old-generation segments survived drain gc: {missed}"
    ws.load(app_name, strategy="stable-mmap-cached")

    window_tag = (
        f"window_completions={len(rep.rollover_latencies_s)};"
        f"p50_s={rep.rollover_p50_s:.4f};adoptions={len(rep.adoptions)}"
    )
    emit("serve/rollover_p99_latency", rep.rollover_p99_s, window_tag)
    emit("serve/rollover_stall", rep.rollover_wall_s,
         f"commit->fleet-adopted wall;old_segments_gcd={len(pre_roll_segments)}")


def run_chaos(cfg, *, smoke: bool = True) -> None:
    """``--chaos``: kill-a-worker tail + wedge->deadline->rollback wall."""
    from repro import models
    from repro.launch.serve import publish_model
    from repro.serve import run_traffic

    from .common import emit, emit_value, fresh_workspace, write_bench_json

    workers = 2 if smoke else 3
    n_requests = 16 if smoke else 48
    print("name,us_per_call,derived")
    ws = fresh_workspace()
    try:
        app_name = publish_model(ws, cfg, models.init_params_np(cfg, 0))

        # Half 1: SIGKILL worker 0 mid-decode under a supervised fleet.
        # The supervisor must finish the whole schedule anyway: dead-owner
        # detection -> verbatim re-route of the in-flight frames -> respawn.
        # die_at_step counts CUMULATIVE serve-loop decode steps, warmup
        # included: the one warmup request costs max_new steps, so step
        # max_new+2 kills worker 0 two steps into its first MEASURED batch
        max_new = 8
        rep = run_traffic(
            ws,
            app_name,
            cfg=cfg,
            workers=workers,
            n_requests=n_requests,
            rate_hz=200.0,
            prompt_len=12,
            max_new_tokens=max_new,
            max_batch=2,
            supervise=True,
            faults={"die_at_step": max_new + 2, "worker": 0},
        )
        s = rep.summary()
        assert rep.completed == n_requests, f"lost requests under kill: {s}"
        assert rep.failed == 0, f"unrecovered worker failures: {s}"
        assert rep.restarts >= 1, f"fault plan never killed a worker: {s}"
        assert rep.rerouted_requests >= 1, f"nothing was in flight: {s}"
        kill_p99 = rep.kill_p99_s
        assert kill_p99 > 0 and np.isfinite(kill_p99), s
        emit(
            "serve/kill_p99_latency",
            kill_p99,
            f"workers={workers};restarts={rep.restarts};"
            f"rerouted={rep.rerouted_requests};from ORIGINAL enqueue",
        )
        emit_value("serve/fleet_restarts", rep.restarts,
                   "supervisor respawns (capped-backoff)")
        emit_value("serve/fleet_rerouted", rep.rerouted_requests,
                   "in-flight frames replayed to surviving workers")

        # Half 2: wedged reload -> deadline -> auto-rollback, in-process.
        _bench_rollback_wall(cfg, ws, app_name)
    finally:
        ws.close()
        print(f"wrote {write_bench_json(BENCH_JSON, merge=True)}")


def _bench_rollback_wall(cfg, ws, app_name) -> None:
    """Commit v2, wedge the reload, adopt with a deadline; time the
    recovery (deadline fires -> abort_adopt -> serving v1 bytes again)."""
    from repro import models
    from repro.core.errors import AdoptDeadlineError
    from repro.launch.serve import publish_model
    from repro.serve import FaultPlan, ServeEngine
    from repro.serve import faults as serve_faults

    from .common import emit

    engine = ServeEngine.from_workspace(cfg, ws, app_name, cache_len=16)
    good = _image_digest(ws.load(app_name, strategy="stable-mmap-cached"))
    gen_before = ws.epoch_gen

    publish_model(ws, cfg, models.init_params_np(cfg, 7), version="v2-bad")

    deadline_s = 0.25
    serve_faults.install(FaultPlan(wedge_adopt_s=30.0))
    try:
        t0 = time.perf_counter()
        try:
            engine.adopt_epoch(ws, app_name, deadline_s=deadline_s)
        except AdoptDeadlineError as err:
            wall = time.perf_counter() - t0
            rolled_back_to = err.rolled_back_to
        else:
            raise AssertionError("wedged adopt_epoch did not deadline")
    finally:
        serve_faults.clear()

    # rollback is a FORWARD generation: v2 commit bumped the gen, the
    # abort bumped it again re-adopting the v1 world
    assert rolled_back_to == gen_before + 2, (rolled_back_to, gen_before)
    assert ws.epoch_gen == rolled_back_to
    after = _image_digest(ws.load(app_name, strategy="stable-mmap-cached"))
    assert after == good, "rollback did not restore the v1 bytes"
    rollback_wall = wall - deadline_s
    assert rollback_wall > 0, (wall, deadline_s)
    emit(
        "serve/rollback_wall",
        rollback_wall,
        f"deadline_s={deadline_s};wedge_s=30;rolled_back_to="
        f"{rolled_back_to};bytes==v1",
    )


def main() -> None:
    from repro.configs import get_config

    smoke = "--smoke" in sys.argv
    cfg = get_config(ARCH, smoke=smoke)
    if "--chaos" in sys.argv:
        run_chaos(cfg, smoke=smoke)
        return
    rollover = "--rollover" in sys.argv
    if smoke:
        run(cfg, workers=2, n_requests=24, rate_hz=200.0, rollover=rollover)
        return
    run(cfg, workers=3, n_requests=96, rate_hz=400.0, max_batch=4,
        rollover=rollover)


if __name__ == "__main__":
    main()
