"""The chaos tier: rollover & fleet hardening under injected faults.

PR 8 acceptance matrix, hardest claims first:

* **Back-to-back rollovers converge** — two commits landing mid-drain
  coalesce: every worker ends on the NEWEST generation (byte-verified
  digest), zero requests dropped, and the retained chain held BOTH
  outgoing generations until the drain closed.
* **Wedged flip deadlines and auto-rolls-back** — a fault-wedged
  ``adopt_epoch(deadline_s=...)`` raises ``AdoptDeadlineError``, the
  store rolls back to a NEW generation whose weights are byte-identical
  to pre-flip, ``state.json`` carries ``rolled_back_from``, and a serve
  loop counts the abort and resumes admission.
* **SIGKILLed worker under Poisson load** — the supervisor detects the
  corpse via its rsp-ring owner record, respawns it with backoff,
  re-routes its in-flight requests, and every request completes: bounded
  kill-p99, zero lost.
* **Deadlines everywhere** — expired requests (queued or in-flight, local
  or over the shm wire) come back as structured DEADLINE completions,
  never silent drops.
* Satellites: the generation-chain manager semantics, ``gc(dry_run=True)``
  preflight, and the EpochWatch coarse-mtime fallback regression.

Fleet bodies are module-level (spawn pickles by qualified name); every
wait carries its own deadline. The shm-backed tests skip without POSIX
shared memory, mirroring test_traffic.
"""

from __future__ import annotations

import hashlib
import json
import time
from collections import deque

import numpy as np
import pytest

from repro.core import EpochCache, Mode, ModeError
from repro.core.errors import AdoptDeadlineError, RollbackError
from repro.link import Workspace

from conftest import build_app, build_bundle

JOIN_S = 90.0


@pytest.fixture()
def shm_ws(tmp_path):
    """Workspace whose shm leftovers are force-unlinked on teardown."""
    pytest.importorskip("_posixshmem")
    from repro.core import shm_arena

    ws = Workspace.open(tmp_path / "store", epoch_cache=EpochCache())
    try:
        yield ws
    finally:
        shm_arena.unlink_root_segments(ws.registry)


@pytest.fixture(autouse=True)
def _clear_faults():
    from repro.serve import faults

    faults.clear()
    yield
    faults.clear()


def _commit_tensors(ws, val: float, version: str):
    """Commit one generation: bundle ``w`` at ``val`` (app stays)."""
    bundle = build_bundle(
        "w", {"s/a": np.full(8, val, np.float32)}, version=version
    )
    with ws.management() as tx:
        tx.publish(*bundle)
    return bundle[0].content_hash


def _seed_store(ws):
    from repro.core import SymbolRef

    bundle = build_bundle("w", {"s/a": np.full(8, 1.0, np.float32)})
    app = build_app("app", [SymbolRef("s/a", (8,), "float32")], ["w"])
    with ws.management() as tx:
        tx.publish(*bundle)
        tx.publish(app)
    return bundle[0].content_hash


def _publish_model(ws, arch: str):
    """Publish the weights bundle + app for ``arch`` (smoke config)."""
    from repro import models
    from repro.ckpt import bundle_from_params
    from repro.configs import get_config
    from repro.core import ObjectKind, make_object

    cfg = get_config(arch, smoke=True)
    params = {
        n: np.asarray(v) for n, v in models.init_params(cfg, 0).items()
    }
    bundle, payload = bundle_from_params(f"weights:{cfg.name}", "v1", params)
    app, _ = make_object(
        name=f"serve:{cfg.name}",
        version="1",
        kind=ObjectKind.APPLICATION,
        refs=models.manifest_refs(cfg),
        needed=[bundle.name],
    )
    with ws.management() as tx:
        tx.publish(bundle, payload)
        tx.publish(app)
    return cfg, app.name


def _commit_model_version(ws, cfg, seed: int, version: str):
    from repro import models
    from repro.ckpt import bundle_from_params

    params = {
        n: np.asarray(v) for n, v in models.init_params(cfg, seed).items()
    }
    bundle, payload = bundle_from_params(
        f"weights:{cfg.name}", version, params
    )
    with ws.management() as tx:
        tx.publish(bundle, payload)


def _digest_params(params) -> str:
    h = hashlib.blake2b(digest_size=16)
    for name in sorted(params):
        h.update(
            np.ascontiguousarray(np.asarray(params[name]))
            .view(np.uint8)
            .tobytes()
        )
    return h.hexdigest()


def _digest_image(ws, app_name: str) -> str:
    img = ws.load(app_name, strategy="stable-mmap-cached")
    return _digest_params(img.tensors)


# =================================================== generation chain (unit)
def test_generation_chain_retains_and_trims(tmp_path):
    ws = Workspace.open(tmp_path / "store")
    _seed_store(ws)
    g1 = ws.epoch_gen
    _commit_tensors(ws, 2.0, "2")
    g2 = ws.epoch_gen
    mgr = ws.manager
    assert mgr.retained_generations() == [g1]
    _commit_tensors(ws, 3.0, "3")
    g3 = ws.epoch_gen
    # both still-draining generations are retained (back-to-back window)
    assert mgr.retained_generations() == [g1, g2]
    assert mgr.last_retired == []
    # a fourth commit trims the oldest past the cap — gracefully, recorded
    _commit_tensors(ws, 4.0, "4")
    assert mgr.retained_generations() == [g2, g3]
    assert mgr.last_retired == [g1]
    # schema keeps the chain head mirrored for v3 readers
    st = ws.registry.read_state()
    assert st["previous_epoch_gen"] == g3
    assert [e["epoch_gen"] for e in st["retained"]] == [g2, g3]


def test_rollback_is_a_forward_generation(tmp_path):
    ws = Workspace.open(tmp_path / "store")
    v1 = _seed_store(ws)
    _commit_tensors(ws, 2.0, "2")
    bad_gen = ws.epoch_gen
    prev_bindings = dict(ws.manager.previous_bindings)

    new_gen = ws.rollback_epoch()
    mgr = ws.manager
    assert new_gen == bad_gen + 1            # monotone: watchers fire
    assert mgr.rolled_back_from == bad_gen
    assert dict(mgr.world().bindings) == prev_bindings
    assert mgr.world().bindings["w"] == v1   # byte-identical target
    # the aborted generation joined the chain: a worker caught mid-flip
    # onto it can drain back before reclamation
    assert bad_gen in mgr.retained_generations()
    st = ws.registry.read_state()
    assert st["rolled_back_from"] == bad_gen
    # the marker clears on the next normal commit
    _commit_tensors(ws, 5.0, "5")
    assert ws.manager.rolled_back_from == 0
    assert ws.registry.read_state()["rolled_back_from"] == 0


def test_rollback_to_named_generation(tmp_path):
    ws = Workspace.open(tmp_path / "store")
    v1 = _seed_store(ws)
    g1 = ws.epoch_gen
    _commit_tensors(ws, 2.0, "2")
    _commit_tensors(ws, 3.0, "3")
    # roll past the newest retained generation to the older one
    new_gen = ws.rollback_epoch(to_gen=g1)
    assert ws.manager.world().bindings["w"] == v1
    assert new_gen > ws.manager.rolled_back_from
    with pytest.raises(RollbackError):
        ws.rollback_epoch(to_gen=999)


# ============================================================= gc dry-run
def test_gc_dry_run_reports_without_reclaiming(tmp_path):
    ws = Workspace.open(tmp_path / "store")
    _seed_store(ws)
    ws.load("app")                            # materialize gen-1 tables
    _commit_tensors(ws, 2.0, "2")
    ws.load("app")                            # materialize gen-2 tables
    tables = sorted(p.name for p in (ws.registry.root / "tables").glob("*"))
    chain_before = ws.manager.retained_generations()
    assert chain_before                       # the rollover window is open

    # preflight: what WOULD drain reclaim? nothing may actually move
    rep = ws.gc(drain=True, dry_run=True)
    assert rep.dry_run
    assert rep.removed_files > 0              # gen-1 tables become dead
    assert rep.bytes_reclaimed > 0
    assert sorted(p.name for p in (ws.registry.root / "tables").glob("*")) == tables
    assert ws.manager.retained_generations() == chain_before
    assert ws.registry.read_state()["retained"]  # state untouched too

    # the real drain reclaims exactly what the preflight named
    real = ws.gc(drain=True)
    assert not real.dry_run
    assert sorted(real.removed) == sorted(rep.removed)
    assert real.removed_files == rep.removed_files
    assert ws.manager.retained_generations() == []


# ============================================== EpochWatch mtime fallback
def test_epoch_watch_coarse_mtime_fallback(tmp_path, monkeypatch):
    """Two same-size commits inside the filesystem's mtime granularity
    leave (mtime_ns, size) identical — the stat fast path would sleep
    through the second commit forever. The throttled fallback parse
    notices it anyway."""
    import repro.link.workspace as wsmod

    ws = Workspace.open(tmp_path / "store")
    _seed_store(ws)
    watch = ws.epoch_watch()
    watch._fallback_interval_s = 0.01
    watch._next_fallback = time.monotonic() + 0.01

    # freeze the stat the watcher sees at its baseline: every later stat
    # looks unchanged, exactly like a coarse-granularity filesystem
    frozen = wsmod.os.stat(ws.registry.state_path)
    real_stat = wsmod.os.stat

    def coarse_stat(path, *a, **kw):
        if str(path) == str(ws.registry.state_path):
            return frozen
        return real_stat(path, *a, **kw)

    monkeypatch.setattr(wsmod.os, "stat", coarse_stat)

    _commit_tensors(ws, 2.0, "2")
    deadline = time.monotonic() + 5.0
    change = None
    while change is None and time.monotonic() < deadline:
        change = watch.poll()
        time.sleep(0.002)
    assert change is not None, "fallback parse never noticed the commit"
    assert change.epoch_gen == ws.epoch_gen
    assert watch.fallback_parses >= 1         # it was the fallback that fired

    # with the fallback disabled, the same frozen stat hides the commit
    watch2 = ws.epoch_watch(fallback_interval_s=None)
    _commit_tensors(ws, 3.0, "3")
    for _ in range(50):
        assert watch2.poll() is None
    assert watch2.parses == 0                 # pure stat behaviour


# ======================================== scheduler deadlines + coalescing
def _mk_engine(arch="mamba2-370m", cache_len=24):
    from repro import models
    from repro.configs import get_config
    from repro.serve import ServeEngine

    cfg = get_config(arch, smoke=True)
    params = models.init_params(cfg, 0)
    return cfg, ServeEngine(cfg, params, cache_len=cache_len, impl="naive")


def test_request_deadline_returns_structured_frame():
    """An expired request is answered with a DEADLINE completion (status
    + whatever partial row it earned) — never silently dropped."""
    from repro.serve import Request, STOP

    cfg, engine = _mk_engine()
    rng = np.random.default_rng(0)
    prompts = rng.integers(0, cfg.vocab_size, (2, 10), dtype=np.int32)
    # the serving tier's one clock domain: stamps are time.monotonic()
    now = time.monotonic()
    feed = iter(
        [
            # already a full second past its budget when accepted
            Request(rid=0, prompt=prompts[0], max_new_tokens=4,
                    enqueued_ts=now - 1.0, deadline_s=0.001),
            Request(rid=1, prompt=prompts[1], max_new_tokens=4),
            STOP,
        ]
    )
    done = {}
    report = engine.serve_loop(
        lambda: next(feed, STOP), lambda c: done.setdefault(c.rid, c),
        max_batch=2,
    )
    assert report.deadline_expired == 1
    assert done[0].status == "deadline"
    assert done[0].tokens.shape[0] == 0       # expired in queue: no decode
    assert done[1].status == "ok"
    assert done[1].tokens.shape == (4,)
    assert report.completed == 1              # ok completions only


def test_in_flight_slot_deadline_frees_slot_with_partial_row():
    from repro.serve import Request, STOP
    from repro.serve.scheduler import run_serve_loop

    cfg, engine = _mk_engine()
    rng = np.random.default_rng(1)
    prompt = rng.integers(0, cfg.vocab_size, (10,), dtype=np.int32)
    # a long decode with a budget it cannot meet: expires mid-flight
    feed = iter(
        [Request(rid=0, prompt=prompt, max_new_tokens=512,
                 deadline_s=0.05), STOP]
    )
    done = {}
    report = run_serve_loop(
        engine, lambda: next(feed, STOP),
        lambda c: done.setdefault(c.rid, c),
        max_batch=1, max_new_cap=512,
    )
    assert report.deadline_expired == 1
    assert done[0].status == "deadline"
    assert 0 < done[0].tokens.shape[0] < 512  # partial row came back
    assert report.completed == 0


def test_back_to_back_commits_coalesce_to_newest():
    """Two commits landing while slots drain produce ONE flip, to the
    newest generation — the superseded commit is counted, not flipped to."""
    from repro.serve import Request, STOP
    from repro.serve.scheduler import run_serve_loop

    cfg, engine = _mk_engine()
    rng = np.random.default_rng(2)
    prompts = rng.integers(0, cfg.vocab_size, (2, 10), dtype=np.int32)

    class FakeChange:
        def __init__(self, gen):
            self.epoch_gen = gen
            self.rolled_back_from = 0

    class FakeWatch:
        """Delivers gen 2 then gen 3 on consecutive polls — a double
        commit landing while request 0's slot is still decoding. The
        first poll happens before anything is admitted, so it stays
        quiet; polls 2 and 3 land mid-decode (request 0 runs 8 steps)."""

        def __init__(self):
            self.calls = 0

        def poll(self):
            self.calls += 1
            if self.calls == 2:
                return FakeChange(2)
            if self.calls == 3:
                return FakeChange(3)
            return None

    adopted = []
    feed = deque(
        [Request(rid=0, prompt=prompts[0], max_new_tokens=8), None,
         Request(rid=1, prompt=prompts[1], max_new_tokens=4), STOP]
    )
    done = {}
    report = run_serve_loop(
        engine,
        lambda: feed.popleft() if feed else STOP,
        lambda c: done.setdefault(c.rid, c),
        max_batch=1,
        max_new_cap=8,
        epoch_watch=FakeWatch(),
        on_epoch=lambda ch: adopted.append(ch.epoch_gen),
        watch_interval_s=0.0,
    )
    assert adopted == [3]                     # one flip, newest generation
    assert report.rollovers == 1
    assert report.coalesced_rollovers == 1
    assert report.completed == 2              # zero dropped across the roll


# ==================================== wedged adopt: deadline + auto-rollback
def test_adopt_deadline_fires_and_rolls_back(shm_ws):
    """A wedged ``adopt_epoch`` hits its deadline, auto-rolls-back, and
    the engine serves weights byte-identical to pre-flip gen N."""
    from repro.serve import ServeEngine, faults

    ws = shm_ws
    cfg, app_name = _publish_model(ws, "mamba2-370m")
    engine = ServeEngine.from_workspace(cfg, ws, app_name, cache_len=16)
    digest_v1 = _digest_params(engine.params)
    gen_v1 = ws.epoch_gen

    _commit_model_version(ws, cfg, seed=1, version="v2")
    bad_gen = ws.epoch_gen

    faults.install(faults.FaultPlan(wedge_adopt_s=30.0))
    t0 = time.perf_counter()
    with pytest.raises(AdoptDeadlineError) as exc:
        engine.adopt_epoch(ws, app_name, deadline_s=0.25)
    rollback_wall = time.perf_counter() - t0
    assert rollback_wall < 20.0               # deadline fired, no 30s ride

    assert exc.value.rolled_back_to == ws.epoch_gen
    assert ws.epoch_gen == bad_gen + 1        # rollback is a NEW generation
    assert ws.manager.rolled_back_from == bad_gen
    assert ws.registry.read_state()["rolled_back_from"] == bad_gen
    # byte-identity: the engine again serves exactly what gen_v1 served
    assert _digest_params(engine.params) == digest_v1

    # the wedge is one-shot: the next flip (a fresh commit) adopts cleanly
    _commit_model_version(ws, cfg, seed=2, version="v3")
    engine.adopt_epoch(ws, app_name, deadline_s=5.0)
    assert _digest_params(engine.params) == _digest_image(ws, app_name)
    assert _digest_params(engine.params) != digest_v1


def test_serve_loop_survives_aborted_flip(shm_ws):
    """The serve loop catches the deadline abort, counts it, resumes
    admission on the rolled-back weights, then adopts the rollback
    generation like any commit — every request completes."""
    from repro.serve import Request, STOP, ServeEngine, faults
    from repro.serve.scheduler import run_serve_loop

    ws = shm_ws
    cfg, app_name = _publish_model(ws, "mamba2-370m")
    engine = ServeEngine.from_workspace(cfg, ws, app_name, cache_len=24)
    digest_v1 = _digest_params(engine.params)

    faults.install(faults.FaultPlan(wedge_adopt_s=30.0))
    rng = np.random.default_rng(3)
    prompts = rng.integers(0, cfg.vocab_size, (4, 10), dtype=np.int32)

    state = {"k": 0, "committed": False}

    def source():
        k = state["k"]
        if k == 1 and not state["committed"]:
            # the bad commit lands while request 0 drains
            _commit_model_version(ws, cfg, seed=1, version="v2")
            state["committed"] = True
        if k >= 4:
            return STOP
        state["k"] += 1
        return Request(rid=k, prompt=prompts[k], max_new_tokens=4)

    done = {}
    report = run_serve_loop(
        engine, source, lambda c: done.setdefault(c.rid, c),
        max_batch=2, max_new_cap=4,
        epoch_watch=ws.epoch_watch(),
        on_epoch=lambda ch: engine.adopt_epoch(
            ws, app_name, deadline_s=0.25
        ),
        watch_interval_s=0.0,
    )
    assert report.completed == 4              # zero dropped across the abort
    assert report.rollover_aborts == 1
    assert report.rollovers >= 1
    assert ws.manager.rolled_back_from > 0    # the rollback landed in state
    # after the dust settles the engine serves the rolled-back bytes
    assert _digest_params(engine.params) == digest_v1


# ============================================ fleet chaos (spawn processes)
def test_back_to_back_rollover_fleet_converges(shm_ws):
    """Acceptance (a): two commits land mid-drain under live traffic; the
    fleet coalesces/chains flips and converges on the NEWEST generation,
    byte-verified, with zero dropped requests."""
    from repro.serve import run_traffic

    ws = shm_ws
    cfg, app_name = _publish_model(ws, "mamba2-370m")
    gen0 = ws.epoch_gen

    def rollover_fn():
        _commit_model_version(ws, cfg, seed=1, version="v2")
        _commit_model_version(ws, cfg, seed=2, version="v3")

    n = 12
    rep = run_traffic(
        ws,
        app_name,
        cfg=cfg,
        workers=2,
        n_requests=n,
        rate_hz=100.0,
        prompt_len=10,
        max_new_tokens=4,
        max_batch=2,
        timeout=JOIN_S * 2,
        rollover_at=n // 3,
        rollover_fn=rollover_fn,
    )
    s = rep.summary()
    assert rep.sent == n and rep.completed == n, s      # zero dropped
    assert rep.failed == 0, s
    assert ws.epoch_gen == gen0 + 2
    # every worker's FINAL adoption is the newest generation, and its
    # digest matches an independent fresh load of that generation
    final = {}
    for a in rep.adoptions:
        final[a["worker"]] = a
    assert set(final) == {0, 1}, s
    assert {a["epoch_gen"] for a in final.values()} == {ws.epoch_gen}, s
    want = _digest_image(ws, app_name)
    assert {a["digest"] for a in final.values()} == {want}, s
    # both outgoing generations rode the retained chain until this drain
    assert ws.manager.retained_generations() == [gen0, gen0 + 1]
    ws.gc(drain=True)
    assert ws.manager.retained_generations() == []


def test_sigkilled_worker_respawned_zero_lost(shm_ws):
    """Acceptance (c): worker 0 SIGKILLs itself mid-decode under Poisson
    load. The supervisor detects it via the rsp-ring owner record,
    re-routes its in-flight requests, respawns it with backoff — and
    every request completes."""
    from repro.serve import run_traffic

    ws = shm_ws
    cfg, app_name = _publish_model(ws, "mamba2-370m")

    n = 10
    rep = run_traffic(
        ws,
        app_name,
        cfg=cfg,
        workers=2,
        n_requests=n,
        rate_hz=100.0,
        prompt_len=10,
        max_new_tokens=4,
        max_batch=2,
        timeout=JOIN_S * 2,
        supervise=True,
        # dies AFTER its warmup request (4 decode steps) — mid measured load
        faults={"die_at_step": 6, "worker": 0},
    )
    s = rep.summary()
    assert rep.sent == n and rep.completed == n, s      # zero lost
    assert rep.restarts >= 1, s
    assert rep.failed == 0, s                 # supervised death != failure
    assert rep.rerouted_requests >= 1, s
    assert rep.kill_p99_s > 0 and np.isfinite(rep.kill_p99_s), s
    # honest-zero counters are present either way
    assert "kill_p99_latency_s" in s and "restarts" in s


def test_request_deadline_over_the_wire(shm_ws):
    """A deadline rides the request frame; expired requests come back as
    DEADLINE completions from a real worker process — answered, counted,
    never dropped."""
    from repro.serve import run_traffic

    ws = shm_ws
    cfg, app_name = _publish_model(ws, "mamba2-370m")

    n = 6
    rep = run_traffic(
        ws,
        app_name,
        cfg=cfg,
        workers=1,
        n_requests=n,
        rate_hz=200.0,
        prompt_len=10,
        max_new_tokens=4,
        max_batch=2,
        timeout=JOIN_S * 2,
        request_deadline_s=0.0005,            # expired on arrival
    )
    s = rep.summary()
    assert rep.sent == n and rep.completed == n, s
    assert rep.deadline_expired > 0, s
    # every completion is accounted for exactly once
    assert rep.deadline_expired + len(rep.latencies_s) == n, s


def test_sigkilled_worker_midstream_rerouted_stream_intact(shm_ws):
    """PR 10 acceptance: worker 0 SIGKILLs itself MID-STREAM. The
    supervisor re-routes its in-flight requests; the survivor replays
    each re-routed stream from seq 0 (sampling keys are a pure function
    of (seed, rid, i), so the replay is byte-identical) and the
    dispatcher's reassembly ends with zero gaps, zero duplicate seqs,
    and zero mismatches against the completion rows."""
    from repro.serve import run_traffic

    ws = shm_ws
    cfg, app_name = _publish_model(ws, "mamba2-370m")

    n, max_new = 10, 4
    rep = run_traffic(
        ws,
        app_name,
        cfg=cfg,
        workers=2,
        n_requests=n,
        rate_hz=100.0,
        prompt_len=10,
        max_new_tokens=max_new,
        max_batch=2,
        timeout=JOIN_S * 2,
        supervise=True,
        stream=True,
        temperature=0.7,
        top_k=8,
        sampling_seed=42,
        # dies AFTER its warmup request (4 decode steps) — mid stream
        faults={"die_at_step": 6, "worker": 0},
    )
    s = rep.summary()
    assert rep.sent == n and rep.completed == n, s      # zero lost
    assert rep.restarts >= 1, s
    assert rep.rerouted_requests >= 1, s
    assert rep.failed == 0, s
    assert rep.stream_gaps == 0, s
    assert rep.stream_mismatches == 0, s
    # every stream reassembled complete: seqs 0..max_new-1 exactly once
    assert set(rep.stream_tokens) == set(range(n)), s
    for rid, toks in rep.stream_tokens.items():
        assert len(toks) == max_new, (rid, toks, s)
    assert 0 < rep.ttft_p99_s and np.isfinite(rep.ttft_p99_s), s


def test_duplicated_stream_frames_absorbed_idempotently(shm_ws):
    """At-least-once delivery: a fault plan re-pushes every 2nd PARTIAL
    frame. The dispatcher's seq-keyed reassembly must count the dups and
    absorb them — no gaps, no mismatches, streams still complete."""
    from repro.serve import run_traffic

    ws = shm_ws
    cfg, app_name = _publish_model(ws, "mamba2-370m")

    n, max_new = 6, 4
    rep = run_traffic(
        ws,
        app_name,
        cfg=cfg,
        workers=1,
        n_requests=n,
        rate_hz=200.0,
        prompt_len=10,
        max_new_tokens=max_new,
        max_batch=2,
        timeout=JOIN_S * 2,
        stream=True,
        faults={"dup_stream_every": 2},
    )
    s = rep.summary()
    assert rep.sent == n and rep.completed == n and rep.failed == 0, s
    assert rep.stream_dup_frames > 0, s       # the fault actually fired
    assert rep.stream_gaps == 0, s
    assert rep.stream_mismatches == 0, s
    for rid, toks in rep.stream_tokens.items():
        assert len(toks) == max_new, (rid, toks, s)
