"""The paper's three dependency-management vignettes (§5.3), on a model zoo.

    PYTHONPATH=src python examples/vignettes.py

Vignette 1 — ABI compatibility: does a new weight bundle still export every
             symbol the deployed apps bind (with compatible shapes)?
Vignette 2 — CVE audit: which apps bind the "vulnerable" expert tensor from
             a specific bundle? (per-expert symbols <- fragmented manifests)
Vignette 3 — fine-grained interposition: route ONE layer's norm scale to an
             instrumented bundle for ONE app, leaving everything else alone.
Vignette 4 — preflight a risky library roll: stage the v2 bundle in a
             management transaction, read tx.diff()/tx.preview() to see the
             exact per-app relocation delta BEFORE commit, and abort when
             the preview shows broken bindings — epoch untouched.
Vignette 5 — warm-start a serving fleet inside an epoch: replicas spin up
             via the baked-arena stable-mmap path (one copy-on-write mmap,
             zero resolve/copy), an unrelated publish reuses every table
             (closure-hash keying), and the epoch path writes zero journal
             bytes throughout.
Vignette 6 — serve a Poisson load over the shm fleet: spawn ring-connected
             worker processes, drive exponential arrivals through the
             continuous-batching ``engine.serve_loop``, and read sustained
             req/s plus p50/p99 end-to-end latency off the TrafficReport.
Vignette 7 — roll a library under load (blue/green): while the fleet keeps
             serving, bake a v2 weights generation, preview the exact
             relocation delta, commit it ALONGSIDE the live generation,
             let every worker flip at a request boundary
             (epoch_watch/adopt_epoch), then drain and gc the old
             generation's segments — zero requests dropped end to end.
Vignette 8 — survive a bad roll: commit a v3 whose reload wedges, let the
             adopt deadline fire, and watch ``abort_adopt`` roll the store
             FORWARD to a generation that re-adopts the v2 world —
             byte-identical weights, journal-replay safe, the aborted
             generation reclaimed by the next drain gc.
Vignette 9 — survive a flaky artifact store: one machine bakes and exports
             (``ws.export_store()``), a fleet of fresh machines warms
             through ``stable-remote`` while the wire truncates a stream
             mid-blob (the fetch RESUMES via a range read), flips a byte
             (the hash check quarantines the transfer and a clean retry
             lands), and finally the store drops dead mid-rollout (warmup
             completes DEGRADED via local fallback bakes) — every loaded
             arena byte-identical to the baker's throughout.
Vignette 10 — stream a sampled response through a shared ring: workers
             push every generated token as its own PARTIAL frame on the
             MPMC response rings (temperature/top-k sampling with
             per-request PRNG keys), the dispatcher reassembles each
             stream in seq order and verifies it byte-for-byte against
             the final completion frame, and the report's TTFT quantiles
             show the first token landing well before the last.
"""

import numpy as np

from repro import models
from repro.ckpt import bundle_from_params
from repro.configs import get_config
from repro.core import ObjectKind, inspector, interpose, make_object
from repro.core.executor import LoadStats
from repro.link import Workspace


def main() -> None:
    # Everything lives under main(): vignette 6 spawns real worker
    # processes (spawn context re-imports this module in each child),
    # so the script body must not run at import time.
    ws = Workspace.ephemeral(prefix="repro-vignettes-")

    # World: an MoE model (fragmented per-expert symbols) + a dense model
    moe_cfg = get_config("olmoe-1b-7b", smoke=True)
    dense_cfg = get_config("starcoder2-3b", smoke=True)
    moe_params = {n: np.asarray(v) for n, v in models.init_params(moe_cfg, 0).items()}
    dense_params = {
        n: np.asarray(v) for n, v in models.init_params(dense_cfg, 1).items()
    }

    moe_bundle, moe_pl = bundle_from_params(
        "weights:olmoe", "v1", moe_params,
        fragment_layers=True, fragment_experts=True,
    )
    dense_bundle, dense_pl = bundle_from_params(
        "weights:starcoder", "v1", dense_params, fragment_layers=True
    )
    moe_app, _ = make_object(
        name="serve:olmoe", version="1", kind=ObjectKind.APPLICATION,
        refs=models.manifest_refs(moe_cfg, fragment=True), needed=["weights:olmoe"],
    )
    dense_app, _ = make_object(
        name="serve:starcoder", version="1", kind=ObjectKind.APPLICATION,
        refs=models.manifest_refs(dense_cfg, fragment=True),
        needed=["weights:starcoder"],
    )
    with ws.management() as tx:
        for o, p in [(moe_bundle, moe_pl), (dense_bundle, dense_pl),
                     (moe_app, b""), (dense_app, b"")]:
            tx.publish(o, p)

    t_moe = ws.load("serve:olmoe").table
    t_dense = ws.load("serve:starcoder").table

    # ---------------------------------------------------------------- vignette 1
    print("=== Vignette 1: ABI compatibility (Alice) ===")
    # the proposed v2 bundle drops layer 0's mlp_norm and reshapes a router
    v2_params = {
        k: v for k, v in moe_params.items() if k != "blocks/mlp_norm/scale"
    }
    v2_params["blocks/router/w"] = moe_params["blocks/router/w"][:, :, : -1]
    v2_bundle, _ = bundle_from_params(
        "weights:olmoe-v2", "v2", v2_params,
        fragment_layers=True, fragment_experts=True,
    )
    conn = inspector.to_sqlite(
        [t_moe, t_dense], abi_objects=[moe_bundle, v2_bundle]
    )
    missing = inspector.abi_incompatibilities(
        conn, app="serve:olmoe", old_bundle="weights:olmoe",
        new_bundle="weights:olmoe-v2",
    )
    print(f"  upgrading to v2 would break {len(missing)} relocations, e.g.:")
    for sym, req in missing[:4]:
        print(f"    {sym}  (required by {req})")

    # ---------------------------------------------------------------- vignette 2
    print("=== Vignette 2: CVE audit (Bob) ===")
    bad_symbol = "blocks/experts/w_down[1][3]"   # layer 1, expert 3
    hits = inspector.cve_audit(conn, bundle="weights:olmoe", symbol=bad_symbol)
    print(f"  apps binding {bad_symbol!r}: {hits}")
    hits2 = inspector.cve_audit(conn, bundle="weights:olmoe", symbol="nonexistent")
    print(f"  apps binding a clean symbol: {hits2} (quarantine nothing)")

    # ---------------------------------------------------------------- vignette 3
    print("=== Vignette 3: fine-grained interposition (Charlie) ===")
    dbg = {"blocks/attn_norm/scale[1]": moe_params["blocks/attn_norm/scale"][1] * 100}
    dbg_bundle, dbg_pl = bundle_from_params("debug:norms", "1", dbg)
    with ws.management() as tx:
        tx.publish(dbg_bundle, dbg_pl)
    n = interpose.rebind(
        t_moe, symbol_glob="blocks/attn_norm/scale[1]", new_provider=dbg_bundle
    )
    img = ws.executor._apply_table(
        ws.world().resolve("serve:olmoe"), t_moe, LoadStats()
    )
    print(f"  rebound {n} relocation(s); layer-1 norm now instrumented:")
    print(
        "    layer0 scale[:3] =", np.asarray(img["blocks/attn_norm/scale[0]"])[:3],
        "\n    layer1 scale[:3] =", np.asarray(img["blocks/attn_norm/scale[1]"])[:3],
    )
    edited = [r for r in inspector.table_records(t_moe) if r["flags"]]
    print(f"  inspector shows {len(edited)} edited row(s) -> fully auditable")

    # ---------------------------------------------------------------- vignette 4
    print("=== Vignette 4: preflight a risky library roll (Dana) ===")
    # Dana wants to roll weights:olmoe to the v2 params from vignette 1 (which
    # drop a norm scale and reshape the router). Stage it, preview, decide.
    roll_bundle, roll_pl = bundle_from_params(
        "weights:olmoe", "v2", v2_params,
        fragment_layers=True, fragment_experts=True,
    )


    class AbortRoll(Exception):
        pass


    epoch_before = ws.epoch
    try:
        with ws.management() as tx:
            tx.publish(roll_bundle, roll_pl)
            diff = tx.diff()
            print(f"  staged diff: upgraded={sorted(diff.upgraded)}")
            preview = tx.preview()
            d = preview.delta_for("serve:olmoe")
            print(
                f"  preview for serve:olmoe: {len(d.changed)} changed, "
                f"{len(d.unresolved)} unresolved, "
                f"tables to rebuild: {preview.tables_to_rebuild}"
            )
            for u in d.unresolved[:3]:
                print(f"    would break: {u['symbol']}")
            # the same delta is visible through the one-call surface:
            rep = ws.explain("serve:olmoe", pending=True)
            assert rep.pending and rep.delta is not None
            if d.unresolved:
                raise AbortRoll  # commit would strand these relocations
    except AbortRoll:
        print(
            f"  roll aborted pre-commit; epoch still {ws.epoch} "
            f"(was {epoch_before}), journal truncated "
            f"({len(ws.journal.entries())} entries)"
        )
    assert ws.epoch == epoch_before
    np.testing.assert_array_equal(
        np.asarray(ws.load("serve:olmoe")["blocks/router/w[0]"]),
        moe_params["blocks/router/w"][0],
    )
    print("  committed world unchanged -> jobs keep loading the v1 mapping")

    # ---------------------------------------------------------------- vignette 5
    print("=== Vignette 5: warm-start a serving fleet inside an epoch (Eve) ===")
    # Eve runs a fleet of replicas of serve:starcoder. Every replica start is an
    # epoch load: the relocation work already happened at end_mgmt (the table
    # was materialized AND pre-applied into a baked arena), so each warm start
    # is one copy-on-write mmap + view construction.
    import time as _time

    REPLICAS = 4


    def _journal_bytes() -> int:
        p = ws.registry.journal_path
        return p.stat().st_size if p.exists() else 0


    journal_bytes0 = _journal_bytes()
    # one-call fleet warmup: the whole world is preloaded in parallel through
    # the process-wide EpochCache — after this, every replica spin-up is a hit
    warm = ws.warmup(workers=REPLICAS)
    print(
        f"  warmup: {len(warm.names)} app(s) preloaded in "
        f"{warm.wall_s * 1e3:.1f}ms (fills={warm.cache_fills})"
    )
    t0 = _time.perf_counter()
    fleet = [ws.load("serve:starcoder", strategy="stable-mmap")
             for _ in range(REPLICAS)]
    mmap_s = _time.perf_counter() - t0
    t0 = _time.perf_counter()
    shared = [ws.load("serve:starcoder", strategy="stable-mmap-cached")
              for _ in range(REPLICAS)]
    cached_s = _time.perf_counter() - t0
    t0 = _time.perf_counter()
    for _ in range(REPLICAS):
        ws.load("serve:starcoder", strategy="stable")
    copy_s = _time.perf_counter() - t0
    assert all(r.arena is shared[0].arena for r in shared)  # ONE shared mapping
    print(
        f"  {REPLICAS} replicas: epoch-resident {cached_s * 1e3:.1f}ms vs "
        f"stable-mmap {mmap_s * 1e3:.1f}ms vs "
        f"table-driven copy {copy_s * 1e3:.1f}ms "
        f"({copy_s / max(cached_s, 1e-9):.0f}x); all cached replicas share "
        f"one read-only mapping"
    )
    # CoW isolation: one replica scribbling on its weights cannot leak into the
    # baked arena or its siblings
    fleet[0]["final_norm/scale"][:] = 0
    assert np.any(np.asarray(fleet[1]["final_norm/scale"]))
    assert _journal_bytes() == journal_bytes0  # epoch path: zero journal bytes
    print("  epoch-path journal bytes written by the fleet: 0 (asserted)")
    # A publish that does not touch the fleet's closure (the debug bundle roll
    # below) reuses every materialized table and arena: replicas keep warm-
    # starting across the epoch bump with zero re-materialization.
    with ws.management() as tx:
        tx.publish(*bundle_from_params(
            "debug:norms", "2",
            {"blocks/attn_norm/scale[1]": moe_params["blocks/attn_norm/scale"][1]},
        ))
    mat = tx.materialization
    print(
        f"  unrelated publish: re-materialized={sorted(mat.materialized)}, "
        f"tables reused={mat.tables_reused}"
    )
    assert "serve:starcoder" in mat.reused
    ws.load("serve:starcoder", strategy="stable-mmap")  # still one mmap away
    print("  fleet keeps warm-starting across the epoch bump")

    # ---------------------------------------------------------------- vignette 6
    print("=== Vignette 6: serve a Poisson load over the shm fleet ===")
    # The traffic plane end to end: real worker processes, each loading the
    # app through ONE machine-shared shm arena, wired to this dispatcher by
    # shm request/response rings, running the continuous-batching
    # engine.serve_loop. Workers reconstruct params 1:1 from the image, so
    # the served app uses whole-tensor symbols (no per-layer fragments).
    tr_cfg = get_config("mamba2-370m", smoke=True)
    tr_params = {
        n: np.asarray(v) for n, v in models.init_params(tr_cfg, 2).items()
    }
    tr_bundle, tr_pl = bundle_from_params("weights:mamba", "v1", tr_params)
    tr_app, _ = make_object(
        name="serve:mamba", version="1", kind=ObjectKind.APPLICATION,
        refs=models.manifest_refs(tr_cfg), needed=["weights:mamba"],
    )
    with ws.management() as tx:
        tx.publish(tr_bundle, tr_pl)
        tx.publish(tr_app)
    from repro.serve import run_traffic

    rep = run_traffic(
        ws, "serve:mamba", cfg=tr_cfg,
        workers=2, n_requests=8, rate_hz=50.0,
        prompt_len=8, max_new_tokens=6, max_batch=2,
    )
    assert rep.failed == 0 and rep.completed == 8
    print(
        f"  {rep.workers} workers ready in {max(rep.ready_s):.1f}s; "
        f"{rep.completed}/{rep.sent} requests completed"
    )
    print(
        f"  sustained {rep.req_per_s:.1f} req/s, {rep.tok_per_s:.1f} tok/s; "
        f"p50 {rep.p50_s * 1e3:.1f}ms, p99 {rep.p99_s * 1e3:.1f}ms"
    )
    # every ring segment is already unlinked; a SIGKILLed worker would
    # instead leave a dead-owner ring record for the next ws.gc()
    print("  ring segments reclaimed; fleet shm arena survives for reuse")

    # ---------------------------------------------------------------- vignette 7
    print("=== Vignette 7: roll a library under load (Frank) ===")
    # Blue/green rollover end to end: the fleet keeps serving while Frank
    # rolls weights:mamba to v2 — bake, preview, flip, drain, gc.
    import hashlib as _hashlib

    from repro.core import shm_arena as _shm_arena

    v2_mamba = {
        n: np.asarray(v) for n, v in models.init_params(tr_cfg, 3).items()
    }
    gen_before = ws.epoch_gen
    pre_roll: list = []


    def commit_v2():
        # snapshot generation N's segments, then bake + preview + commit:
        # the operator reads the exact per-app delta (staged interposition
        # edits would show as `edited` rows) BEFORE the flip
        pre_roll.extend(
            r["name"] for r in _shm_arena.list_segments(ws.registry)
            if r.get("kind") != "ring"
        )
        b2, p2 = bundle_from_params("weights:mamba", "v2", v2_mamba)
        with ws.management() as tx:
            tx.publish(b2, p2)
            pv = tx.preview()
            d = pv.delta_for("serve:mamba")
            assert d is not None and pv.is_clean
            print(
                f"  preview: {len(d.changed)} relocation(s) change, "
                f"{len(d.unresolved)} break -> safe to flip"
            )
        # clean exit = end_mgmt: generation N+1 now lives ALONGSIDE N


    rep2 = run_traffic(
        ws, "serve:mamba", cfg=tr_cfg,
        workers=2, n_requests=9, rate_hz=50.0,
        prompt_len=8, max_new_tokens=6, max_batch=2,
        rollover_at=3, rollover_fn=commit_v2,
    )
    assert rep2.failed == 0 and rep2.completed == 9   # zero dropped
    assert ws.epoch_gen == gen_before + 1
    # the weights every worker now serves are byte-identical to a fresh
    # independent load of generation N+1
    img = ws.load("serve:mamba", strategy="stable-mmap-cached")
    h = _hashlib.blake2b(digest_size=16)
    for nm in sorted(img.tensors):
        h.update(
            np.ascontiguousarray(img.tensors[nm]).view(np.uint8).tobytes()
        )
    assert {a["digest"] for a in rep2.adoptions} == {h.hexdigest()}
    print(
        f"  flip: {len(rep2.adoptions)} worker(s) adopted gen "
        f"{ws.epoch_gen} at a request boundary in "
        f"{rep2.rollover_wall_s * 1e3:.0f}ms; weights byte-identical"
    )
    print(
        f"  rollover p99 {rep2.rollover_p99_s * 1e3:.1f}ms vs steady p99 "
        f"{rep2.steady_p99_s * 1e3:.1f}ms; {rep2.completed}/{rep2.sent} "
        f"requests completed across the roll"
    )
    g = ws.gc(drain=True)
    assert all(nm in g.removed for nm in pre_roll)
    print(
        f"  drain: gc reclaimed {g.segments_removed} old-generation "
        f"segment(s); the v2 world keeps serving"
    )
    ws.load("serve:mamba", strategy="stable-mmap-cached")

    # ---------------------------------------------------------------- vignette 8
    print("=== Vignette 8: survive a bad roll (Grace) ===")
    # Grace ships a v3 that wedges on reload (a fault plan stands in for a
    # hung filesystem / corrupt bundle). The adopt deadline is the ONLY
    # thing standing between her and a wedged fleet: it fires, abort_adopt
    # rolls the store FORWARD (rollback is a new generation, so every
    # watcher's epoch_watch sees it like any commit), and the engine is
    # serving the v2 bytes again — provably.
    import time as _time

    from repro.core.errors import AdoptDeadlineError
    from repro.serve import FaultPlan, ServeEngine
    from repro.serve import faults as _faults

    engine = ServeEngine.from_workspace(tr_cfg, ws, "serve:mamba",
                                        cache_len=16)
    good = h.hexdigest()          # the v2 digest vignette 7 just verified
    gen_good = ws.epoch_gen

    v3_mamba = {
        n: np.asarray(v) for n, v in models.init_params(tr_cfg, 4).items()
    }
    b3, p3 = bundle_from_params("weights:mamba", "v3", v3_mamba)
    with ws.management() as tx:
        tx.publish(b3, p3)
    print(f"  committed v3 as generation {ws.epoch_gen} — but its reload "
          f"wedges")

    _faults.install(FaultPlan(wedge_adopt_s=30.0))
    try:
        t0 = _time.perf_counter()
        try:
            engine.adopt_epoch(ws, "serve:mamba", deadline_s=0.3)
            raise AssertionError("wedged adopt did not deadline")
        except AdoptDeadlineError as err:
            wall = _time.perf_counter() - t0
            rolled_back_to = err.rolled_back_to
    finally:
        _faults.clear()

    assert rolled_back_to == gen_good + 2 == ws.epoch_gen
    img3 = ws.load("serve:mamba", strategy="stable-mmap-cached")
    h3 = _hashlib.blake2b(digest_size=16)
    for nm in sorted(img3.tensors):
        h3.update(
            np.ascontiguousarray(img3.tensors[nm]).view(np.uint8).tobytes()
        )
    assert h3.hexdigest() == good  # byte-identical to pre-roll v2
    print(
        f"  deadline fired at 0.3s; rolled back to generation "
        f"{ws.epoch_gen} in {wall:.2f}s total — weights byte-identical "
        f"to v2"
    )
    ws.gc(drain=True)             # the aborted v3 generation is reclaimed
    ws.load("serve:mamba", strategy="stable-mmap-cached")
    print("  drain: aborted generation reclaimed; v2 keeps serving")
    print("  failure mode          detection                recovery")
    print("  -------------------   ----------------------   ---------------------------")
    print("  wedged/slow reload    adopt_epoch deadline     auto-rollback (forward gen)")
    print("  bad weights shipped   operator / digest        ws.rollback_epoch()")
    print("  SIGKILLed worker      dead rsp-ring owner      supervisor re-route + respawn")
    print("  stuck request         per-request deadline     DEADLINE frame, slot freed")

    # ---------------------------------------------------------------- vignette 9
    print("=== Vignette 9: survive a flaky artifact store (Heidi) ===")
    # Heidi bakes ONCE on this machine and ships the bytes to a fleet that
    # never bakes: ws.export_store() publishes every baked arena as a
    # content-addressed, zlib-framed blob; repro.launch.store serves it;
    # fresh machines warm through the `stable-remote` strategy. The wire
    # is hostile today — streams truncate, bytes flip, and the store dies
    # mid-rollout — and not one corrupt byte may become epoch-visible.
    from pathlib import Path as _Path

    from repro.core import EpochCache as _EpochCache
    from repro.core.arena_store import FetchPolicy
    from repro.launch.store import StoreServer
    from repro.serve.faults import StoreFaultPlan

    export = ws.export_store()
    print(
        f"  baker exported {export['entries']} arena blob(s): "
        f"{export['raw_bytes']} raw -> {export['blob_bytes']} encoded "
        f"({export['codec']})"
    )
    policy = FetchPolicy(connect_timeout_s=1.0, read_timeout_s=1.0,
                         retry_budget=6, backoff_base_s=0.02,
                         backoff_max_s=0.25)
    mamba_world = ws.world()
    mamba_app = mamba_world.resolve("serve:mamba")
    mamba_key = ws.executor.closure_key(mamba_app, mamba_world)
    truth = ws.registry.arena_path(
        mamba_app.content_hash, mamba_key
    ).read_bytes()


    def fresh_machine():
        # the fleet machine: objects replicated, never baked — identical
        # content hashes, empty tables/
        m = Workspace.ephemeral(prefix="repro-vignette9-",
                                epoch_cache=_EpochCache())
        b2, p2 = bundle_from_params("weights:mamba", "v2", v2_mamba)
        with m.management() as tx:
            tx.publish(b2, p2)
            tx.publish(tr_app)
        for p in _Path(m.root).glob("tables/*"):
            p.unlink()
        return m


    blob_len = export["blob_bytes"] // max(export["entries"], 1)
    # -- a mid-stream truncation: the fetch must RESUME, not restart
    srv = StoreServer(
        _Path(ws.root) / "store",
        faults=StoreFaultPlan(truncate_at=blob_len // 2, truncate_n=1),
    ).start()
    m1 = fresh_machine()
    m1.attach_store(srv.url, policy=policy)
    m1.load("serve:mamba", strategy="stable-remote")
    r1 = m1.store_report()
    assert r1.fetch_resumed >= 1 and not r1.degraded
    assert m1.registry.arena_path(
        mamba_app.content_hash, mamba_key
    ).read_bytes() == truth
    print(
        f"  truncated at byte {blob_len // 2}: resumed via range read "
        f"(retries={r1.fetch_retries}, resumed={r1.fetch_resumed}); "
        f"arena byte-identical to the baker's"
    )
    srv.stop()
    m1.close()

    # -- a flipped byte: the content-hash check quarantines the transfer
    srv = StoreServer(
        _Path(ws.root) / "store",
        faults=StoreFaultPlan(flip_at=blob_len // 3, flip_n=1),
    ).start()
    m2 = fresh_machine()
    m2.attach_store(srv.url, policy=policy)
    m2.load("serve:mamba", strategy="stable-remote")
    r2 = m2.store_report()
    assert r2.quarantined == 1 and not r2.degraded
    assert m2.registry.arena_path(
        mamba_app.content_hash, mamba_key
    ).read_bytes() == truth
    qdir = _Path(m2.root) / "store" / "quarantine"
    print(
        f"  flipped byte caught by blake2b before admission: "
        f"{len(list(qdir.glob('*.bad')))} quarantined transfer(s) with "
        f"structured records; clean retry landed identical bytes"
    )
    g9 = m2.gc()
    assert g9.store_files_removed >= 2
    print(
        f"  ws.gc() reclaimed {g9.store_files_removed} quarantine file(s) "
        f"(never retried from quarantine — corrupt bytes leave the machine)"
    )
    srv.stop()
    m2.close()

    # -- the store drops dead mid-rollout: degrade, don't wedge
    m3 = fresh_machine()
    warm9 = m3.warmup(["serve:mamba"], store="http://127.0.0.1:9",
                      policy=policy)
    assert warm9.degraded and warm9.store["fallback_bakes"] == 1
    assert m3.registry.arena_path(
        mamba_app.content_hash, mamba_key
    ).read_bytes() == truth
    print(
        f"  dead store: warmup completed DEGRADED "
        f"(fallback_bakes={warm9.store['fallback_bakes']}) — local bake, "
        f"same bytes, fleet still comes up"
    )
    m3.close()

    print("  failure mode          detection                recovery")
    print("  -------------------   ----------------------   ---------------------------")
    print("  refused connect       socket error             capped backoff + jitter, budgeted")
    print("  truncated stream      short read vs length     range-read RESUME of the partial")
    print("  flipped/corrupt bytes blake2b vs index digest  quarantine (+record), clean re-fetch")
    print("  slow-loris stall      per-read timeout         cut the cord, resume")
    print("  dead store            retry budget exhausted   degrade: local bake, degraded=True")

    # ---------------------------------------------------------------- vignette 10
    print("=== Vignette 10: stream a sampled response through a shared ring ===")
    # Ivan's users watch tokens appear one at a time: every decode step a
    # worker pushes a PARTIAL frame (rid, seq, token span) on its response
    # ring, the dispatcher reassembles each stream strictly in seq order,
    # and at completion verifies the reassembled stream byte-for-byte
    # against the authoritative completion frame. Decode samples with
    # temperature/top-k — token i of request r is a pure function of
    # (sampling_seed, r, i), so the stream a user sees never depends on
    # which siblings shared the batch. Request rings run in MPMC mode:
    # multiple producers reserve slots through a bakery-locked claim
    # cursor, then write and publish independently.
    rep10 = run_traffic(
        ws, "serve:mamba", cfg=tr_cfg,
        workers=2, n_requests=6, rate_hz=50.0,
        prompt_len=8, max_new_tokens=6, max_batch=2,
        stream=True, temperature=0.7, top_k=8, sampling_seed=42,
        mpmc=True,
    )
    assert rep10.failed == 0 and rep10.completed == 6
    assert rep10.partial_frames == 6 * 6       # every token was streamed
    assert rep10.stream_gaps == 0              # in-order, no holes
    assert rep10.stream_mismatches == 0        # reassembly == completion
    assert len(rep10.stream_tokens) == 6
    assert all(len(t) == 6 for t in rep10.stream_tokens.values())
    print(
        f"  {rep10.partial_frames} PARTIAL frames streamed for "
        f"{rep10.completed} requests; {rep10.stream_gaps} gaps, "
        f"{rep10.stream_mismatches} reassembly mismatches (asserted 0)"
    )
    assert 0.0 < rep10.ttft_p50_s <= rep10.ttft_p99_s <= rep10.p99_s
    print(
        f"  TTFT p50 {rep10.ttft_p50_s * 1e3:.1f}ms / p99 "
        f"{rep10.ttft_p99_s * 1e3:.1f}ms vs completion p99 "
        f"{rep10.p99_s * 1e3:.1f}ms — the first token lands well before "
        f"the last"
    )
    # determinism across runs: same (seed, rid, position) -> same stream,
    # regardless of arrival timing or batch composition
    rep10b = run_traffic(
        ws, "serve:mamba", cfg=tr_cfg,
        workers=1, n_requests=6, rate_hz=200.0,
        prompt_len=8, max_new_tokens=6, max_batch=3,
        stream=True, temperature=0.7, top_k=8, sampling_seed=42,
    )
    assert set(rep10b.stream_tokens) == set(rep10.stream_tokens)
    assert all(
        np.array_equal(rep10b.stream_tokens[r], rep10.stream_tokens[r])
        for r in rep10.stream_tokens
    )
    print(
        "  re-served with different workers/batching/arrivals: every "
        "stream byte-identical (per-request PRNG keys)"
    )
    ws.close()


if __name__ == "__main__":
    main()
