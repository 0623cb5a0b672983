"""Program spans and compile records (``repro.core.spans``) at the CPU size:
nesting and the ring's bound, compile attribution, and the spans the serve
loop and the epoch flip record."""

from __future__ import annotations

import threading
import time

import numpy as np
import pytest

from repro.core import spans


def _since(t_ns: int, name: str | None = None) -> list:
    return [r for r in spans.records()
            if r.t0_ns >= t_ns and (name is None or r.name == name)]


def test_spans_nest_by_thread_and_carry_their_attributes():
    t = time.monotonic_ns()
    started, release = threading.Event(), threading.Event()

    def other():
        with spans.span("t.other"):
            started.set()
            release.wait(10)

    with spans.span("t.outer", rid=7):
        th = threading.Thread(target=other)
        th.start()
        assert started.wait(10)
        with spans.span("t.inner"):
            pass
        release.set()
        th.join(10)
    assert not th.is_alive()
    got = {r.name: r for r in _since(t)}
    outer, inner, alone = got["t.outer"], got["t.inner"], got["t.other"]
    assert inner.parent == outer.index and outer.parent == -1
    # another thread's span has its own stack: no parent, though it ran
    # while t.outer was open
    assert alone.parent == -1
    assert outer.t0_ns <= inner.t0_ns <= inner.t1_ns <= outer.t1_ns
    assert outer.attrs == {"rid": 7} and inner.attrs is None


def test_the_ring_keeps_the_newest_records():
    rec = spans.Recorder(size=8)
    for i in range(20):
        with rec.span("t.ring", i=i):
            pass
    kept = rec.records()
    assert [r.attrs["i"] for r in kept] == list(range(12, 20))
    s = rec.summary()
    assert s["records_seen"] == 20 and s["records_kept"] == 8
    assert s["spans"]["t.ring"]["count"] == 8


def test_a_compile_inside_a_span_is_put_down_to_it():
    import jax

    x = np.arange(7, dtype=np.float32)
    t = time.monotonic_ns()
    with spans.span("t.outer"):
        with spans.span("t.compile"):
            jax.jit(lambda v: v * 3 + 1)(x).block_until_ready()
    jax.jit(lambda v: v * 5 - 2)(x).block_until_ready()
    events = [e for e in spans.compile_events()
              if e.t1_ns >= t and e.thread == threading.get_ident()]
    inside = {e.kind for e in events if e.span == "t.compile"}
    assert {"trace", "lower"} <= inside
    assert inside & {"compile", "cache_hit"}
    assert any(e.span is None and e.kind == "lower" for e in events)
    summary = spans.summary()
    assert summary["counters"]["compile.lower"] >= 2
    assert summary["compiles"]["lower"]["max_s"] > 0


def test_a_persistent_cache_hit_is_not_a_compile(tmp_path):
    """JAX times a persistent-cache read inside its backend compile: the
    record says ``cache_hit``, with the read nested inside it."""
    import jax
    from jax.experimental.compilation_cache import compilation_cache as cc

    keys = ("jax_compilation_cache_dir",
            "jax_persistent_cache_min_compile_time_secs",
            "jax_persistent_cache_min_entry_size_bytes")
    saved = {k: getattr(jax.config, k) for k in keys}
    try:
        jax.config.update("jax_compilation_cache_dir", str(tmp_path))
        jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
        jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
        cc.reset_cache()
        x = np.arange(11, dtype=np.float32)
        kinds = []
        for _ in range(2):
            t = time.monotonic_ns()
            with spans.span("t.cached"):
                jax.jit(lambda v: v * 7 + 3)(x).block_until_ready()
            kinds.append([e for e in spans.compile_events()
                          if e.t1_ns >= t and e.span == "t.cached"])
            jax.clear_caches()
    finally:
        for k, v in saved.items():
            jax.config.update(k, v)
        cc.reset_cache()
    first, second = ({e.kind for e in ev} for ev in kinds)
    assert "compile" in first and "cache_hit" not in first
    assert "cache_hit" in second and "compile" not in second
    hit = next(e for e in kinds[1] if e.kind == "cache_hit")
    read = next(e for e in kinds[1] if e.kind == "cache_retrieval")
    assert hit.t0_ns <= read.t0_ns and read.t1_ns <= hit.t1_ns


@pytest.fixture(scope="module")
def engine():
    from repro import models
    from repro.configs import get_config
    from repro.serve import ServeEngine

    cfg = get_config("mamba2-370m", smoke=True)
    return ServeEngine(cfg, models.init_params(cfg, 0), cache_len=64)


def _serve(engine, lens, max_new=(3, 5, 4, 6), rid0=0):
    from repro.serve import STOP, Request

    rng = np.random.default_rng(rid0)
    feed = [Request(rid=rid0 + i, prompt=rng.integers(0, 200, n, dtype=np.int32),
                    max_new_tokens=max_new[i % len(max_new)])
            for i, n in enumerate(lens)]
    done = {}
    report = engine.serve_loop(
        lambda: feed.pop(0) if feed else STOP,
        lambda c: done.setdefault(c.rid, c),
        max_batch=2, max_new_cap=8, temperature=0.7, top_k=4,
        on_delta=lambda d: None,
    )
    return report, done, feed


def test_the_serve_loop_records_each_admission_and_step(engine):
    t = time.monotonic_ns()
    report, done, _ = _serve(engine, [8, 8, 16, 8, 16])
    admits = _since(t, "serve.admit")
    steps = _since(t, "serve.step")
    assert len(admits) == report.admitted == 5
    assert sorted(r.attrs["rid"] for r in admits) == sorted(done)
    assert len(steps) == report.steps
    # every step decodes one token for each active slot; every request
    # had its first token from its prefill
    assert all(1 <= r.attrs["n_active"] <= 2 for r in steps)
    assert sum(r.attrs["n_active"] for r in steps) == sum(
        len(c.tokens) - 1 for c in done.values())
    children = {}
    for r in _since(t):
        children.setdefault(r.parent, []).append(r.name)
    for a in admits:
        assert sorted(children[a.index]) == [
            "serve.admit.prefill", "serve.admit.sample", "serve.admit.splice",
            "serve.admit.sync"]
    for s in steps:
        assert {"serve.step.dispatch", "serve.step.sync", "serve.step.emit",
                "serve.step.retire"} == set(children[s.index])
    assert _since(t, "serve.accept")
    assert report.summary()["programs_lowered"] == report.programs_lowered


def test_a_second_loop_lowers_nothing_and_a_new_length_lowers_a_prefill(
        engine):
    _serve(engine, [8, 16])
    again, _, _ = _serve(engine, [16, 8, 8], rid0=10)
    assert again.programs_lowered == {}
    longer, _, _ = _serve(engine, [8, 24], rid0=20)
    assert set(longer.programs_lowered) == {"serve.admit.prefill"}


def test_adopt_epoch_nests_the_load_and_the_lift():
    from repro import models
    from repro.configs import get_config
    from repro.launch.serve import publish_model
    from repro.link import Workspace
    from repro.serve import ServeEngine

    cfg = get_config("mamba2-370m", smoke=True)
    ws = Workspace.ephemeral("spans-")
    try:
        app = publish_model(ws, cfg, models.init_params_np(cfg, 0))
        t = time.monotonic_ns()
        engine = ServeEngine.from_workspace(cfg, ws, app, strategy="stable")
        assert {"link.load", "serve.lift"} <= {
            r.name for r in _since(t) if r.parent == -1}
        publish_model(ws, cfg, models.init_params_np(cfg, 1), version="v2")
        t = time.monotonic_ns()
        engine.adopt_epoch(ws, app, strategy="stable")
    finally:
        ws.close()
    (adopt,) = _since(t, "serve.adopt")
    load, lift = sorted((r for r in _since(t) if r.parent == adopt.index),
                        key=lambda r: r.t0_ns)
    assert (load.name, lift.name) == ("link.load", "serve.lift")
    assert adopt.t0_ns <= load.t0_ns <= load.t1_ns <= lift.t0_ns
