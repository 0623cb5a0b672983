"""Smoke run of stable-linked serving on a TPU, at published widths.

    python chip_smoke.py               # one chip: serve, roll the weights, serve
    python chip_smoke.py --chips 4     # four-chip host: the fleet phase only

The one-chip run drives mamba2-370m at its published ``ModelConfig`` in one
process, through the calls ``repro.launch.serve`` makes: weights built from
``--seed`` are published with their app in one ``ws.management()``
transaction, loaded on the epoch path by ``ServeEngine.from_workspace``, and
served by ``engine.serve_loop``. Once that traffic completes, a second
generation of weights (the next seed) is committed; the loop notices the
commit, flips with ``engine.adopt_epoch`` at the request boundary, and serves
the same requests again. The run passes only if all of these hold:

(a) the params on the device hash to the published bundle's bytes, before
    and after the flip;
(b) prefill followed by 3 decode steps gives the logits ``models.forward``
    gives over the same tokens with no cache, within ``logit_tolerance``;
(c) every request returns exactly its ``max_new_tokens`` tokens, each in
    the vocabulary;
(d) the flip, and all serving after it, compiles nothing.

``--chips N`` runs only the fleet phase: a dispatcher routing over replica
processes that share one shm arena. This process initialises no JAX backend.
It serves one greedy request set through ``run_traffic`` with one worker,
then with N (worker i pinned to chip i), and passes only if N distinct TPU
chips served, no request was lost, and every request's tokens are identical
between the two runs.

Phase times are printed as smoke observations, not metrics. The last line of
standard output is one JSON object naming the device; a failed check, or a
platform other than TPU, exits non-zero and prints no such line.
"""

from __future__ import annotations

import argparse
import hashlib
import json
import sys
import time
from collections import deque
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parent / "src"))

import numpy as np  # noqa: E402

from repro import models  # noqa: E402
from repro.configs import get_config  # noqa: E402
from repro.core.chips import (  # noqa: E402
    compile_cache_dir,
    device_report,
    jax_backend_initialized,
)
from repro.launch.serve import publish_model  # noqa: E402
from repro.link import Workspace  # noqa: E402

ARCH = "mamba2-370m"
PROMPT_LENS = (32, 64)       # two prefill programs
N_REQUESTS = 8
MAX_NEW = 16
MAX_BATCH = 4
CHECK_DECODE_STEPS = 3


def observe(**kw) -> None:
    """Print phase seconds: what one smoke run saw, not a metric."""
    print("smoke observation (not a metric):", json.dumps(kw), flush=True)


def digest(arrays: dict) -> str:
    h = hashlib.blake2b(digest_size=16)
    for name in sorted(arrays):
        h.update(np.ascontiguousarray(arrays[name]).view(np.uint8).tobytes())
    return h.hexdigest()


def logit_tolerance(cfg) -> float:
    """Bound on max|cached - forward| / max|forward| over the checked logits.

    The cached path (chunked scan for the prompt, then the one-token
    recurrence) and the plain forward compute the same function but round
    in different places. Each of the ``num_layers`` residual blocks rounds
    its output to ``cfg.dtype`` (unit roundoff u: 2**-8 for bfloat16), so
    the two paths differ by a sum of about ``num_layers`` independent
    rounding errors of relative size u each: about sqrt(num_layers) * u.
    The bound allows four times that, for the logits matmul and the
    activations' spread. For mamba2-370m in bfloat16 it is 4 * sqrt(48) *
    2**-8 = 0.108: well below the size of a wrong cache (an error of order
    1), well above rounding noise.
    """
    import jax.numpy as jnp

    u = float(jnp.finfo(cfg.dtype).eps) / 2
    return 4.0 * np.sqrt(cfg.num_layers) * u


def cache_vs_forward(cfg, engine, seed: int) -> float:
    """(b): relative max error of prefill + decode logits against
    ``models.forward`` over the same tokens."""
    import jax
    import jax.numpy as jnp

    s = PROMPT_LENS[0]
    rng = np.random.default_rng(seed)
    tokens = rng.integers(
        0, cfg.vocab_size, (1, s + CHECK_DECODE_STEPS), dtype=np.int32
    )
    logits, cache = engine._prefill(engine.params, {"tokens": tokens[:, :s]})
    got = [logits[:, -1]]
    decode = jax.jit(lambda p, c, t: models.decode_step(cfg, p, c, t))
    for k in range(CHECK_DECODE_STEPS):
        logits, cache = decode(engine.params, cache, tokens[:, s + k:s + k + 1])
        got.append(logits[:, -1])
    forward = jax.jit(lambda p, t: models.forward(cfg, p, {"tokens": t})[0])
    want = forward(engine.params, jnp.asarray(tokens))[0, s - 1:]
    got = np.asarray(jnp.concatenate(got).astype(jnp.float32))
    want = np.asarray(want.astype(jnp.float32))
    return float(np.max(np.abs(got - want)) / np.max(np.abs(want)))


class CompileCounter:
    """Programs lowered and backend compiles, from ``jax.monitoring``.

    A lowering happens for every new program, whether or not the
    persistent compilation cache then supplies the executable."""

    def __init__(self):
        self.lowered = 0
        self.compiled = 0
        self.compile_s = 0.0

    def __call__(self, event: str, duration: float, **kw) -> None:
        if event == "/jax/core/compile/jaxpr_to_mlir_module_duration":
            self.lowered += 1
        elif event == "/jax/core/compile/backend_compile_duration":
            self.compiled += 1
            self.compile_s += duration


def requests(cfg, seed: int, rid0: int):
    from repro.serve import Request

    rng = np.random.default_rng(seed)
    return [
        Request(
            rid=rid0 + i,
            prompt=rng.integers(
                0, cfg.vocab_size, PROMPT_LENS[i % len(PROMPT_LENS)],
                dtype=np.int32,
            ),
            max_new_tokens=MAX_NEW,
        )
        for i in range(N_REQUESTS)
    ]


def token_failures(cfg, outputs: dict, rids) -> list[str]:
    """(c): every request answered with exactly MAX_NEW in-vocab tokens."""
    bad = []
    for rid in rids:
        toks = outputs.get(rid)
        if toks is None:
            bad.append(f"request {rid} never completed")
        elif len(toks) != MAX_NEW:
            bad.append(f"request {rid} returned {len(toks)} tokens, not {MAX_NEW}")
        elif not ((toks >= 0) & (toks < cfg.vocab_size)).all():
            bad.append(f"request {rid} returned tokens outside the vocabulary")
    return bad


def serve_phase(cfg, seed: int) -> list[str]:
    """Publish, load, serve, commit, flip, serve; return failed checks."""
    import jax

    from repro.serve import STOP, ServeEngine

    counter = CompileCounter()
    jax.monitoring.register_event_duration_secs_listener(counter)
    failures: list[str] = []
    ws = Workspace.ephemeral("chip-smoke-")
    try:
        t0 = time.perf_counter()
        params = models.init_params_np(cfg, seed)
        published = {1: digest(params)}
        app = publish_model(ws, cfg, params)
        del params
        publish_s = time.perf_counter() - t0

        t0 = time.perf_counter()
        engine = ServeEngine.from_workspace(
            cfg, ws, app, strategy="stable",
            cache_len=max(PROMPT_LENS) + MAX_NEW,
        )
        jax.block_until_ready(engine.params)
        spinup_s = time.perf_counter() - t0
        load_s = engine.load_stats.startup_s
        observe(publish_s=publish_s, load_s=load_s, lift_s=spinup_s - load_s)
        if digest(jax.device_get(engine.params)) != published[1]:
            failures.append("(a) device params differ from the published bundle")

        err = cache_vs_forward(cfg, engine, seed)
        tol = logit_tolerance(cfg)
        print(f"(b) cached vs forward logits: relative max error {err!r} "
              f"(tolerance {tol!r})", flush=True)
        if not err <= tol:
            failures.append(f"(b) cached logits off by {err!r} > {tol!r}")

        # One serve loop over both generations: generation 1's requests,
        # then a commit, a flip at the empty request boundary, and the same
        # prompts again on generation 2.
        gen1 = requests(cfg, seed, rid0=0)
        gen2 = requests(cfg, seed, rid0=N_REQUESTS)
        pending = deque(gen1)
        outputs: dict[int, np.ndarray] = {}
        state = {"committed": 1, "serving": 1}
        marks: dict[str, float] = {}

        def source():
            if pending:
                return pending.popleft()
            if state["serving"] == 1:
                if state["committed"] == 1 and len(outputs) == N_REQUESTS:
                    t = time.perf_counter()
                    marks["gen1_done"] = t
                    params2 = models.init_params_np(cfg, seed + 1)
                    published[2] = digest(params2)
                    publish_model(ws, cfg, params2, version="v2")
                    marks["commit_s"] = time.perf_counter() - t
                    state["committed"] = 2
                return None
            if len(outputs) == 2 * N_REQUESTS:
                return STOP
            return None

        def on_epoch(change):
            t = time.perf_counter()
            lowered0 = counter.lowered
            engine.adopt_epoch(ws, app, strategy="stable")
            jax.block_until_ready(engine.params)
            marks["flip_s"] = time.perf_counter() - t
            marks["lowered_at_flip"] = lowered0
            state["serving"] = 2
            marks["gen2_t0"] = time.perf_counter()
            pending.extend(gen2)

        def on_delta(d):
            marks.setdefault("first_token", time.perf_counter())

        compiled0, compile_s0 = counter.compiled, counter.compile_s
        t0 = time.perf_counter()
        engine.serve_loop(
            source, lambda c: outputs.__setitem__(c.rid, c.tokens),
            max_batch=MAX_BATCH, max_new_cap=MAX_NEW,
            epoch_watch=ws.epoch_watch(), on_epoch=on_epoch,
            on_delta=on_delta,
        )
        end = time.perf_counter()
        failures += token_failures(cfg, outputs, range(2 * N_REQUESTS))
        if state["serving"] != 2:
            failures.append("the serve loop never flipped to generation 2")
        else:
            if digest(jax.device_get(engine.params)) != published[2]:
                failures.append(
                    "(a) device params differ from the published bundle "
                    "after the flip"
                )
            recompiled = counter.lowered - marks["lowered_at_flip"]
            if recompiled:
                failures.append(
                    f"(d) the flip and the serving after it lowered "
                    f"{recompiled} programs"
                )
            observe(
                compile_s=counter.compile_s - compile_s0,
                programs_compiled=counter.compiled - compiled0,
                first_token_s=marks["first_token"] - t0,
                gen1_tok_per_s=N_REQUESTS * MAX_NEW / (marks["gen1_done"] - t0),
                commit_s=marks["commit_s"],
                flip_s=marks["flip_s"],
                gen2_tok_per_s=N_REQUESTS * MAX_NEW / (end - marks["gen2_t0"]),
            )
    finally:
        ws.close()
    return failures


def fleet_phase(cfg, seed: int, chips: int) -> tuple[dict, list[str]]:
    """Serve one greedy request set with 1 worker, then ``chips`` workers.
    Returns the reports by worker count and the failed checks."""
    from repro.serve import run_traffic

    ws = Workspace.ephemeral("chip-smoke-fleet-")
    runs = {}
    try:
        t0 = time.perf_counter()
        app = publish_model(ws, cfg, models.init_params_np(cfg, seed))
        observe(publish_s=time.perf_counter() - t0)
        if jax_backend_initialized():
            return runs, ["the dispatcher initialised a JAX backend before "
                          "spawning its workers"]
        for n in sorted({1, chips}):
            t0 = time.perf_counter()
            runs[n] = run_traffic(
                ws, app, cfg=cfg, workers=n, n_requests=2 * N_REQUESTS,
                rate_hz=50.0, prompt_len=PROMPT_LENS[-1],
                max_new_tokens=MAX_NEW, max_batch=MAX_BATCH, seed=seed,
                timeout=900.0,
            )
            rep = runs[n]
            observe(workers=n, wall_s=time.perf_counter() - t0,
                    ready_s=rep.ready_s, tok_per_s=rep.tok_per_s)
            print(f"workers={n} devices: {json.dumps(rep.devices)}", flush=True)
    finally:
        ws.close()
    return runs, fleet_failures(cfg, runs, chips)


def fleet_failures(cfg, runs: dict, chips: int) -> list[str]:
    """Every request of both runs answered in full, and each request's
    tokens identical with one worker and with ``chips`` workers."""
    failures = []
    for n, rep in runs.items():
        if rep.failed or rep.completed != rep.sent:
            failures.append(
                f"workers={n}: {rep.completed}/{rep.sent} completed, "
                f"errors {rep.worker_errors}"
            )
        failures += [
            f"workers={n}: {f}"
            for f in token_failures(cfg, rep.outputs, range(rep.sent))
        ]
    if failures or chips == 1:
        return failures
    one, many = runs[1].outputs, runs[chips].outputs
    for rid in sorted(one):
        diff = np.flatnonzero(one[rid] != many[rid])
        if diff.size:
            failures.append(
                f"request {rid}: tokens differ between 1 and {chips} workers "
                f"from position {diff[0]}: {one[rid].tolist()} vs "
                f"{many[rid].tolist()}"
            )
            break
    return failures


def device_failures(devices: list[dict], chips: int) -> list[str]:
    """``chips`` workers on ``chips`` distinct TPU chips, one each. A chip
    is told apart by the id the runtime gives it and by the device node
    the worker's process holds open."""
    owned = {(d["hw_id"], tuple(d["files"])) for d in devices}
    if {d["platform"] for d in devices} != {"tpu"}:
        return [f"the workers did not serve on TPU: {devices}"]
    if len(devices) != chips or len(owned) != chips:
        return [f"{chips} workers did not hold {chips} distinct chips: "
                f"{devices}"]
    if any(d["count"] != 1 for d in devices):
        return [f"a worker saw more than its one chip: {devices}"]
    return []


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument(
        "--chips", type=int, default=None, metavar="N",
        help="run only the fleet phase, over N chips of this host",
    )
    args = ap.parse_args()
    cfg = get_config(ARCH)
    print(f"compile cache: {compile_cache_dir()}", flush=True)

    if args.chips is None:
        device = device_report()
        print(f"device: {json.dumps(device)}", flush=True)
        if device["platform"] != "tpu":
            print(f"FAIL: JAX found no TPU; platform is {device['platform']!r}",
                  file=sys.stderr)
            return 1
        failures = serve_phase(cfg, args.seed)
    else:
        runs, failures = fleet_phase(cfg, args.seed, args.chips)
        devices = runs[args.chips].devices if args.chips in runs else []
        failures += device_failures(devices, args.chips)
        if devices:
            device = dict(devices[0], count=len(devices))
    for f in failures:
        print(f"FAIL: {f}", file=sys.stderr)
    if failures:
        return 1
    print(json.dumps({"ok": True, "device": {
        "platform": device["platform"], "kind": device["kind"],
        "count": device["count"],
    }}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
