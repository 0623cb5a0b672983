"""Serving launcher: batched greedy generation at published widths.

    PYTHONPATH=src python -m repro.launch.serve --arch mamba2-370m \
        --batch 4 --prompt-len 32 --max-new 16

``--smoke`` serves the reduced (``configs.reduced``) variant instead, the
size tests and CPU runs use. Startup goes through the stable-linking
session API: the weight bundle and application are published into a
``Workspace`` (one management transaction), then every server start is an
epoch-path ``ws.load`` — pass ``--strategy`` to compare loaders by name
(any strategy registered in ``repro.link``).

``--fleet N`` additionally spawns N real worker processes that load the
same app via the ``stable-shm`` strategy, proving the whole machine shares
ONE physical arena copy (at most one worker fills the shm segment, the
rest attach); the fleet summary is included in the output JSON.

``--traffic N`` goes one step further: it spawns N serving workers wired
to the dispatcher by shm request/response rings and drives a Poisson load
(``--rate-hz``, ``--requests``) through ``engine.serve_loop`` — the
continuous-batching scheduler — reporting sustained req/s, tok/s, and
p50/p99 end-to-end latency. Worker i serves on chip i, so this process
builds no engine of its own then: a parent that touched the chip would
leave its workers none.

With ``--stream`` every generated token comes back as its own PARTIAL
frame on the response ring (the dispatcher reassembles them in order and
verifies the reassembled stream byte-for-byte against the completion
frame), and the report gains time-to-first-token quantiles. ``--temperature``
and ``--top-k`` switch decode from greedy argmax to batched sampling with
per-request PRNG keys — token i of request r depends only on
``(--sampling-seed, r, i)``, never on batch composition. ``--mpmc`` runs
the request rings in multi-producer mode (bakery-locked claim cursor).
"""

from __future__ import annotations

import argparse
import json
import tempfile

import numpy as np

from repro import models
from repro.ckpt import bundle_from_params
from repro.configs import ARCHS, get_config
from repro.core import ObjectKind, make_object
from repro.core.chips import compile_cache_dir
from repro.link import Workspace, available_strategies
from repro.serve import ServeEngine


def publish_model(ws, cfg, params, *, version: str = "v1") -> str:
    """Publish ``params`` as ``cfg``'s weight bundle ``version`` — and, the
    first time, the serving app that imports it — in one management
    transaction. Returns the app name; a later version rolls every
    server of that app to the new weights at its next epoch."""
    bundle, payload = bundle_from_params(f"weights:{cfg.name}", version, params)
    app_name = f"serve:{cfg.name}"
    first = app_name not in ws.world()
    with ws.management() as tx:
        tx.publish(bundle, payload)
        if first:
            app, _ = make_object(
                name=app_name,
                version="1",
                kind=ObjectKind.APPLICATION,
                refs=models.manifest_refs(cfg),
                needed=[bundle.name],
            )
            tx.publish(app)
    return app_name


def main() -> None:
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--arch", choices=sorted(ARCHS), required=True)
    ap.add_argument(
        "--smoke", action="store_true",
        help="serve the reduced config (tests, CPU runs) instead of the "
             "published one",
    )
    ap.add_argument("--batch", type=int, default=4)
    ap.add_argument("--prompt-len", type=int, default=32)
    ap.add_argument("--max-new", type=int, default=16)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument(
        "--strategy", default="stable", choices=available_strategies()
    )
    ap.add_argument(
        "--fleet", type=int, default=0, metavar="N",
        help="also spawn N worker processes sharing one shm arena "
             "(stable-shm) and report fills/attaches",
    )
    ap.add_argument(
        "--traffic", type=int, default=0, metavar="N",
        help="drive a Poisson request load through N serving workers "
             "connected by shm rings (continuous batching via "
             "engine.serve_loop); reports sustained req/s and p50/p99",
    )
    ap.add_argument(
        "--rate-hz", type=float, default=100.0,
        help="Poisson arrival rate for --traffic",
    )
    ap.add_argument(
        "--requests", type=int, default=32,
        help="number of requests --traffic sends",
    )
    ap.add_argument(
        "--stream", action="store_true",
        help="with --traffic: stream every token as a PARTIAL frame and "
             "report TTFT p50/p99 alongside completion latency",
    )
    ap.add_argument(
        "--temperature", type=float, default=0.0,
        help="with --traffic: sampling temperature (0 = greedy argmax)",
    )
    ap.add_argument(
        "--top-k", type=int, default=0,
        help="with --traffic: restrict sampling to the k most likely "
             "tokens (0 = full vocabulary)",
    )
    ap.add_argument(
        "--sampling-seed", type=int, default=0,
        help="with --traffic: PRNG seed for sampled decode; tokens are a "
             "pure function of (seed, request id, position)",
    )
    ap.add_argument(
        "--mpmc", action="store_true",
        help="with --traffic: run request rings in multi-producer mode",
    )
    ap.add_argument("--registry", default=None)
    args = ap.parse_args()

    cfg = get_config(args.arch, smoke=args.smoke)
    cache_dir = compile_cache_dir()
    ws = Workspace.open(
        args.registry or tempfile.mkdtemp(prefix="repro-serve-")
    )
    app_name = f"serve:{cfg.name}"
    if app_name not in ws.world():
        publish_model(ws, cfg, models.init_params_np(cfg, args.seed))

    payload = {
        "arch": cfg.name,
        "epoch": ws.epoch,
        "compile_cache_dir": cache_dir,
    }
    if not args.traffic:
        # Replica spin-up through the epoch-resident path: params load via
        # the process-wide EpochCache, so same-process replicas share one
        # mapping.
        engine = ServeEngine.from_workspace(
            cfg,
            ws,
            app_name,
            strategy=args.strategy,
            cache_len=args.prompt_len + args.max_new,
        )
        rng = np.random.default_rng(args.seed)
        prompts = rng.integers(
            0, cfg.vocab_size, (args.batch, args.prompt_len), dtype=np.int32
        )
        out, stats = engine.generate(prompts, args.max_new)
        payload.update(
            load_strategy=engine.load_stats.strategy,
            load_s=round(engine.load_stats.startup_s, 4),
            load_cache_hit=engine.load_stats.cache_hit,
            out_shape=list(out.shape),
            prefill_s=round(stats.prefill_s, 4),
            decode_s=round(stats.decode_s, 4),
            tok_per_s=round(stats.tok_per_s, 1),
            sample=out[0, :8].tolist(),
        )
    if args.fleet:
        # True multi-process fleet: every replica attaches to the one shm
        # segment the first loader published (load-only probes; pass
        # cfg=cfg to ServeEngine.spawn_fleet for full replicas).
        report = ServeEngine.spawn_fleet(
            ws, app_name, processes=args.fleet, strategy="stable-shm"
        )
        payload["fleet"] = report.summary()
    if args.traffic:
        # The full traffic plane: dispatcher + N ring-connected serving
        # workers under a Poisson load (repro.serve.traffic).
        from repro.serve import run_traffic

        rep = run_traffic(
            ws,
            app_name,
            cfg=cfg,
            workers=args.traffic,
            n_requests=args.requests,
            rate_hz=args.rate_hz,
            prompt_len=args.prompt_len,
            max_new_tokens=args.max_new,
            max_batch=args.batch,
            stream=args.stream,
            temperature=args.temperature,
            top_k=args.top_k,
            sampling_seed=args.sampling_seed,
            mpmc=args.mpmc,
        )
        payload["traffic"] = rep.summary()
    if args.registry is None:
        # throwaway registry: any stable-shm load (single engine OR fleet)
        # published machine-wide segments nothing will ever reattach — a
        # persistent --registry keeps them instead (the warm machine)
        from repro.core import shm_arena

        shm_arena.unlink_root_segments(ws.registry)
    print(json.dumps(payload, indent=1))


if __name__ == "__main__":
    main()
