"""Publish once per checkout, load on every run.

Stable linking exists so that an epoch is published once and every later
execution loads it. The benchmark does the same: a configuration's
workspace lives at a fixed path inside the checkout, is published by the
first run that finds it missing, and is only loaded after that. A marker
written after the commit names the weights it holds; a workspace without
it, or with another, is published again from scratch. Once loaded, the
params on the device are compared bit for bit with the weights drawn from
the seed (``weights.leaves_differing``): a workspace that loads wrong is
published again too, and never served.
"""

from __future__ import annotations

import json
import shutil
import time
from pathlib import Path

import jax

from bench.harness import weights

MARKER = "bench-published.json"


def _wanted(c: dict, layout: dict) -> dict:
    return {
        "weight_seed": c["weight_seed"],
        "layout": {n: [list(s[0]), s[1], s[2], s[3]]
                   for n, s in sorted(layout.items())},
    }


def open_published(root: Path, c: dict, layout: dict, prog_cfg, *,
                   force: bool = False):
    """(workspace, app name, seconds spent publishing)."""
    from repro.launch.serve import publish_model
    from repro.link import Workspace

    marker = root / MARKER
    want = _wanted(c, layout)
    if not force and marker.is_file():
        got = json.loads(marker.read_text())
        if {k: got.get(k) for k in want} == want:
            return Workspace.open(root, bake_arenas=False), got["app"], 0.0
    t0 = time.monotonic()
    shutil.rmtree(root, ignore_errors=True)
    root.mkdir(parents=True)
    params = jax.device_get(weights.generate(layout, c["weight_seed"]))
    ws = Workspace.open(root, bake_arenas=False)
    app = publish_model(ws, prog_cfg, params)
    del params
    marker.write_text(json.dumps(dict(want, app=app)))
    return ws, app, time.monotonic() - t0


def load_engine(root: Path, c: dict, layout: dict, prog_cfg, cache_len: int):
    """Load and lift the published epoch; publish again where it is missing
    or loads wrong. Returns the engine and the set-up's readings, among them
    the seconds the bit-for-bit check took, which are correctness work and
    not set-up."""
    from repro.serve import ServeEngine

    check_s = 0.0
    for attempt in range(2):
        ws, app, publish_s = open_published(root, c, layout, prog_cfg,
                                            force=attempt > 0)
        t0 = time.monotonic()
        engine = ServeEngine.from_workspace(
            prog_cfg, ws, app, strategy="stable", cache_len=cache_len
        )
        jax.block_until_ready(engine.params)
        load_s = time.monotonic() - t0
        t1 = time.monotonic()
        differing = weights.leaves_differing(
            engine.params, layout, c["weight_seed"]
        )
        check_s += time.monotonic() - t1
        if not differing:
            break
        print(f"workspace at {root} loaded {len(differing)} leaves that "
              f"differ from the seed's weights ({differing[:3]})", flush=True)
        if attempt == 0:
            del engine
    startup_s = engine.load_stats.startup_s
    return engine, {
        "publish_s": publish_s,
        "epoch_load_s": startup_s,
        "lift_s": load_s - startup_s,
        "leaves_differing": len(differing),
        "param_check_s": check_s,
    }
