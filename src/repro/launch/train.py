"""Training launcher.

    PYTHONPATH=src python -m repro.launch.train --arch gemma3-1b --smoke \
        --steps 20 --checkpoint-every 10

``--smoke`` selects the reduced config (CPU-runnable); full configs need a
real fleet and are exercised via the dry-run. The registry directory is the
stable linker's store; rerunning with the same --registry resumes from the
newest checkpoint through the epoch (table-driven) path.
"""

from __future__ import annotations

import argparse
import json
import tempfile

from repro.configs import ARCHS, ShapeConfig, get_config
from repro.core.chips import compile_cache_dir
from repro.launch.mesh import make_local_mesh, mesh_from_spec
from repro.optim import OptConfig
from repro.train import TrainConfig, Trainer


def main() -> None:
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--arch", choices=sorted(ARCHS), required=True)
    ap.add_argument("--smoke", action="store_true", default=True)
    ap.add_argument("--no-smoke", dest="smoke", action="store_false")
    ap.add_argument("--steps", type=int, default=20)
    ap.add_argument("--checkpoint-every", type=int, default=10)
    ap.add_argument("--batch", type=int, default=8)
    ap.add_argument("--seq", type=int, default=64)
    ap.add_argument("--microbatches", type=int, default=1)
    ap.add_argument("--mesh", default="local")
    ap.add_argument("--registry", default=None)
    ap.add_argument("--lr", type=float, default=1e-3)
    ap.add_argument("--fail-at-step", type=int, default=-1)
    args = ap.parse_args()

    compile_cache_dir()
    cfg = get_config(args.arch, smoke=args.smoke)
    shape = ShapeConfig("cli", args.seq, args.batch, "train")
    mesh = mesh_from_spec(args.mesh) if args.mesh != "local" else make_local_mesh()
    registry = args.registry or tempfile.mkdtemp(prefix="repro-registry-")
    tcfg = TrainConfig(
        steps=args.steps,
        checkpoint_every=args.checkpoint_every,
        microbatches=args.microbatches,
        fail_at_step=args.fail_at_step,
        opt=OptConfig(peak_lr=args.lr, warmup_steps=5, decay_steps=args.steps),
    )
    tr = Trainer(registry, cfg, shape, mesh, tcfg)
    if tr.app_name not in tr.ws.world():
        tr.publish()
    res = tr.run()
    print(
        json.dumps(
            {
                "arch": cfg.name,
                "registry": registry,
                "steps": res.steps_done,
                "restarts": res.restarts,
                "checkpoint_saves": res.checkpoint_saves,
                "first_loss": res.losses[0] if res.losses else None,
                "last_loss": res.losses[-1] if res.losses else None,
                "startups": res.startup_stats,
            },
            indent=1,
        )
    )


if __name__ == "__main__":
    main()
