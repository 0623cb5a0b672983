"""The training driver: stable-linked job startup + fault-tolerant loop.

Lifecycle (maps 1:1 onto the paper's Figure 4):

1. management time — register the application (its SymbolRefs come from the
   model's param specs), the initial weight bundle, and an empty optimizer
   bundle; ``end_mgmt`` materializes relocation tables.
2. epoch — every (re)start loads params AND optimizer state through the
   relocation table (Executor strategy="stable"), device_puts them with the
   mesh shardings, fetches the AOT executable from the compile cache, and
   trains. Optimizer symbols are WEAK references: they resolve to
   RelocType.INIT (zeros — the correct Adam init) before the first
   checkpoint and to DIRECT bindings afterwards, so restart-resume and
   cold-start are the same code path.
3. checkpoints are management-time events (ckpt.Checkpointer, async): they
   publish new bundles and re-materialize, so recovery after a failure is
   an epoch-path (fast) startup from the newest world. The resume step is
   read from the restored ``opt/step`` tensor — no sidecar metadata.

Fault tolerance: injectable failure (tests), per-step deadline -> straggler
counter, elastic rescale = management event with a new mesh (tables are
world-keyed, re-materialization is automatic).
"""

from __future__ import annotations

import time
from dataclasses import dataclass, field
from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np

from repro import models
from repro.ckpt import Checkpointer, bundle_from_params
from repro.core import ObjectKind, SymbolRef, cache_key, make_object
from repro.data import Prefetcher, SyntheticTokens
from repro.launch.steps import build_step
from repro.link import Workspace
from repro.optim import OptConfig


@dataclass
class TrainConfig:
    steps: int = 20
    checkpoint_every: int = 10
    microbatches: int = 1
    seed: int = 0
    impl: str = "chunked"
    step_deadline_s: float = 0.0       # 0 = no straggler detection
    fail_at_step: int = -1             # failure injection (tests)
    opt: OptConfig = field(default_factory=OptConfig)


@dataclass
class TrainResult:
    losses: list
    steps_done: int
    restarts: int
    stragglers: int
    startup_stats: list
    checkpoint_saves: int


def _opt_refs(cfg) -> list[SymbolRef]:
    refs = []
    for name, s in models.param_specs(cfg).items():
        refs.append(SymbolRef(f"opt/m/{name}", tuple(s.shape), "float32", weak=True))
        refs.append(SymbolRef(f"opt/v/{name}", tuple(s.shape), "float32", weak=True))
    refs.append(SymbolRef("opt/step", (1,), "int32", weak=True))
    return refs


class Trainer:
    def __init__(self, registry_root, cfg, shape, mesh, tcfg: TrainConfig):
        self.ws = Workspace.open(registry_root)
        # engine-room views of the workspace (Checkpointer + tests use them)
        self.registry = self.ws.registry
        self.manager = self.ws.manager
        self.executor = self.ws.executor
        self.compile_cache = self.ws.compile_cache
        self.cfg = cfg
        self.shape = shape
        self.mesh = mesh
        self.tcfg = tcfg
        self.app_name = f"train:{cfg.name}:{shape.name}"
        self.weights_name = f"weights:{cfg.name}"
        self.opt_name = f"opt:{cfg.name}"
        self.ckpt = Checkpointer(self.manager, self.weights_name, self.opt_name)

    # ------------------------------------------------------------- publish
    def publish(self, params_np: Optional[dict] = None) -> None:
        """Initial management time: app + bundles, one transaction."""
        if params_np is None:
            params_np = {
                n: np.asarray(v)
                for n, v in models.init_params(self.cfg, self.tcfg.seed).items()
            }
        wobj, wpl = bundle_from_params(
            self.weights_name, "init", params_np, meta={"step": 0}
        )
        oobj, opl = bundle_from_params(self.opt_name, "init", {}, meta={})
        app, _ = make_object(
            name=self.app_name,
            version="1",
            kind=ObjectKind.APPLICATION,
            refs=list(models.manifest_refs(self.cfg)) + _opt_refs(self.cfg),
            needed=[self.weights_name, self.opt_name],
            meta={"arch": self.cfg.name, "shape": self.shape.name},
        )
        with self.ws.management() as tx:
            tx.publish(wobj, wpl)
            tx.publish(oobj, opl)
            tx.publish(app)

    # --------------------------------------------------------------- start
    def _startup(self):
        """Epoch-path startup: table-driven load + AOT-compile cache."""
        t0 = time.perf_counter()
        image = self.ws.load(self.app_name, strategy="stable")
        bundle = build_step(
            self.cfg,
            self.shape,
            self.mesh,
            opt_cfg=self.tcfg.opt,
            num_microbatches=self.tcfg.microbatches,
            impl=self.tcfg.impl,
        )
        p_sh = bundle.shardings["params"]
        o_sh = bundle.shardings["opt"]
        params = {}
        m_state, v_state = {}, {}
        for n in models.param_specs(self.cfg):
            params[n] = jax.device_put(image[n], p_sh[n])
            m_state[n] = jax.device_put(image[f"opt/m/{n}"], o_sh["m"][n])
            v_state[n] = jax.device_put(image[f"opt/v/{n}"], o_sh["v"][n])
        step0 = int(np.asarray(image["opt/step"]).reshape(()))
        opt_state = {
            "m": m_state,
            "v": v_state,
            "step": jax.device_put(jnp.int32(step0), o_sh["step"]),
        }
        # Key is PROGRAM identity only (arch/shape/mesh/microbatching) — the
        # executable contains no weight values, exactly as relocation tables
        # contain no addresses (the ASLR-compatibility analogue), so world
        # updates (checkpoints!) never invalidate it.
        key = cache_key(
            self.cfg.name,
            self.shape.name,
            "x".join(map(str, self.mesh.devices.shape)),
            f"mb{self.tcfg.microbatches}",
            self.tcfg.impl,
        )
        with self.mesh:
            step_exe, cstats = self.compile_cache.get_or_compile(
                key, lambda: bundle.jitted.lower(*bundle.args)
            )
        startup = {
            "strategy": image.stats.strategy,
            "load_s": image.stats.startup_s,
            "compile_source": cstats.source,
            "compile_cache_error": cstats.cache_error,
            "total_s": time.perf_counter() - t0,
            "resume_step": step0,
        }
        return params, opt_state, step_exe, step0, startup

    # ----------------------------------------------------------------- run
    def run(self) -> TrainResult:
        tcfg = self.tcfg
        losses: list[float] = []
        restarts = 0
        stragglers = 0
        startup_stats = []
        failed_once = tcfg.fail_at_step < 0
        done = False

        while not done:
            params, opt_state, step_exe, step, startup = self._startup()
            startup_stats.append(startup)
            data = SyntheticTokens(
                vocab_size=self.cfg.vocab_size,
                global_batch=self.shape.global_batch,
                seq_len=self.shape.seq_len,
                seed=tcfg.seed,
                start_step=step,
                with_frames=self.cfg.d_model if self.cfg.is_encdec else 0,
            )
            it = Prefetcher(data, depth=2)
            try:
                for batch in it:
                    if step >= tcfg.steps:
                        done = True
                        break
                    if step == tcfg.fail_at_step and not failed_once:
                        failed_once = True
                        raise RuntimeError("injected node failure")
                    t0 = time.perf_counter()
                    with self.mesh:
                        params, opt_state, metrics = step_exe(
                            params, opt_state, batch
                        )
                    losses.append(float(metrics["loss"]))
                    if (
                        tcfg.step_deadline_s
                        and time.perf_counter() - t0 > tcfg.step_deadline_s
                    ):
                        stragglers += 1
                    step += 1
                    if step % tcfg.checkpoint_every == 0:
                        self.ckpt.save(step, params, opt_state)
                else:
                    done = True
            except RuntimeError:
                restarts += 1
                self.ckpt.wait()   # recovery: epoch path from newest world
                continue
        self.ckpt.wait()
        return TrainResult(
            losses=losses,
            steps_done=step,
            restarts=restarts,
            stragglers=stragglers,
            startup_stats=startup_stats,
            checkpoint_saves=self.ckpt.saves,
        )
