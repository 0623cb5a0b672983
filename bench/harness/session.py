"""One configuration on the chip: its engine, its windows and its checks.

``Session`` loads the cell's published epoch once; ``serve`` runs one
serve loop through a window (``window.py``) and ``check`` compares what
that window served with the reference (``check.py``). ``bench/run.py``
makes one window per process; the control and the sweep make several in
one process, to pay the set-up once.
"""

from __future__ import annotations

import gc
import importlib
import json
import time
from dataclasses import dataclass

import jax

from bench.harness import check, traffic
from bench.harness.publish import load_engine
from bench.harness.trace import Tracer
from bench.harness.window import CompileCounter, Observed, Window


@dataclass
class Served:
    seed: int
    seconds: float
    win: Window
    obs: Observed
    report: object
    marks: dict
    tracer: Tracer | None

    def lines(self) -> list[str]:
        m = self.marks
        return [
            f"window: {m['lowered_in']} programs lowered and "
            f"{m['compiled_in']} compiled inside it (there should be none); "
            f"serve loop: {json.dumps(self.report.summary())}"
        ]


class Session:
    def __init__(self, cell, *, work_dir):
        from repro import models
        from repro.configs import get_config

        self.cell = cell
        c = self.c = cell.c
        self.fam = importlib.import_module(f"bench.families.{c['family']}")
        self.layout = self.fam.layout(c)
        self.prog_cfg = get_config(c["program_arch"]).replace(
            **self.fam.program_overrides(c))
        specs = {n: (tuple(s.shape), s.dtype)
                 for n, s in models.param_specs(self.prog_cfg).items()}
        mine = {n: (tuple(s[0]), s[1]) for n, s in self.layout.items()}
        if specs != mine:
            raise SystemExit(
                f"{c['name']}: the program's parameters "
                f"{sorted(set(specs.items()) ^ set(mine.items()))[:4]} "
                "differ from the configuration's layout")
        self.slots = c["serving"]["max_batch"]
        self.vocab = self.fam.sizes(c)["vocab"]      # rows the head scores
        self.tokens = self.fam.sizes(c)["tokens"]    # what prompts draw from
        self.engine, self.load = load_engine(
            work_dir / c["name"], c, self.layout, self.prog_cfg,
            c["serving"]["cache_len"])
        self.counter = CompileCounter()
        jax.monitoring.register_event_duration_secs_listener(self.counter)
        self._ref = None

    @staticmethod
    def sampling_seed(seed: int) -> int:
        return seed % 2147483647

    def serve(self, seed: int, seconds: float, trace: bool = False) -> Served:
        mix, counter = self.cell.mix, self.counter
        marks: dict = {}
        tracer = Tracer() if trace else None
        win = Window(
            traffic.warmup(mix, self.slots, self.tokens),
            traffic.schedule(mix, seed, seconds, self.tokens),
            seconds, tracer=tracer, annotate=trace,
            on_open=lambda now: marks.update(
                open=now, lowered=counter.lowered, compiled=counter.compiled),
            on_close=lambda now: marks.update(
                lowered_in=counter.lowered - marks["lowered"],
                compiled_in=counter.compiled - marks["compiled"]),
        )
        sampling = mix["sampling"]
        report = self.engine.serve_loop(
            win.source, win.sink, on_delta=win.on_delta,
            max_batch=self.slots, max_queue=self.slots,
            max_new_cap=traffic.max_new_cap(mix),
            temperature=sampling["temperature"], top_k=sampling["top_k"],
            sampling_seed=self.sampling_seed(seed),
        )
        return Served(seed, seconds, win, Observed(win, self.slots), report,
                      marks, tracer)

    def free(self) -> None:
        """Drop the program's state, so that the reference runs beside
        nothing the window left on the device."""
        self.engine = None
        gc.collect()

    def reference(self) -> check.Reference:
        if self._ref is None:
            mix = self.cell.mix
            cap = traffic.max_new_cap(mix)
            length = -(-(max(traffic.grid(mix["prompt_len"])) + cap) // 256) * 256
            self._ref = check.Reference(
                self.fam, self.c, self.layout, self.c["weight_seed"],
                batch=check.BATCH, length=length, n_pos=cap,
                vocab=self.vocab)
        return self._ref

    def check(self, served: Served, *, control: bool = False):
        """(readings compared with the limits, by side; other readings; the
        malformed answers). The side ``program`` reads what the window
        served. With ``control``, the side ``control`` puts the float8
        control in the program's place: the same answers and params, with
        the token the control ranks first at each served position."""
        t0 = time.monotonic()
        win = served.win
        failures = served.obs.answer_failures(self.vocab)
        readings = {
            "param_leaves_differing": self.load["leaves_differing"],
            "requests_unanswered_or_malformed": len(failures),
            "logit_gap": float("inf"),
            "mean_logit_gap": float("inf"),
        }
        sides = {"program": readings}
        if control:
            sides["control"] = dict(readings)
        extra: dict = {}
        picked = check.sample(win.planned, win.completions, served.seed,
                              **self.cell.sample)
        if picked:
            seqs = [(p.prompt, win.completions[p.rid].tokens) for p in picked]
            g = check.gaps(
                self.reference(), seqs, [p.rid for p in picked],
                sampling=self.cell.mix["sampling"],
                sampling_seed=self.sampling_seed(served.seed),
                dtype=self.c["dtype"], control=control)
            readings["logit_gap"] = float(g["served"].max())
            readings["mean_logit_gap"] = g["served_mean"]
            if control:
                sides["control"]["logit_gap"] = float(g["control"].max())
                sides["control"]["mean_logit_gap"] = g["control_mean"]
            extra["served_tokens_checked"] = int(sum(len(s) for _, s in seqs))
            extra["widest_by_request"] = [
                {"rid": p.rid, "prompt": len(p.prompt), "served": len(out),
                 "gap": float(gap), "at": int(at)}
                for p, (_, out), gap, at in zip(picked, seqs, g["served"],
                                                 g["served_at"])]
        extra["requests_checked"] = len(picked)
        extra["check_s"] = time.monotonic() - t0
        return sides, extra, failures
