"""Benchmark of stable-linked serving on one accelerator chip.

    python bench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

Each cell of ``BENCHMARK.json`` names a model configuration
(``bench/configs/<config>.json``) and a traffic mix
(``bench/traffic/<traffic>.json``). One process holds the chip and drives
the program's own serving path:

1. set-up: the configuration's weights, drawn from its weight seed, are
   published into a workspace at a fixed path in the checkout the first
   time (``bench/harness/publish.py``); every run loads that epoch with
   ``ServeEngine.from_workspace(strategy="stable")`` and checks the params
   on the device bit for bit against the seed's weights (timed apart: the
   check is correctness work, not counted in ``setup_s``);
2. one ``engine.serve_loop`` carries the run (``bench/harness/window.py``):
   warm-up requests compile every prompt length of the mix's grid and pass
   through every slot, then the window offers the seeded open-loop schedule
   for ``--seconds``, then the loop drains;
3. after the window, with the program's state freed, a sample of the
   served requests is compared with the family's float32 reference
   (``bench/harness/check.py``) under the cell's limits
   (``bench/limits/<cell>.json``).

With ``--trace 0`` the last line of standard output is the result with the
cell's end-to-end metrics; with ``--trace 1`` the profiler traces the last
seconds of the window and the result carries the per-layer metrics, each
read by ``bench/metrics/<metric>.py``. Without an accelerator, or with
fewer chips than the cell asks for, the run exits non-zero and prints no
result.
"""

from __future__ import annotations

import time

T_START = time.monotonic()

import argparse  # noqa: E402
import importlib.util  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402
from pathlib import Path  # noqa: E402

ROOT = Path(__file__).resolve().parents[1]
BENCH = ROOT / "bench"
CACHE_DIR = BENCH / ".cache" / "jax"
WORK_DIR = BENCH / ".work"
REFUSED = 3                      # exit code: no accelerator, or too few chips


class Cell:
    """One workload of ``BENCHMARK.json`` with everything it names."""

    def __init__(self, spec: dict, name: str, root: Path = ROOT):
        cells = {w["name"]: w for w in spec["workloads"]}
        if name not in cells:
            raise SystemExit(f"no workload {name!r} in BENCHMARK.json; "
                             f"there are {sorted(cells)}")
        self.w = cells[name]
        self.name = name
        cfgs = {c["name"]: c for c in spec["configs"]}
        self.c = json.loads((root / cfgs[self.w["config"]]["file"]).read_text())
        self.mix = json.loads(
            (root / "bench" / "traffic" / f"{self.w['traffic']}.json").read_text()
        )
        check = json.loads(
            (root / "bench" / "limits" / f"{name}.json").read_text()
        )
        self.sample = check["sample"]       # how many answers are compared
        self.limits = check["compare"]      # number -> its limit
        self.metrics = {      # by --trace: end-to-end, or per-layer
            False: [m for m in spec["end_to_end"] if _reports(m, name)],
            True: [m for m in spec["per_layer"] if _reports(m, name)],
        }


def _reports(metric: dict, cell: str) -> bool:
    return "workloads" not in metric or cell in metric["workloads"]


def setup_jax():
    """The persistent compilation cache at a fixed path in the checkout,
    keeping every program, however quick its compile."""
    CACHE_DIR.mkdir(parents=True, exist_ok=True)
    os.environ["JAX_COMPILATION_CACHE_DIR"] = str(CACHE_DIR)
    import jax

    jax.config.update("jax_compilation_cache_dir", str(CACHE_DIR))
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
    jax.config.update("jax_compilation_cache_max_size", -1)   # no eviction
    return jax


def device_row(jax) -> dict:
    d = jax.devices()[0]
    stats = d.memory_stats() or {}
    return {
        "platform": d.platform,
        "kind": d.device_kind,
        "count": len(jax.devices()),
        "memory_peak_bytes": stats.get("peak_bytes_in_use"),
    }


def run_cell(cell: Cell, seed: int, seconds: float, trace: bool, *,
             peaks: dict, work_dir: Path = WORK_DIR,
             control: bool = False) -> dict:
    """Set up, serve the window, check, reduce. Returns the result, the
    lines to print before it, and readings that are not compared; with
    ``control``, also the float8 control's verdict, judged in the program's
    place by the same limits."""
    import jax
    import numpy as np

    from bench.harness.check import judge
    from bench.harness.session import Session
    from bench.harness.trace import Trace
    from bench.harness.window import p95

    s = Session(cell, work_dir=work_dir)
    lines: list[str] = []
    if s.load["publish_s"]:
        lines.append(f"published {cell.c['name']} in {s.load['publish_s']!r} s")
    lines.append("params on the device checked bit for bit against the "
                 f"seed's weights in {s.load['param_check_s']!r} s "
                 "(not counted in setup_s)")
    served = s.serve(seed, seconds, trace)
    device = device_row(jax)
    lines += served.lines()
    obs = served.obs

    ttft = obs.ttft_s()
    itl = p95(obs.itl_s())
    e2e = {
        "tok_per_s": obs.tokens_in_window() / seconds,
        "ttft_p95_ms": p95(ttft) * 1e3,
        "itl_p95_ms": None if itl is None else itl * 1e3,
        "setup_s": served.marks["open"] - T_START - s.load["param_check_s"],
    }
    lines.append(
        "requests: {} due in the window, ttft p50 {!r} ms, p95 {!r} ms, "
        "itl p95 {!r} ms, {} tokens streamed in it".format(
            len(ttft), float(np.percentile(ttft, 50)) * 1e3,
            p95(ttft) * 1e3, e2e["itl_p95_ms"], obs.tokens_in_window())
    )

    tr = None
    if trace and served.tracer.path():
        tr = Trace.load(served.tracer.path(), served.tracer.started_mono)
        served.tracer.cleanup()
    chosen = cell.metrics[trace]
    if trace:
        run = RunData(obs=obs, trace=tr, family=s.fam, c=cell.c, peaks=peaks,
                      load=s.load)
        values = {m["name"]: _reader(m["name"])(run) for m in chosen}
        device["busy_s"] = tr.busy_s() if tr else 0.0
        device["window_s"] = tr.window_s if tr else 0.0
    else:
        values = e2e
    metrics = {
        m["name"]: {"value": values[m["name"]], "unit": m["unit"]}
        for m in chosen
        if values.get(m["name"]) is not None and np.isfinite(values[m["name"]])
    }

    s.free()
    sides, extra, failures = s.check(served, control=control)
    lines.append(f"check: {extra['requests_checked']} requests, "
                 f"{extra.get('served_tokens_checked', 0)} served tokens, "
                 f"reference took {extra['check_s']!r} s")
    lines += [f"answer: {f}" for f in failures[:5]]
    lines.append("readings not compared: " + (", ".join(
        f"{k} {v!r}" for k, v in sides["program"].items()
        if k not in cell.limits) or "none"))
    checks, correct = judge(sides["program"], cell.limits)
    out = {
        "correct": correct,
        "attempted": len(served.win.planned),
        "failed": len(failures),
        "metrics": metrics,
        "device": device,
    }
    if tr is not None:
        out["breakdown"] = tr.breakdown()
    out["checks"] = checks
    ret = {"result": out, "lines": lines, "extra": extra}
    if control:
        checks, correct = judge(sides["control"], cell.limits)
        ret["control"] = {"correct": correct, "checks": checks}
    return ret


class RunData:
    """What a per-layer metric's reader is given."""

    def __init__(self, **kw):
        self.__dict__.update(kw)


def _reader(name: str):
    path = BENCH / "metrics" / f"{name}.py"
    spec = importlib.util.spec_from_file_location(f"bench_metric_{name}", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod.read


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    sys.path[:0] = [str(ROOT), str(ROOT / "src")]
    spec = json.loads((ROOT / "BENCHMARK.json").read_text())
    cell = Cell(spec, args.workload)

    jax = setup_jax()
    from bench.harness.peaks import peaks

    devices = jax.devices()
    if devices[0].platform == "cpu" or len(devices) < cell.w["chips"]:
        print(f"refused: JAX found {len(devices)} {devices[0].platform} "
              f"device(s); {args.workload} needs {cell.w['chips']} "
              "accelerator chip(s)", file=sys.stderr)
        return REFUSED
    out = run_cell(cell, args.seed, args.seconds, bool(args.trace),
                   peaks=peaks(devices[0].device_kind))
    for line in out["lines"]:
        print(line, flush=True)
    checks = out["result"]["checks"]
    for name, v in checks.items():
        print(f"check {name}: {v['value']!r} (limit {v['limit']!r})",
              file=sys.stderr)
    print(json.dumps(out["result"]), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
