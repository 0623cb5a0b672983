"""Sets of benchmark runs of one cell, each a process of its own, as a
check makes them, and the spread that a bound is set from.

    python bench/sets.py --workload <cell> --seeds 1,2,3,4,5,6 --sets 2 \\
        --seconds 51 [--trace-seeds 7,8,9] [--out runs.jsonl]

Runs ``bench/run.py`` once per seed in each set (the same seeds in every
set), then once with ``--trace 1`` per trace seed. This process never
touches JAX, so each run holds the chip alone. Every run's result line, its
exit code, its wall time and the end of its standard error go to ``--out``
as one JSON line. The summary gives, for each set and each metric, the
median and the spread: the distance between the first and the third
quartile (``statistics.quantiles(values, n=4)``) as a share of the median;
the first run of the first set, which compiles, is left out of ``setup_s``.
"""

from __future__ import annotations

import argparse
import json
import statistics
import subprocess
import sys
import time
from pathlib import Path

ROOT = Path(__file__).resolve().parents[1]
RUN_LIMIT_S = 1500          # a first run in a checkout publishes and compiles


def one(workload: str, seed: int, seconds: float, trace: int) -> dict:
    t0 = time.monotonic()
    p = subprocess.run(
        [sys.executable, str(ROOT / "bench" / "run.py"), "--workload",
         workload, "--seed", str(seed), "--seconds", str(seconds),
         "--trace", str(trace)],
        cwd=ROOT, capture_output=True, text=True, timeout=RUN_LIMIT_S,
    )
    lines = p.stdout.strip().splitlines()
    try:
        result = json.loads(lines[-1]) if lines else None
    except json.JSONDecodeError:
        result = None
    return {"seed": seed, "trace": trace, "rc": p.returncode,
            "wall_s": time.monotonic() - t0, "result": result,
            "lines": lines[:-1][-8:], "stderr_tail": p.stderr[-2000:]}


def spread(values: list[float]) -> tuple[float, float]:
    """(median, interquartile distance over the median)."""
    med = statistics.median(values)
    q1, _, q3 = statistics.quantiles(values, n=4)
    return med, (q3 - q1) / med


def summary(rows: list[dict]) -> dict:
    out: dict = {}
    for s in sorted({r["set"] for r in rows}):
        runs = [r for r in rows if r["set"] == s and r["result"]]
        names = {n for r in runs for n in r["result"]["metrics"]}
        for name in sorted(names):
            vals = [r["result"]["metrics"][name]["value"] for r in runs
                    if name in r["result"]["metrics"]]
            if name == "setup_s" and s == 1:
                vals = vals[1:]          # the first run compiles
            if len(vals) >= 2:
                med, sp = spread(vals)
                out.setdefault(name, {})[f"set{s}"] = {
                    "median": med, "spread": sp, "n": len(vals)}
        out.setdefault("correct", {})[f"set{s}"] = [
            r["result"]["correct"] for r in runs]
    return out


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--sets", type=int, default=2)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace-seeds", default="")
    ap.add_argument("--out", default="")
    args = ap.parse_args(argv)
    seeds = [int(x) for x in args.seeds.split(",")]
    plan = [(s, seed, 0) for s in range(1, args.sets + 1) for seed in seeds]
    plan += [(0, int(x), 1) for x in args.trace_seeds.split(",") if x]
    rows = []
    sink = open(args.out, "a") if args.out else None
    for s, seed, trace in plan:
        row = dict(set=s, **one(args.workload, seed, args.seconds, trace))
        rows.append(row)
        res = row["result"] or {}
        print(json.dumps({k: row[k] for k in ("set", "seed", "trace", "rc",
                                              "wall_s")}
                         | {"correct": res.get("correct"),
                            "metrics": {k: v["value"] for k, v in
                                        res.get("metrics", {}).items()},
                            "checks": res.get("checks")}), flush=True)
        if sink:
            sink.write(json.dumps(row) + "\n")
            sink.flush()
    print(json.dumps({"summary": summary([r for r in rows if r["set"]])}),
          flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
