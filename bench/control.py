"""Readings that set a cell's limits: the program's and the control's.

    python bench/control.py --workload <cell> --seeds 1,2,3 --seconds 10

One process loads the cell once and, for each seed, serves a window at the
cell's own load, then judges what it served against the float32 reference
and the cell's limits, as a benchmark run does (the program's verdict),
and judges the float8 control put in the program's place against the same
limits (the control's verdict, which has to be not correct). The lower
reading of ``logit_gap`` is the largest program reading over a dozen seeds
or more; the upper is the smallest control reading. Each seed prints one
JSON line, and the last lines on standard error give every number compared
beside its limit; the benchmark's own runs never run the control.
"""

from __future__ import annotations

import argparse
import json
import sys
from pathlib import Path

sys.path[:0] = [str(Path(__file__).resolve().parents[1]),
                str(Path(__file__).resolve().parents[1] / "src")]

from bench import run as R  # noqa: E402


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--seconds", type=float, default=10.0)
    args = ap.parse_args(argv)
    cell = R.Cell(json.loads((R.ROOT / "BENCHMARK.json").read_text()),
                  args.workload)
    jax = R.setup_jax()
    if jax.devices()[0].platform == "cpu":
        print("refused: the control's readings come from the chip",
              file=sys.stderr)
        return R.REFUSED
    from bench.harness.check import judge
    from bench.harness.session import Session

    s = Session(cell, work_dir=R.WORK_DIR)
    last = []
    for seed in [int(x) for x in args.seeds.split(",")]:
        served = s.serve(seed, args.seconds)
        sides, extra, failures = s.check(served, control=True)
        row = {"seed": seed}
        for side, readings in sides.items():
            checks, correct = judge(readings, cell.limits)
            row[side] = {"correct": correct, "checks": checks,
                         "readings": readings}
        row.update(extra, compiled_in_window=served.marks["compiled_in"],
                   failures=failures[:3])
        print(json.dumps(row), flush=True)
        last = [f"seed {seed} {side} correct {row[side]['correct']}: "
                + ", ".join(f"{k} {v['value']!r} (limit {v['limit']!r})"
                            for k, v in row[side]["checks"].items())
                for side in sides]
    for line in last:
        print(line, file=sys.stderr)
    return 0


if __name__ == "__main__":
    sys.exit(main())
