"""The sweep that finds a cell's knee: the highest arrival rate the chip
sustains with no growing backlog.

    python bench/sweep.py --workload <cell> --rates 8,10,30 --seeds 1,2,3 \
        --seconds 40

One process loads the cell once and serves one window per rate and seed,
with the cell's mix at that rate. Each window prints one JSON line: the
tokens offered and streamed per second, the TTFT and ITL quantiles, and
the requests due but not yet admitted at the middle and at the close of
the window (a backlog that grows between the two is past the knee). A rate
far above the knee reads the saturated throughput, which bounds the knee
from above: the knee in requests per second is at most that throughput
over the mix's mean output. The knee found is written into the mix's file
by hand; the benchmark's runs never search for a rate.
"""

from __future__ import annotations

import argparse
import json
import sys
from pathlib import Path

import numpy as np

sys.path[:0] = [str(Path(__file__).resolve().parents[1]),
                str(Path(__file__).resolve().parents[1] / "src")]

from bench import run as R  # noqa: E402


def backlog(obs, t: float) -> int:
    """Requests due by ``t`` and not yet admitted by then."""
    admitted = {r: c.admitted_ts for r, c in obs.win.completions.items()}
    return sum(1 for r, d in obs.due.items()
               if d <= t and admitted.get(r, np.inf) > t)


def reading(served, rate: float) -> dict:
    o, seconds = served.obs, served.seconds
    ttft = np.array(o.ttft_s()) * 1e3
    itl = np.array(o.itl_s()) * 1e3
    return {
        "rate_per_s": rate, "seed": served.seed,
        "offered_tok_per_s": sum(p.max_new for p in served.win.planned)
        / seconds,
        "tok_per_s": o.tokens_in_window() / seconds,
        "ttft_p50_ms": float(np.percentile(ttft, 50)),
        "ttft_p95_ms": float(np.percentile(ttft, 95)),
        "itl_p50_ms": float(np.percentile(itl, 50)),
        "itl_p95_ms": float(np.percentile(itl, 95)),
        "backlog_mid": backlog(o, o.t0 + seconds / 2),
        "backlog_close": backlog(o, o.close),
        "compiled_in_window": served.marks["compiled_in"],
    }


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--rates", required=True)
    ap.add_argument("--seconds", type=float, default=20.0)
    ap.add_argument("--seeds", default="1")
    args = ap.parse_args(argv)
    cell = R.Cell(json.loads((R.ROOT / "BENCHMARK.json").read_text()),
                  args.workload)
    jax = R.setup_jax()
    if jax.devices()[0].platform == "cpu":
        print("refused: a knee is found on the chip", file=sys.stderr)
        return R.REFUSED
    from bench.harness.session import Session

    s = Session(cell, work_dir=R.WORK_DIR)
    seeds = [int(x) for x in args.seeds.split(",")]
    for rate in [float(x) for x in args.rates.split(",")]:
        cell.mix["arrivals"]["rate_per_s"] = rate
        for seed in seeds:
            print(json.dumps(reading(s.serve(seed, args.seconds), rate)),
                  flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
