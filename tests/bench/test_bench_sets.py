"""The spread that bounds are set from: quartiles as Python's
``statistics.quantiles`` gives them, over the median."""

import statistics
import sys
from pathlib import Path

ROOT = Path(__file__).resolve().parents[2]
sys.path.insert(0, str(ROOT))

from bench import sets  # noqa: E402


def _row(s, seed, itl, setup):
    return {"set": s, "seed": seed, "result": {
        "correct": True,
        "metrics": {"itl_p95_ms": {"value": itl}, "setup_s": {"value": setup}},
    }}


def test_spread_is_the_interquartile_distance_over_the_median():
    vals = [60.0, 62.0, 63.0, 64.0, 66.0, 70.0]
    q1, _, q3 = statistics.quantiles(vals, n=4)
    med, sp = sets.spread(vals)
    assert med == 63.5 and sp == (q3 - q1) / 63.5


def test_the_compiling_first_run_is_left_out_of_setup_only():
    rows = [_row(1, i, 60.0 + i, 30.0) for i in range(6)]
    rows[0]["result"]["metrics"]["setup_s"]["value"] = 140.0
    rows += [_row(2, i, 60.0 + i, 31.0) for i in range(6)]
    out = sets.summary(rows)
    assert out["setup_s"]["set1"] == {"median": 30.0, "spread": 0.0, "n": 5}
    assert out["setup_s"]["set2"]["n"] == 6
    assert out["itl_p95_ms"]["set1"]["n"] == 6
    assert out["correct"]["set2"] == [True] * 6
