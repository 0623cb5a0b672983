"""The traffic generator is a pure function of the mix and the seed."""

import json
import sys
from pathlib import Path

import numpy as np
import pytest

ROOT = Path(__file__).resolve().parents[2]
sys.path.insert(0, str(ROOT))

from bench.harness import traffic  # noqa: E402

MIXES = sorted(p.stem for p in (ROOT / "bench" / "traffic").glob("*.json"))
SEEDS = (0, 12345, 2**31 + 77, 2**33 + 5)


def _mix(name):
    return json.loads((ROOT / "bench" / "traffic" / f"{name}.json").read_text())


def _key(sched):
    return [(p.rid, p.due_s, p.max_new, p.prompt.tobytes()) for p in sched]


@pytest.mark.parametrize("name", MIXES)
def test_same_seed_same_schedule(name):
    mix = _mix(name)
    a = traffic.schedule(mix, SEEDS[2], 30, 50_000)
    b = traffic.schedule(mix, SEEDS[2], 30, 50_000)
    assert _key(a) == _key(b)
    c = traffic.schedule(mix, SEEDS[1], 30, 50_000)
    assert _key(a) != _key(c)


@pytest.mark.parametrize("name", MIXES)
def test_every_seed_offers_the_same_work(name):
    """Seeds shuffle which request gets which size and gap; the sizes and
    the gaps themselves are the same for every seed."""
    mix = _mix(name)
    runs = [traffic.schedule(mix, s, 30, 50_000) for s in SEEDS]
    n = traffic.n_requests(mix, 30)
    for sched in runs:
        assert len(sched) == n
        assert sorted(len(p.prompt) for p in sched) == sorted(
            len(p.prompt) for p in runs[0])
        assert sorted(p.max_new for p in sched) == sorted(
            p.max_new for p in runs[0])
        due = np.array([p.due_s for p in sched])
        assert due[0] == 0.0 and np.all(np.diff(due) >= 0) and due[-1] < 30
        gaps = np.sort(np.diff(np.append(due, 30.0)))
        first = np.array([p.due_s for p in runs[0]])
        assert np.allclose(gaps, np.sort(np.diff(np.append(first, 30.0))))


@pytest.mark.parametrize("name", MIXES)
def test_lengths_lie_on_the_warmed_grid(name):
    mix = _mix(name)
    grid = set(traffic.grid(mix["prompt_len"]))
    sched = traffic.schedule(mix, SEEDS[0], 30, 1000)
    assert {len(p.prompt) for p in sched} <= grid
    warm = traffic.warmup(mix, 64, 1000)
    assert {len(p.prompt) for p in warm} == grid
    assert len(warm) >= 64
    cap = traffic.max_new_cap(mix)
    assert max(p.max_new for p in sched) <= cap
    assert all(0 <= p.prompt.min() and p.prompt.max() < 1000 for p in sched)


def test_arrival_burstiness_follows_the_mix():
    chat = traffic.schedule(_mix("chat"), 3, 30, 1000)
    code = traffic.schedule(_mix("code"), 3, 30, 1000)
    cv = lambda s: (lambda g: g.std() / g.mean())(np.diff([p.due_s for p in s]))
    assert 2.5 < cv(chat) < 3.5
    assert 0.8 < cv(code) < 1.2
