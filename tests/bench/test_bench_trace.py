"""The reduction from a profiler trace to the device metrics, checked on a
short trace recorded on a TPU v5e chip (``bench/testdata``): mamba2-370m
serving the chat mix: 0.12 s cut from the middle of a 3 s traced window, with
the events that overlap it and their names (their stats dropped)."""

import sys
from pathlib import Path

import pytest

ROOT = Path(__file__).resolve().parents[2]
sys.path.insert(0, str(ROOT))

from bench.harness import trace as T  # noqa: E402

TRACE = ROOT / "bench" / "testdata" / "mamba2-370m.chat.v5e.xplane.pb"


@pytest.fixture(scope="module")
def tr():
    return T.Trace.load(str(TRACE))


def _direct(path):
    """The same trace read without the reducer: every event of the first
    TPU plane's op and module lines, and the traced_window span."""
    from jax.profiler import ProfileData

    ops, mods, win = [], [], None
    for plane in ProfileData.from_file(str(path)).planes:
        if plane.name == "/device:TPU:0":
            for line in plane.lines:
                for e in line.events:
                    ev = (e.start_ns, e.start_ns + e.duration_ns, e.name)
                    if line.name == "XLA Ops":
                        ops.append(ev)
                    elif line.name == "XLA Modules":
                        mods.append(ev)
        elif plane.name.startswith("/host:CPU"):
            for line in plane.lines:
                for e in line.events:
                    if e.name == "traced_window":
                        win = (e.start_ns, e.start_ns + e.duration_ns)
    return ops, mods, win


def test_window_and_busy_time(tr):
    ops, _, win = _direct(TRACE)
    assert (tr.lo, tr.hi) == win
    assert tr.devices == 1
    # busy time by brute force over a 1-microsecond grid
    step = 1000
    busy = set()
    for a, b, _ in ops:
        a, b = max(a, win[0]), min(b, win[1])
        busy.update(range(int(a) // step, int(b) // step))
    assert tr.busy_s() == pytest.approx(len(busy) * step / 1e9, rel=0.02)
    assert 0 < tr.busy_s() <= tr.window_s


def test_programs_are_found_by_their_jitted_names(tr):
    _, mods, win = _direct(TRACE)
    for name in ("_step", "_prefill"):
        hits = [(a, b) for a, b, n in mods
                if n.split("(")[0] == f"jit_{name}" and win[0] <= a <= win[1]]
        n, sec = tr.program(name)
        assert n == len(hits)
        assert sec == pytest.approx(sum(b - a for a, b in hits) / 1e9)
    assert tr.program("_step")[0] > 0
    assert tr.program("step") == (0, 0)


def test_gaps_and_breakdown(tr):
    gaps = tr.gaps()
    idle = sum(b - a for a, b in gaps) / 1e9
    # gaps under MIN_GAP_NS are not gaps, so they cover at most the idle time
    assert idle <= tr.window_s - tr.busy_s() + 1e-9
    bd = tr.breakdown()
    assert 0 < len(bd["device_ops"]) <= 10 and len(bd["idle_gaps"]) <= 10
    own = sum(s for _, s in T._self_times(tr.ops, tr.lo, tr.hi)) / 1e9
    # operations' own times tile the busy time: none counted twice
    assert own == pytest.approx(tr.busy_s(), rel=0.02)
    assert abs(sum(s for _, s in bd["idle_gaps"]) - idle) < 1e-6 or \
        len(bd["idle_gaps"]) == 10


def test_self_times_split_nested_operations():
    ops = [(0, 100, "while"), (10, 30, "a"), (40, 90, "b"), (50, 60, "c"),
           (120, 130, "d")]
    own = dict(T._self_times(ops, 0, 200))
    assert own == {"while": 30, "a": 20, "b": 40, "c": 10, "d": 10}
    assert dict(T._self_times(ops, 20, 55)) == {
        "while": 10, "a": 10, "b": 10, "c": 5}
