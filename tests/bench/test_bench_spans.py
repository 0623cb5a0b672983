"""The program's own spans in a traced run of the harness, at the CPU size,
and the three metrics that read them: ``idle_admit_share``,
``idle_step_share`` (device idle inside ``serve.admit`` and ``serve.step``)
and ``setup_compile_s`` (the program's compile records before the window)."""

import json
import shutil
import sys
import time
from pathlib import Path

import pytest

ROOT = Path(__file__).resolve().parents[2]
sys.path.insert(0, str(ROOT))
DATA = Path(__file__).resolve().parent / "data"
PEAKS = {"bf16_flops_per_s": 1e12, "hbm_bytes_per_s": 1e11}
IDLE = ("idle_admit_share", "idle_step_share")


def _cell(root: Path, family: str, mix: str):
    from bench import run as R

    (root / "bench" / "traffic").mkdir(parents=True)
    (root / "bench" / "limits").mkdir(parents=True)
    shutil.copy(DATA / f"tiny-{family}.json", root / "cfg.json")
    shutil.copy(DATA / f"tiny-{mix}.json",
                root / "bench" / "traffic" / f"{mix}.json")
    (root / "bench" / "limits" / "cell.json").write_text(json.dumps({
        "sample": {"min_tokens": 300, "max_requests": 12},
        "compare": {"logit_gap": 0.01}}))
    spec = json.loads((ROOT / "BENCHMARK.json").read_text())
    spec["configs"] = [{"name": "cfg", "file": "cfg.json"}]
    spec["workloads"] = [{"name": "cell", "config": "cfg", "traffic": mix,
                          "chips": 1}]
    return R.Cell(spec, "cell", root=root)


@pytest.fixture(scope="module")
def traced(tmp_path_factory):
    """One traced window of the dense test configuration under the code
    mix, with its trace kept: (what the readers get, the loop's end)."""
    from bench import run as R
    from bench.harness.session import Session
    from bench.harness.trace import Trace

    tmp = tmp_path_factory.mktemp("spans")
    saved = R.CACHE_DIR
    R.CACHE_DIR = tmp / "jax-cache"
    try:
        R.setup_jax()
    finally:
        R.CACHE_DIR = saved
    cell = _cell(tmp / "root", "dense", "code")
    s = Session(cell, work_dir=tmp / "work")
    served = s.serve(2**31 + 17, 2.0, trace=True)
    end_ns = time.monotonic_ns()
    tr = Trace.load(served.tracer.path(), served.tracer.started_mono)
    served.tracer.cleanup()
    run = R.RunData(obs=served.obs, trace=tr, family=s.fam, c=cell.c,
                    peaks=PEAKS, load=s.load)
    return run, end_ns


def _in_window(tr):
    return [e for e in tr.host
            if e[2].startswith(("serve.", "link.")) and tr.lo <= e[0]
            and e[1] <= tr.hi]


def test_program_spans_land_in_the_trace_inside_the_window(traced):
    run, _ = traced
    names = {n for _, _, n in _in_window(run.trace)}
    assert {"serve.accept", "serve.admit", "serve.admit.prefill",
            "serve.admit.sample", "serve.admit.splice", "serve.step",
            "serve.step.dispatch", "serve.step.emit",
            "serve.step.retire"} <= names


def test_ring_spans_map_onto_their_trace_copies(traced):
    from repro.core import spans

    run, end_ns = traced
    tr = run.trace
    ring: dict[str, list] = {}
    for r in spans.records():
        if r.t1_ns <= end_ns:
            ring.setdefault(r.name, []).append(
                (tr.ns(r.t0_ns / 1e9), tr.ns(r.t1_ns / 1e9)))
    copies = _in_window(tr)
    assert len(copies) > 20
    for a, b, name in copies:
        off = min(abs(a - ra) + abs(b - rb) for ra, rb in ring[name])
        assert off < 1_000_000, (name, off)


def test_setup_compile_s_reads_and_idle_shares_leave_the_cpu_out(traced):
    from bench import run as R

    run, _ = traced
    compile_s = R._reader("setup_compile_s")(run)
    assert 0 < compile_s < run.obs.t0 - R.T_START
    for name in IDLE:
        assert R._reader(name)(run) is None


def test_idle_readers_on_a_hand_built_trace():
    from bench import run as R
    from bench.harness.trace import Trace

    tr = Trace(
        ops=[(0, 100_000, "a"), (200_000, 500_000, "b"),
             (600_000, 1_000_000, "c")],
        host=[(50_000, 150_000, "serve.admit"),
              (120_000, 180_000, "serve.admit"),     # overlaps the first
              (450_000, 650_000, "serve.step"),
              (500_000, 550_000, "serve.step.sync"),
              (900_000, 950_000, "serve.step")],
        devices=1, lo=0, hi=1_000_000,
    )
    run = R.RunData(trace=tr)
    # gaps: 100-200 us and 500-600 us; admission covers 50-180 us of the
    # first, the steps all of the second
    assert R._reader("idle_admit_share")(run) == pytest.approx(8.0)
    assert R._reader("idle_step_share")(run) == pytest.approx(10.0)
    tr.host = [e for e in tr.host if e[2] != "serve.admit"]
    assert R._reader("idle_admit_share")(run) is None
    tr.devices = 0
    assert R._reader("idle_step_share")(run) is None


def test_program_steps_against_the_steps_rebuilt_from_the_stream(traced):
    """``Observed.steps()`` rebuilds the decode steps from the order of the
    streamed tokens; the loop's own ``serve.step`` records after the window
    opens are the steps it issued. At this size the two agree step for
    step."""
    from repro.core import spans

    run, end_ns = traced
    obs = run.obs
    issued = sorted((r.t0_ns, r.attrs["n_active"]) for r in spans.records()
                    if r.name == "serve.step" and r.t1_ns <= end_ns
                    and r.t0_ns >= obs.t0 * 1e9)
    rebuilt = obs.steps()
    assert len(issued) == len(rebuilt) > 0
    assert [n for _, n in issued] == [len(pos) for _, pos in rebuilt]
