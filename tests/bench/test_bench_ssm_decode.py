"""The SSM-state roofline's reader on hand-built traces: the kernel's calls
found by name, their union inside the slice, the need from the
``serve.step`` spans' counters, and nothing without them."""

import importlib.util
import json
import sys
from pathlib import Path

import pytest

ROOT = Path(__file__).resolve().parents[2]
sys.path.insert(0, str(ROOT))

from bench.families import ssm  # noqa: E402
from bench.harness.trace import Trace  # noqa: E402

MS = 1_000_000
KERNEL = ("%ssm_decode{} = (f32[64,16,128], f32[48,64,32,64,128]) "
          "custom-call()")


def _reader():
    spec = importlib.util.spec_from_file_location(
        "ssm_decode_roofline",
        ROOT / "bench" / "metrics" / "ssm_decode_roofline.py")
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod.read


def _run(trace):
    class Run:
        family = ssm
        c = json.loads((ROOT / "bench" / "configs" / "mamba2-370m.json")
                       .read_text())
        peaks = {"hbm_bytes_per_s": 819e9}
    Run.trace = trace
    return Run


def test_reader_counts_the_kernels_bytes_over_its_device_time():
    """Two steps' counters (28 and 20 live rows of 64, in 48 layers);
    three kernel calls inside the slice, two of them overlapping by 0.5 ms
    (3 ms in all), one outside it, and another operation that is not the
    kernel."""
    from repro.core import spans

    with spans.span("serve.step", n_active=28) as sp:
        sp.annotate(ssm_rows_stepped=48 * 28, ssm_rows_held=48 * 64)
    with spans.span("serve.step", n_active=20) as sp:
        sp.annotate(ssm_rows_stepped=48 * 20, ssm_rows_held=48 * 64)
    t0 = [r for r in spans.records() if r.name == "serve.step"][-2].t0_ns
    lo, hi = t0 - MS, t0 + 10 * MS
    ops = [(t0, t0 + MS, KERNEL.format(".3")),
           (t0 + MS // 2, t0 + 2 * MS, KERNEL.format("")),
           (t0 + 3 * MS, t0 + 4 * MS, KERNEL.format(".9")),
           (hi + MS, hi + 2 * MS, KERNEL.format(".9")),
           (t0, t0 + 5 * MS, "%fusion.140 = f32[64,32,64] fusion()")]
    run = _run(Trace(ops=ops, devices=1, lo=lo, hi=hi))
    s = run.family.sizes(run.c)
    assert (s["heads"], s["p"], s["n"], s["g"]) == (32, 64, 128, 1)
    # each row and layer: its state in and out, and da, xdt, B, C and y
    row = 4 * (2 * 32 * 64 * 128 + 32 + 2 * 32 * 64 + 2 * 128)
    need = 48 * (28 + 20) * row
    assert _reader()(run) == pytest.approx(100 * need / 819e9 / 3e-3)


def test_reader_reads_nothing_without_the_counters_or_the_kernel():
    """A slice whose steps carry no counters (a program without the
    kernel's counters) reads nothing, and so does one without the
    kernel's calls."""
    from repro.core import spans

    with spans.span("serve.step", n_active=3):
        pass
    t0 = [r for r in spans.records() if r.name == "serve.step"][-1].t0_ns
    lo, hi = t0 - MS, t0 + MS // 2
    kernel = [(t0, t0 + MS // 4, KERNEL.format(".1"))]
    read = _reader()
    assert read(_run(Trace(ops=kernel, devices=1, lo=lo, hi=hi))) is None
    with spans.span("serve.step", n_active=3) as sp:
        sp.annotate(ssm_rows_stepped=48 * 3, ssm_rows_held=48 * 64)
    t1 = [r for r in spans.records() if r.name == "serve.step"][-1].t0_ns
    other = [(t1, t1 + MS, "%multiply_add_fusion = f32[48,64,32,64,128] "
                           "fusion()")]
    assert read(_run(Trace(ops=other, devices=1, lo=t1 - MS,
                           hi=t1 + MS))) is None
    assert read(_run(None)) is None
