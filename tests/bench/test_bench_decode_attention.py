"""The decode-attention roofline's reader on hand-built traces: the
kernel's calls found by name, their union inside the slice, the need from
the ``serve.step`` spans' counters, and nothing without them."""

import importlib.util
import json
import sys
from pathlib import Path

import pytest

ROOT = Path(__file__).resolve().parents[2]
sys.path.insert(0, str(ROOT))

from bench.families import dense, moe  # noqa: E402
from bench.harness.trace import Trace  # noqa: E402

MS = 1_000_000
KERNEL = "%decode_attention{} = bf16[32,24,128] custom-call()"
CELLS = {"starcoder2-3b": dense, "olmoe-1b-7b": moe}


def _reader():
    spec = importlib.util.spec_from_file_location(
        "decode_attn_roofline",
        ROOT / "bench" / "metrics" / "decode_attn_roofline.py")
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod.read


def _run(trace, config):
    class Run:
        family = CELLS[config]
        c = json.loads((ROOT / "bench" / "configs" / f"{config}.json")
                       .read_text())
        peaks = {"hbm_bytes_per_s": 819e9}
    Run.trace = trace
    return Run


@pytest.mark.parametrize("config", sorted(CELLS))
def test_reader_counts_the_kernels_bytes_over_its_device_time(config):
    """Two steps' counters; three kernel calls inside the slice, two of
    them overlapping by 0.5 ms (3 ms in all), one outside it, and another
    operation that is not the kernel."""
    from repro.core import spans

    with spans.span("serve.step", n_active=3) as sp:
        sp.annotate(attn_kv_read=7_000, attn_kv_held=90_000)
    with spans.span("serve.step", n_active=2) as sp:
        sp.annotate(attn_kv_read=5_000, attn_kv_held=90_000)
    t0 = [r for r in spans.records() if r.name == "serve.step"][-2].t0_ns
    lo, hi = t0 - MS, t0 + 10 * MS
    ops = [(t0, t0 + MS, KERNEL.format(".3")),
           (t0 + MS // 2, t0 + 2 * MS, KERNEL.format("")),
           (t0 + 3 * MS, t0 + 4 * MS, KERNEL.format(".9")),
           (hi + MS, hi + 2 * MS, KERNEL.format(".9")),
           (t0, t0 + 5 * MS, "%fusion.205 = f32[32,2,12] fusion()")]
    run = _run(Trace(ops=ops, devices=1, lo=lo, hi=hi), config)
    s = run.family.sizes(run.c)
    need = (12_000 * 2 * s["kv"] * s["hd"] * 2
            + 5 * s["layers"] * 2 * s["heads"] * s["hd"] * 2)
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
    assert read(_run(Trace(ops=kernel, devices=1, lo=lo, hi=hi),
                     "starcoder2-3b")) is None
    with spans.span("serve.step", n_active=3) as sp:
        sp.annotate(attn_kv_read=7_000, attn_kv_held=90_000)
    t1 = [r for r in spans.records() if r.name == "serve.step"][-1].t0_ns
    other = [(t1, t1 + MS, "%fusion.205 = f32[32,2,12] fusion()")]
    assert read(_run(Trace(ops=other, devices=1, lo=t1 - MS, hi=t1 + MS),
                     "starcoder2-3b")) is None
    assert read(_run(None, "starcoder2-3b")) is None
