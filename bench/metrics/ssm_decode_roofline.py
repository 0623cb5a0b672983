"""SSM decode state (``kernels/ssm_decode``, called by the model decode
step): the least time the traced steps' SSM state needs, its bytes over
the chip's HBM bandwidth, as a share of the device time that the kernel's
calls took in the traced slice, in percent.

The bytes come from the counters that the ``serve.step`` spans carry
(worked out on the host from the active rows): ``ssm_rows_stepped``, the
rows whose state the step reads and writes, summed over the SSM layers,
times what one such row moves in one layer: its state read and written
(2 H P N, in float32), and the token's ``da`` (H), ``xdt`` (H P), ``B`` and
``C`` (G N each) and ``y`` (H P), in float32. The arithmetic (about 4
operations an element of the state) is far under the chip's peak over that
time, so bytes bound it.

The device time is the union of the operations that the chip's trace names
``%ssm_decode.<n>``, the Pallas kernel's name
(``kernels/ssm_decode/ssm_decode.py`` ``NAME``). A program without the
counters or the kernel reads nothing."""

import re

from bench.harness.trace import Trace

KERNEL = re.compile(r"^%?ssm_decode(\.\d+)?\s")


def read(run):
    tr = run.trace
    if tr is None or tr.devices == 0 or tr.window_s <= 0:
        return None
    try:
        from repro.core import spans
    except ImportError:
        return None
    stepped = 0
    for r in spans.records():
        a = r.attrs or {}
        if r.name == "serve.step" and "ssm_rows_stepped" in a \
                and tr.inside(r.t0_ns / 1e9):
            stepped += a["ssm_rows_stepped"]
    kernel = Trace(ops=[e for e in tr.ops if KERNEL.match(e[2])],
                   lo=tr.lo, hi=tr.hi)
    busy_s = kernel.busy_s()              # the union: no time counted twice
    if stepped <= 0 or busy_s <= 0:
        return None
    s = run.family.sizes(run.c)
    state = s["heads"] * s["p"] * s["n"]
    row = 4 * (2 * state + s["heads"] + 2 * s["heads"] * s["p"]
               + 2 * s["g"] * s["n"])
    return 100.0 * stepped * row / run.peaks["hbm_bytes_per_s"] / busy_s
