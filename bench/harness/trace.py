"""The profiler's trace of a slice of the window, and its reduction.

``Tracer`` starts JAX's profiler ``TRACE_S`` seconds before the window
closes and stops it at the close, inside a span named ``traced_window``
whose start is also read from ``time.monotonic()``: that pair maps the
host's clock onto the trace's. ``Trace`` reads the ``.xplane.pb`` with
``jax.profiler.ProfileData``: the device's operations (the ``XLA Ops``
line of each ``/device:`` plane), its programs (``XLA Modules``) and the
host's spans. Programs are found by their jitted names: ``_step`` and
``_admit`` of the slot scheduler, ``_prefill`` of the engine.
"""

from __future__ import annotations

import bisect
import glob
import itertools
import os
import re
import shutil
import tempfile
import time
from dataclasses import dataclass, field

TRACE_S = 3.0                 # seconds traced, ending at the window's close
MIN_GAP_NS = 10_000           # idle stretches shorter than this are not gaps
MAX_SPAN_NS = 1_000_000_000   # host spans longer than this explain no gap


class Tracer:
    def __init__(self, trace_s: float = TRACE_S):
        self.trace_s = trace_s
        self.dir = tempfile.mkdtemp(prefix="bench-trace-")
        self.start_at = None
        self.started_mono = None      # time.monotonic() at the span's start
        self.stopped = False
        self._span = None

    def arm(self, t0: float, close: float) -> None:
        self.start_at = max(t0, close - self.trace_s)

    def poll(self, now: float) -> None:
        if self.started_mono is None and now >= self.start_at:
            import jax

            opts = jax.profiler.ProfileOptions()
            opts.python_tracer_level = 0      # host spans, not every call
            opts.enable_hlo_proto = False
            jax.profiler.start_trace(self.dir, profiler_options=opts)
            self._span = jax.profiler.TraceAnnotation("traced_window")
            self.started_mono = time.monotonic()
            self._span.__enter__()

    def stop(self) -> None:
        if self.started_mono is None or self.stopped:
            return
        import jax

        self._span.__exit__(None, None, None)
        jax.profiler.stop_trace()
        self.stopped = True

    def path(self) -> str | None:
        found = glob.glob(os.path.join(self.dir, "**", "*.xplane.pb"),
                          recursive=True)
        return found[0] if found else None

    def cleanup(self) -> None:
        shutil.rmtree(self.dir, ignore_errors=True)


@dataclass
class Trace:
    ops: list = field(default_factory=list)       # (start_ns, end_ns, name)
    modules: list = field(default_factory=list)   # (start_ns, end_ns, name)
    host: list = field(default_factory=list)      # (start_ns, end_ns, name)
    devices: int = 0
    lo: int = 0          # the traced_window span, in the trace's clock
    hi: int = 0
    mono_offset_ns: int = 0   # trace ns = monotonic s * 1e9 + this

    @classmethod
    def load(cls, path: str, started_mono: float | None = None) -> "Trace":
        from jax.profiler import ProfileData

        tr = cls()
        pd = ProfileData.from_file(path)
        first_device = None
        for plane in pd.planes:
            if plane.name.startswith("/device:") and "TPU" in plane.name.upper() \
                    or plane.name.startswith("/device:GPU"):
                tr.devices += 1
                if first_device is not None:
                    continue             # one chip's ops; cells use one chip
                first_device = plane.name
                for line in plane.lines:
                    if line.name == "XLA Ops":
                        tr.ops += _events(line)
                    elif line.name == "XLA Modules":
                        tr.modules += _events(line)
            elif plane.name.startswith("/host:CPU"):
                for line in plane.lines:
                    tr.host += _events(line)
        win = [e for e in tr.host if e[2] == "traced_window"]
        if win:
            tr.lo, tr.hi = win[0][0], win[0][1]
        elif tr.ops:
            tr.lo, tr.hi = tr.ops[0][0], tr.ops[-1][1]
        if started_mono is not None and win:
            tr.mono_offset_ns = tr.lo - int(started_mono * 1e9)
        tr.ops.sort()
        tr.modules.sort()
        return tr

    # -------------------------------------------------------------- clocks
    def ns(self, mono_s: float) -> int:
        return int(mono_s * 1e9) + self.mono_offset_ns

    def inside(self, mono_s: float) -> bool:
        return self.lo <= self.ns(mono_s) <= self.hi

    @property
    def window_s(self) -> float:
        return (self.hi - self.lo) / 1e9

    # ----------------------------------------------------------- reductions
    def busy_s(self) -> float:
        """Union of the device's operation intervals inside the window."""
        return sum(b - a for a, b in _union(self.ops, self.lo, self.hi)) / 1e9

    def program(self, name: str) -> tuple[int, float]:
        """(count, device seconds) of the program jitted from ``name``,
        over its runs that start inside the window."""
        pat = re.compile(rf"^jit_{re.escape(name)}(\(|$)")
        hits = [(a, b) for a, b, n in self.modules
                if pat.search(n) and self.lo <= a <= self.hi]
        return len(hits), sum(b - a for a, b in hits) / 1e9

    def gaps(self) -> list[tuple[int, int]]:
        busy = _union(self.ops, self.lo, self.hi)
        out, t = [], self.lo
        for a, b in busy:
            if a - t >= MIN_GAP_NS:
                out.append((t, a))
            t = max(t, b)
        if self.hi - t >= MIN_GAP_NS:
            out.append((t, self.hi))
        return out

    def breakdown(self, top: int = 10) -> dict:
        """The device operations that took most time, and idle time by what
        the host was doing: the innermost host span that covers most of each
        gap, or ``serve_loop`` where none of the host's spans does."""
        by_op: dict[str, int] = {}
        for n, own in _self_times(self.ops, self.lo, self.hi):
            n = _short(n)
            by_op[n] = by_op.get(n, 0) + own
        spans = sorted(e for e in self.host
                       if e[2] != "traced_window" and e[1] - e[0] < MAX_SPAN_NS)
        starts = [s for s, _, _ in spans]
        by_gap: dict[str, int] = {}
        for a, b in self.gaps():
            i = bisect.bisect_left(starts, a - MAX_SPAN_NS)
            label = _dominant(spans, i, a, b) or "serve_loop"
            by_gap[label] = by_gap.get(label, 0) + (b - a)
        rank = lambda d: [[k, v / 1e9] for k, v in
                          sorted(d.items(), key=lambda kv: -kv[1])[:top]]
        return {"device_ops": rank(by_op), "idle_gaps": rank(by_gap)}


def _events(line) -> list:
    return [(int(e.start_ns), int(e.start_ns + e.duration_ns), e.name)
            for e in line.events]


def _short(op: str) -> str:
    """An operation's name and result shape, without its operands."""
    return op.split("{", 1)[0].split(" fusion(", 1)[0][:120]


def _self_times(ops, lo: int, hi: int) -> list[tuple[str, int]]:
    """Operations' own time, clipped to [lo, hi]: a ``while`` or a call
    that contains other operations on the line keeps only the time none of
    them covers. Returned as (name, own ns)."""
    out = []
    stack: list[list] = []       # [start, end, name, covered_ns]

    def pop():
        a, b, n, covered = stack.pop()
        own = max(0, min(b, hi) - max(a, lo) - covered)
        out.append((n, own))
        if stack:
            pa, pb = stack[-1][0], stack[-1][1]
            stack[-1][3] += max(0, min(b, pb, hi) - max(a, pa, lo))

    for a, b, n in sorted(ops, key=lambda e: (e[0], -e[1])):
        if b <= lo or a >= hi:
            continue
        while stack and stack[-1][1] <= a:
            pop()
        stack.append([a, b, n, 0])
    while stack:
        pop()
    return out


def _union(intervals, lo: int, hi: int) -> list[tuple[int, int]]:
    out: list[list[int]] = []
    for a, b, _ in sorted(intervals):
        a, b = max(a, lo), min(b, hi)
        if b <= a:
            continue
        if out and a <= out[-1][1]:
            out[-1][1] = max(out[-1][1], b)
        else:
            out.append([a, b])
    return [(a, b) for a, b in out]


def _dominant(spans, i: int, a: int, b: int) -> str | None:
    """The host span that overlaps [a, b] most; the shorter one on a tie,
    so that a callback wins over the loop call that contains it."""
    best, best_key = None, None
    for s, e, n in itertools.islice(spans, i, None):
        if s >= b:
            break
        ov = min(e, b) - max(s, a)
        if ov <= 0:
            continue
        key = (ov, -(e - s))
        if best_key is None or key > best_key:
            best, best_key = n, key
    return best
