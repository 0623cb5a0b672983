"""Device idle time inside the program's own host spans.

The program names its layer boundaries with ``repro.core.spans``; while
the profiler runs, each span lands on the trace's host plane under its
name (``Trace.host``). A span's idle time is the part of the device's
idle gaps (``Trace.gaps``) that it covers.
"""

from __future__ import annotations

from bench.harness.trace import _union


def idle_share_in(tr, name: str) -> float | None:
    """Device idle time inside the host spans named ``name``, as a share
    of the traced slice, in percent. None where the trace has no device,
    or the slice holds no span of that name: never 0 for what was not
    seen."""
    if tr is None or tr.devices == 0 or tr.window_s <= 0:
        return None
    covered = _union([e for e in tr.host if e[2] == name], tr.lo, tr.hi)
    if not covered:
        return None
    return 100.0 * _overlap_ns(tr.gaps(), covered) / (tr.hi - tr.lo)


def _overlap_ns(a: list, b: list) -> int:
    """Length of the intersection of two sorted lists of disjoint
    intervals."""
    out, i, j = 0, 0, 0
    while i < len(a) and j < len(b):
        lo, hi = max(a[i][0], b[j][0]), min(a[i][1], b[j][1])
        out += max(0, hi - lo)
        if a[i][1] < b[j][1]:
            i += 1
        else:
            j += 1
    return out
