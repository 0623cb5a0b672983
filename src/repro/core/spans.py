"""Program spans and compile counters, on the clock the device trace shares.

One API for the program's own layer boundaries:

    with spans.span("serve.step", n_active=3):
        ...
    spans.count("name")
    spans.records(); spans.compile_events(); spans.summary()

``span`` enters ``jax.profiler.TraceAnnotation(name)``, so while the
profiler runs the span lands on the trace's host plane, on the device
trace's clock. It also appends a ``Span`` record to a bounded in-memory
ring, stamped with ``time.monotonic_ns()``: the clock the serve loop and
its callers already use, which a trace maps onto its own by one offset.
Parent links come from a per-thread stack of open spans; attributes stay
few (a request id on admission, ``n_active`` on a decode step).

The first span also registers one process-wide ``jax.monitoring``
listener. It records every compile event with its monotonic end, its
duration and the name of the innermost open span on the compiling thread:

``trace``            jaxpr tracing of a jitted function;
``lower``            lowering to an MLIR module: one per new program, even
                     where the persistent cache then supplies the executable;
``compile``          a backend compile that the persistent cache did not serve;
``cache_hit``        a backend compile that the persistent cache served: JAX
                     times the cache read inside the backend compile;
``cache_retrieval``  the seconds of that cache read. It lies inside its
                     ``cache_hit``: never add the two together.

Nothing is exported or written out, and there is no switch: recording
stays on and bounded, and "tracing on" means the profiler is running. JAX
is imported at the first span, not at import.
"""

from __future__ import annotations

import itertools
import threading
import time
from collections import deque
from typing import NamedTuple

#: Span records kept: the newest ``RING`` ever closed. A 51 s benchmark run
#: of a 64-slot decode loop, warm-up included, closes a few tens of
#: thousands; an idle loop polling its source every half millisecond closes
#: 2,000 a second.
RING = 1 << 18
COMPILE_RING = 1 << 16

_KINDS = {
    "/jax/core/compile/jaxpr_trace_duration": "trace",
    "/jax/core/compile/jaxpr_to_mlir_module_duration": "lower",
    "/jax/compilation_cache/cache_retrieval_time_sec": "cache_retrieval",
}
_BACKEND = "/jax/core/compile/backend_compile_duration"
_HIT = "/jax/compilation_cache/cache_hits"


class Span(NamedTuple):
    index: int            # order of opening, process-wide
    name: str
    t0_ns: int            # time.monotonic_ns()
    t1_ns: int
    parent: int           # index of the enclosing span on its thread, or -1
    attrs: dict | None


class Compile(NamedTuple):
    kind: str             # trace | lower | compile | cache_hit | cache_retrieval
    t0_ns: int            # end less the duration JAX reports
    t1_ns: int            # time.monotonic_ns() when JAX reported it
    span: str | None      # innermost open span on the compiling thread
    thread: int           # threading.get_ident() of that thread


class Recorder:
    """Span records, compile records and counters of one process."""

    def __init__(self, size: int = RING, compile_size: int = COMPILE_RING):
        self._spans: deque = deque(maxlen=size)
        self._compiles: deque = deque(maxlen=compile_size)
        self._counters: dict[str, int] = {}
        self._lock = threading.Lock()
        self._ids = itertools.count()
        self._opened = 0
        self._local = threading.local()
        self._annotation = None       # jax.profiler.TraceAnnotation, at first use

    def _stack(self) -> list:
        try:
            return self._local.stack
        except AttributeError:
            self._local.stack = []
            return self._local.stack

    def _start(self) -> None:
        import jax

        self._annotation = jax.profiler.TraceAnnotation

    def span(self, name: str, **attrs) -> "_Open":
        if self._annotation is None:
            self._start()
        return _Open(self, name, attrs or None)

    def count(self, name: str, n: int = 1) -> None:
        with self._lock:
            self._counters[name] = self._counters.get(name, 0) + n

    def records(self) -> list[Span]:
        """The ring's span records, in the order they closed."""
        return [Span._make(r) for r in list(self._spans)]

    def compile_events(self) -> list[Compile]:
        return list(self._compiles)

    def summary(self) -> dict:
        """Count, total and max seconds of each span name and compile kind,
        the counters, and how many spans were opened against how many the
        ring still holds."""
        return {
            "spans": _stats((r.name, r.t1_ns - r.t0_ns) for r in self.records()),
            "compiles": _stats(
                (c.kind, c.t1_ns - c.t0_ns) for c in self.compile_events()),
            "counters": dict(self._counters),
            "records_seen": self._opened,
            "records_kept": len(self._spans),
        }

    # ---------------------------------------------- jax.monitoring callbacks
    def on_duration(self, event: str, duration: float, **kw) -> None:
        if event == _BACKEND:
            kind = "cache_hit" if getattr(self._local, "hit", False) else "compile"
            self._local.hit = False
        else:
            kind = _KINDS.get(event)
            if kind is None:
                return
        t1 = time.monotonic_ns()
        stack = self._stack()
        self._compiles.append(Compile(
            kind, t1 - int(duration * 1e9), t1,
            stack[-1][1] if stack else None, threading.get_ident()))
        self.count("compile." + kind)

    def on_event(self, event: str, **kw) -> None:
        if event == _HIT:
            self._local.hit = True


class _Open:
    """One span while it is open. The ring holds plain tuples, made into
    ``Span`` records when read: the open path stays a few microseconds."""

    __slots__ = ("_rec", "_name", "_attrs", "_index", "_parent", "_t0",
                 "_ann", "_stack")

    def __init__(self, rec: Recorder, name: str, attrs: dict | None):
        self._rec = rec
        self._name = name
        self._attrs = attrs

    def __enter__(self):
        rec = self._rec
        self._stack = stack = rec._stack()
        self._index = index = next(rec._ids)
        rec._opened = index + 1
        self._parent = stack[-1][0] if stack else -1
        stack.append((index, self._name))
        self._ann = ann = rec._annotation(self._name)
        ann.__enter__()
        self._t0 = time.monotonic_ns()
        return self

    def __exit__(self, *exc) -> None:
        t1 = time.monotonic_ns()
        self._ann.__exit__(*exc)
        self._stack.pop()
        self._rec._spans.append((self._index, self._name, self._t0, t1,
                                 self._parent, self._attrs))


def _stats(pairs) -> dict:
    out: dict[str, dict] = {}
    for name, ns in pairs:
        s = out.setdefault(name, {"count": 0, "total_s": 0.0, "max_s": 0.0})
        s["count"] += 1
        s["total_s"] += ns / 1e9
        s["max_s"] = max(s["max_s"], ns / 1e9)
    return out


class _Process(Recorder):
    """The process's recorder: its first span also registers the compile
    listener."""

    def _start(self) -> None:
        import jax

        with self._lock:
            if self._annotation is None:
                jax.monitoring.register_event_duration_secs_listener(
                    self.on_duration)
                jax.monitoring.register_event_listener(self.on_event)
                super()._start()


_PROCESS = _Process()
span = _PROCESS.span
count = _PROCESS.count
records = _PROCESS.records
compile_events = _PROCESS.compile_events
summary = _PROCESS.summary
