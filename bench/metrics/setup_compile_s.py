"""Program loading in set-up: the seconds of jaxpr tracing, lowering and
backend compiling (a persistent-cache read counted once, inside its
backend compile) that the program's compile records put inside one of
its spans, from the epoch load that set the engine up until the window
opens. Compiles outside the program's spans, such as the bit-for-bit
check of the params, are not counted. Nested records (a jitted function
traced inside another's trace) count once: the union of their
intervals."""

from bench.harness.trace import _union

KINDS = ("trace", "lower", "compile", "cache_hit")


def read(run):
    try:
        from repro.core import spans
    except ImportError:       # a program that keeps no compile records
        return None
    open_ns = int(run.obs.t0 * 1e9)
    loads = [r.t0_ns for r in spans.records()
             if r.name == "link.load" and r.t0_ns < open_ns]
    start_ns = max(loads, default=0)
    hits = [(c.t0_ns, c.t1_ns, c.kind) for c in spans.compile_events()
            if c.kind in KINDS and c.span is not None
            and c.t0_ns >= start_ns and c.t1_ns <= open_ns]
    if not hits:
        return None
    return sum(b - a for a, b in _union(hits, start_ns, open_ns)) / 1e9
