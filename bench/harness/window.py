"""The callbacks that drive ``ServeEngine.serve_loop`` through one run.

One serve loop carries the whole run, because the slot scheduler's programs
belong to the loop: a second loop would lower them again. The source first
hands out the warm-up requests and waits for all of them to finish; that
ends set-up and opens the window. In the window it releases each planned
request once its due time has passed (open loop: requests the loop has no
room for yet wait in this backlog, and their wait counts). At the close it
stops the traced slice, releases what is still in the backlog and then
returns ``STOP``, so the loop drains and every request due in the window
is answered.
"""

from __future__ import annotations

import time
from contextlib import nullcontext

import numpy as np

from bench.harness.traffic import WARMUP_RID0


class CompileCounter:
    """Programs lowered and backend compiles, from ``jax.monitoring``. A
    lowering happens for every new program, even where the persistent
    cache then supplies the executable."""

    def __init__(self):
        self.lowered = 0
        self.compiled = 0

    def __call__(self, event: str, duration: float, **kw) -> None:
        if event == "/jax/core/compile/jaxpr_to_mlir_module_duration":
            self.lowered += 1
        elif event == "/jax/core/compile/backend_compile_duration":
            self.compiled += 1


class Window:
    def __init__(self, warm, planned, seconds: float, *, tracer=None,
                 annotate: bool = False, on_open=None, on_close=None):
        from repro.serve import STOP, Request

        self._Request, self._STOP = Request, STOP
        self.warm = list(warm)
        self.planned = list(planned)
        self.seconds = seconds
        self.tracer = tracer            # bench.harness.trace.Tracer or None
        self.on_open = on_open
        self.on_close = on_close
        self._next_warm = 0
        self._warm_done = 0
        self._next = 0
        self.t0: float | None = None    # window start, time.monotonic()
        self.closed_at: float | None = None
        self.deltas: list[tuple] = []   # (t, rid, seq, n_tokens, tokens)
        self.completions: dict = {}
        if annotate:
            import jax

            self._span = jax.profiler.TraceAnnotation
        else:
            self._span = lambda name: nullcontext()

    @property
    def close(self) -> float:
        return self.t0 + self.seconds

    # ------------------------------------------------------------ callbacks
    def source(self):
        with self._span("source"):
            return self._source(time.monotonic())

    def _source(self, now: float):
        if self.t0 is None:
            if self._next_warm < len(self.warm):
                p = self.warm[self._next_warm]
                self._next_warm += 1
                return self._request(p)
            if self._warm_done < len(self.warm):
                return None
            self.t0 = now
            if self.on_open is not None:
                self.on_open(now)
            if self.tracer is not None:
                self.tracer.arm(now, self.close)
        self._tick(now)
        if self._next < len(self.planned):
            p = self.planned[self._next]
            if self.t0 + p.due_s <= now:
                self._next += 1
                return self._request(p)
            return None
        return self._STOP if self.closed_at is not None else None

    def _tick(self, now: float) -> None:
        """Start and stop the traced slice, and mark the close. Called by
        the source and at every streamed token: a loop whose queue is full
        polls the source only when a slot frees."""
        if self.t0 is None:
            return
        if self.tracer is not None:
            self.tracer.poll(now)
        if now >= self.close and self.closed_at is None:
            self.closed_at = now
            if self.on_close is not None:
                self.on_close(now)
            if self.tracer is not None:
                self.tracer.stop()

    def _request(self, p):
        return self._Request(rid=p.rid, prompt=p.prompt, max_new_tokens=p.max_new)

    def sink(self, comp) -> None:
        with self._span("sink"):
            if comp.rid >= WARMUP_RID0:
                self._warm_done += 1
            else:
                self.completions[comp.rid] = comp

    def on_delta(self, d) -> None:
        with self._span("on_delta"):
            now = time.monotonic()
            if d.rid < WARMUP_RID0:
                self.deltas.append((now, d.rid, d.seq, len(d.tokens), d.tokens))
            self._tick(now)


# ---------------------------------------------------------------- reductions
def p95(values) -> float | None:
    v = np.asarray(list(values), dtype=np.float64)
    return float(np.percentile(v, 95)) if v.size else None


class Observed:
    """What the window saw, reduced from the callbacks' records."""

    def __init__(self, win: Window, max_batch: int):
        self.win = win
        self.max_batch = max_batch
        self.t0, self.close = win.t0, win.close
        self.due = {p.rid: win.t0 + p.due_s for p in win.planned}
        self.prompt_len = {p.rid: len(p.prompt) for p in win.planned}
        self.first = {}
        self.streamed: dict[int, dict[int, tuple]] = {}
        for t, rid, seq, _, toks in win.deltas:
            if seq == 0:
                self.first[rid] = t
            self.streamed.setdefault(rid, {})[seq] = toks

    def in_window(self, t: float) -> bool:
        return self.t0 <= t <= self.close

    def tokens_in_window(self) -> int:
        return sum(n for t, _, _, n, _ in self.win.deltas if self.in_window(t))

    def ttft_s(self) -> list[float]:
        """Due time to first token, for every request due in the window; a
        request with no first token counts as infinitely late."""
        return [self.first.get(r, np.inf) - d for r, d in self.due.items()]

    def itl_s(self) -> list[float]:
        last: dict[int, float] = {}
        gaps = []
        for t, rid, _, _, _ in self.win.deltas:
            if rid in last and self.in_window(last[rid]) and self.in_window(t):
                gaps.append(t - last[rid])
            last[rid] = t
        return gaps

    def steps(self) -> list[tuple[float, list[int]]]:
        """The decode steps, rebuilt from the streamed tokens: (time, the
        position each active slot fed). A step streams one token for every
        active slot, in slot order, so a step ends where a request repeats
        or an admission's first token comes between."""
        out: list[tuple[float, list[int]]] = []
        cur: dict[int, int] = {}
        t_cur = 0.0

        def flush():
            if cur:
                out.append((t_cur, list(cur.values())))
                cur.clear()

        for t, rid, seq, _, _ in self.win.deltas:
            if seq == 0 or rid in cur:
                flush()
            if seq > 0:
                cur[rid] = self.prompt_len[rid] + seq - 1
                t_cur = t
        flush()
        return out

    def admissions(self) -> list[tuple[float, int]]:
        """(time of the first token, prompt length) of every admission."""
        return [(t, self.prompt_len[rid]) for rid, t in self.first.items()]

    def answer_failures(self, vocab: int) -> list[str]:
        """Requests due in the window that were never answered, or whose
        answer is malformed: not exactly their tokens, a token outside the
        vocabulary, or a streamed token that differs from the completion."""
        bad = []
        for p in self.win.planned:
            comp = self.win.completions.get(p.rid)
            if comp is None:
                bad.append(f"request {p.rid} never completed")
                continue
            toks = np.asarray(comp.tokens)
            streamed = self.streamed.get(p.rid, {})
            flat = [t for seq in sorted(streamed) for t in streamed[seq]]
            if comp.status != "ok" or len(toks) != p.max_new:
                bad.append(f"request {p.rid}: status {comp.status}, "
                           f"{len(toks)} of {p.max_new} tokens")
            elif not ((toks >= 0) & (toks < vocab)).all():
                bad.append(f"request {p.rid}: a token outside the vocabulary")
            elif flat != toks.tolist():
                bad.append(f"request {p.rid}: streamed tokens differ from "
                           "its completion")
        return bad
