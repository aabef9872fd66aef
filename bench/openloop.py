"""The open-loop client: submits each request when it is due, on the wall
clock, and steps the engine; records what a client would see.

Each iteration submits every request now due, then calls `Engine.step()`;
it sleeps only when the engine is idle.  Tokens are stamped on the host
after the step in which they materialize.  The engine's executor is
wrapped (not changed) so that each dispatch is logged with its rows and
shapes, and, in a traced run, annotated as a host span.
"""
from __future__ import annotations

import contextlib
import time
from dataclasses import dataclass, field

COMPILE_EVENT = "/jax/core/compile/backend_compile_duration"
CACHE_MISS_EVENT = "/jax/compilation_cache/cache_misses"


class CompileCounter:
    """Counts executables built (compiled or loaded from the persistent
    cache) and persistent-cache misses (real compiles), process-wide."""

    def __init__(self):
        import jax

        self.built = 0
        self.misses = 0
        jax.monitoring.register_event_duration_secs_listener(self._on_duration)
        jax.monitoring.register_event_listener(self._on_event)

    def _on_duration(self, event, duration, **_):
        if event == COMPILE_EVENT:
            self.built += 1

    def _on_event(self, event, **_):
        if event == CACHE_MISS_EVENT:
            self.misses += 1

    def snapshot(self) -> tuple[int, int]:
        return self.built, self.misses


@dataclass
class Dispatch:
    kind: str      # prefill | decode
    t: float       # host clock when dispatched
    rows: int      # batch rows the programs ran (dummy rows included)
    live: int      # rows that carry a request
    length: int    # prefill: prompt length; decode: positions attended


class Recorder:
    """Wraps the engine's executor stages: logs dispatches, and in a
    traced run names each stage as a host span in the profiler's trace."""

    STAGES = ("merge", "retire", "encode", "decode_cohort")

    def __init__(self, engine, annotate: bool = False):
        import jax

        self.log: list[Dispatch] = []
        self.annotate = annotate
        self._ann = jax.profiler.TraceAnnotation
        ex = engine.executor
        align = engine.batch_align
        prefill, dispatch_decode = ex.prefill, ex._dispatch_decode

        def prefill_logged(group):
            n = len(group)
            self.log.append(Dispatch("prefill", time.perf_counter(),
                                     n + (-n) % align, n,
                                     max(r.prompt_len for r in group)))
            with self.span("prefill"):
                return prefill(group)

        def decode_logged(cohort):
            n = len(cohort.slots)
            self.log.append(Dispatch("decode", time.perf_counter(),
                                     n + cohort.n_dummy, n, cohort.length + 1))
            with self.span("decode"):
                return dispatch_decode(cohort)

        ex.prefill = prefill_logged
        ex._dispatch_decode = decode_logged
        for name in self.STAGES:
            setattr(ex, name, self._spanned(name, getattr(ex, name)))

    def span(self, name: str):
        return self._ann(f"bench.{name}") if self.annotate else contextlib.nullcontext()

    def _spanned(self, name, fn):
        def wrapped(*a, **k):
            with self.span(name):
                return fn(*a, **k)
        return wrapped


@dataclass
class Window:
    """What one measured window saw."""

    t0: float
    end: float
    requests: list
    stop_t: float = 0.0                 # when the loop ended (drain included)
    counters0: dict = field(default_factory=dict)
    counters1: dict = field(default_factory=dict)
    compiles_in_window: int = 0
    cache_misses_in_window: int = 0
    steps: int = 0
    outstanding: list = field(default_factory=list)  # (t - t0, n) samples
    trace_span: tuple | None = None     # (start, stop) host clock of the trace


ENGINE_COUNTERS = ("n_decode_rows", "n_decode_batches", "n_prefill_batches",
                   "n_padded_rows")


def _counters(engine) -> dict:
    return {k: getattr(engine.metrics, k) for k in ENGINE_COUNTERS}


def _states(engine) -> dict:
    return {st.rid: st for c in engine.cohorts for st in c.slots}


def drive(engine, requests, seconds: float, *, drain_s: float,
          compiles: CompileCounter | None = None, tracer=None,
          recorder: Recorder | None = None,
          clock=time.perf_counter, sleep=time.sleep) -> Window:
    """Run the open loop for ``seconds``, then step on (no new arrivals are
    due: every request is due inside the window) until every submitted
    request has finished or ``drain_s`` more seconds have passed.

    ``tracer``: optional; ``start_at`` and ``stop_at`` seconds after the
    window opens, at step boundaries, it calls ``start()`` and ``stop()``,
    which return the host clock of the traced span's ends."""
    from repro.serve.scheduler import AdmissionError

    span = recorder.span if recorder is not None else (
        lambda name: contextlib.nullcontext())
    pending = sorted(requests, key=lambda r: r.due)
    pending.reverse()
    live: dict[int, object] = {}
    n_done = 0
    t0 = clock()
    w = Window(t0=t0, end=t0 + seconds, requests=requests)
    w.counters0 = _counters(engine)
    c0 = compiles.snapshot() if compiles else (0, 0)
    closed = False
    tracing = False
    next_sample = 0.0
    while True:
        now = clock()
        if not closed and now >= w.end:
            closed = True
            w.counters1 = _counters(engine)
            c1 = compiles.snapshot() if compiles else (0, 0)
            w.compiles_in_window = c1[0] - c0[0]
            w.cache_misses_in_window = c1[1] - c0[1]
        if tracer is not None:
            if not tracing and w.trace_span is None and now - t0 >= tracer.start_at:
                w.trace_span = (tracer.start(), None)
                tracing = True
            elif tracing and (now - t0 >= tracer.stop_at or closed):
                w.trace_span = (w.trace_span[0], tracer.stop())
                tracing = False
        with span("submit"):
            while pending and t0 + pending[-1].due <= now:
                r = pending.pop()
                r.submit_t = clock()
                try:
                    r.rid = engine.submit(r.prompt, r.max_new).rid
                    live[r.rid] = r
                except AdmissionError:
                    r.refused = True
        if now - t0 >= next_sample:
            w.outstanding.append((now - t0, len(live)))
            next_sample += 0.5
        if closed and (not live or now >= w.end + drain_s):
            break
        if engine.idle:
            with span("idle"):
                wake = t0 + pending[-1].due if pending else w.end
                sleep(max(0.0, min(wake - clock(), 0.05)))
            continue
        with span("step"):
            engine.step()
        w.steps += 1
        t = clock()
        with span("harvest"):
            states = _states(engine)
            for rid in list(live):
                r = live[rid]
                st = states.get(rid) or engine.results.get(rid)
                if st is None:
                    continue
                new = len(st.generated) - len(r.token_t)
                if new > 0:
                    r.token_t.extend([t] * new)
                if st.done and rid in engine.results:
                    r.tokens = [int(x) for x in st.generated]
                    r.done_t = t
                    del live[rid]
                    n_done += 1
    if tracing:
        w.trace_span = (w.trace_span[0], tracer.stop())
    w.stop_t = clock()
    return w
