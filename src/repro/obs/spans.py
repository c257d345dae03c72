"""Structured spans: one low-overhead, thread-safe tracing API, two sinks.

A ``Span`` is one timed region of the host program: the planning path
(``PlannerService.plan`` -> store lookup -> policy resolve -> MCTS
playouts with expand / featurize / gnn_forward / simulate sub-spans) and
the pipeline engine (``pipeline.step`` -> one ``pipeline.F`` /
``pipeline.B`` / ``pipeline.W`` per schedule event with nested
``pipeline.transfer``s, ``pipeline.sync`` around each blocking
device-to-host read, ``step.optimizer`` per stage; see
``docs/observability.md`` for the full list).

A span goes to either or both of two sinks:

  * the in-memory ``Tracer`` (opt-in, ``tracer.enable()``; ``--trace-dir``
    turns it on), which keeps ``perf_counter`` times and renders Chrome
    trace events;
  * a ``jax.profiler.TraceAnnotation`` whenever a profiler session is
    active (``jax.profiler.trace``), so the span lands in the
    ``.xplane.pb`` on the device ops' clock, with its args as stats.

With neither sink on, ``span()`` returns a shared no-op context manager:
no allocation, no clock read, one attribute read and one profiler-state
check. Spans nest per thread (each thread keeps its own open-span stack)
and finished spans are appended under a lock, so concurrent planners
share one tracer:

    from repro.obs import get_tracer
    tr = get_tracer()
    tr.enable()
    with tr.span("plan", cat="planner", model="bert_small"):
        ...
    events = tr.to_chrome()           # chrome://tracing JSON events

``to_chrome`` renders spans in the same Chrome trace-event format as
``obs.trace`` renders schedule timelines, so planner spans and pipeline
timelines open in one viewer. Span args that reach the profiler should
be strings or numbers.
"""
from __future__ import annotations

from dataclasses import dataclass, field
import threading
import time

from jax.profiler import TraceAnnotation

_profiling = TraceAnnotation.is_enabled     # a profiler session is active


@dataclass
class Span:
    """One finished timed region. Times are seconds relative to the
    tracer epoch; ``tid`` is a dense per-thread track id."""
    name: str
    cat: str
    start: float
    end: float
    tid: int
    depth: int
    args: dict = field(default_factory=dict)

    @property
    def dur(self) -> float:
        return self.end - self.start


class _NullSpan:
    """Reusable, re-entrant no-op context manager (disabled tracer)."""

    __slots__ = ()

    def __enter__(self):
        return None

    def __exit__(self, *exc):
        return False


_NULL_SPAN = _NullSpan()


class _SpanCtx:
    """A span the tracer records; also a profiler annotation while a
    profiler session is active."""

    __slots__ = ("tracer", "name", "cat", "args", "_t0", "_ann")

    def __init__(self, tracer, name, cat, args):
        self.tracer = tracer
        self.name = name
        self.cat = cat
        self.args = args
        self._ann = None

    def __enter__(self):
        if _profiling():
            self._ann = TraceAnnotation(self.name, **self.args)
            self._ann.__enter__()
        self._t0 = self.tracer._push()
        return self

    def __exit__(self, *exc):
        self.tracer._pop(self.name, self.cat, self._t0, self.args)
        if self._ann is not None:
            self._ann.__exit__(*exc)
            self._ann = None
        return False


class _ThreadState(threading.local):
    def __init__(self):
        self.depth = 0
        self.tid = None


class Tracer:
    """Thread-safe span recorder. A disabled tracer records nothing; its
    ``span()`` is a profiler annotation during a profiler session and a
    no-op otherwise."""

    def __init__(self, *, enabled: bool = False, max_spans: int = 200_000):
        self.enabled = enabled
        self.max_spans = max_spans
        self._epoch = time.perf_counter()
        self._spans: list = []
        self._lock = threading.Lock()
        self._local = _ThreadState()
        self._tids: dict = {}              # thread ident -> dense tid
        self.dropped = 0

    @property
    def epoch(self) -> float:
        """``time.perf_counter()`` reading that span-relative times are
        measured from (lets exporters recover monotonic timestamps)."""
        return self._epoch

    # ------------------------------------------------------------- control
    def enable(self):
        self.enabled = True
        return self

    def disable(self):
        self.enabled = False
        return self

    def clear(self):
        with self._lock:
            self._spans = []
            self.dropped = 0
            self._epoch = time.perf_counter()

    # --------------------------------------------------------------- spans
    def span(self, name: str, cat: str = "planner", **args):
        """Context manager timing one region: recorded when the tracer is
        enabled, annotated on the profiler's clock while a profiler
        session is active, a no-op otherwise."""
        return self._span(name, cat, args)

    def _span(self, name: str, cat: str, args: dict):
        if self.enabled:
            return _SpanCtx(self, name, cat, args)
        if _profiling():
            return TraceAnnotation(name, **args)
        return _NULL_SPAN

    def _tid(self) -> int:
        st = self._local
        if st.tid is None:
            ident = threading.get_ident()
            with self._lock:
                st.tid = self._tids.setdefault(ident, len(self._tids))
        return st.tid

    def _push(self) -> float:
        self._local.depth += 1
        return time.perf_counter()

    def _pop(self, name, cat, t0, args):
        t1 = time.perf_counter()
        st = self._local
        depth = st.depth - 1
        st.depth = depth
        sp = Span(name=name, cat=cat, start=t0 - self._epoch,
                  end=t1 - self._epoch, tid=self._tid(), depth=depth,
                  args=args)
        with self._lock:
            if len(self._spans) < self.max_spans:
                self._spans.append(sp)
            else:
                self.dropped += 1

    def spans(self) -> list:
        with self._lock:
            return list(self._spans)

    def __len__(self):
        with self._lock:
            return len(self._spans)

    # ------------------------------------------------------------- summary
    def summary(self) -> dict:
        """Per-(cat, name) totals: count and summed seconds."""
        out: dict = {}
        for sp in self.spans():
            key = f"{sp.cat}/{sp.name}"
            agg = out.setdefault(key, {"count": 0, "total_s": 0.0})
            agg["count"] += 1
            agg["total_s"] += sp.dur
        return out

    def to_chrome(self, *, pid: int = 0, process_name: str = "planner",
                  time_scale: float = 1e6) -> list:
        """Chrome trace-event JSON events (``ph: "X"`` complete events,
        microsecond timestamps) for all finished spans."""
        events = [{
            "name": "process_name", "ph": "M", "pid": pid, "tid": 0,
            "args": {"name": process_name},
        }]
        tids = sorted({sp.tid for sp in self.spans()})
        for t in tids:
            events.append({
                "name": "thread_name", "ph": "M", "pid": pid, "tid": t,
                "args": {"name": f"thread {t}"}})
        for sp in self.spans():
            events.append({
                "name": sp.name, "cat": sp.cat, "ph": "X",
                "ts": sp.start * time_scale,
                "dur": max(sp.dur, 0.0) * time_scale,
                "pid": pid, "tid": sp.tid,
                "args": dict(sp.args, depth=sp.depth),
            })
        return events


_GLOBAL = Tracer(enabled=False)


def get_tracer() -> Tracer:
    """The process-global tracer (disabled until ``.enable()``)."""
    return _GLOBAL


def set_tracer(tracer: Tracer) -> Tracer:
    """Swap the process-global tracer (tests); returns the old one."""
    global _GLOBAL
    old, _GLOBAL = _GLOBAL, tracer
    return old


def span(name: str, cat: str = "planner", **args):
    """``get_tracer().span(...)`` shorthand for instrumented call sites."""
    return _GLOBAL._span(name, cat, args)


def export_tracer_metrics(registry, tracer: Tracer | None = None):
    """Mirror a tracer's drop/buffer state into a metrics registry.

    ``tracer_dropped_spans_total`` counts spans silently discarded at
    the ``max_spans`` cap — the one failure mode of the span layer that
    is otherwise invisible. The counter is advanced by the delta since
    the last export (a swapped/cleared tracer resets its ``dropped``;
    the registry counter stays monotonic, as counters must). Also sets
    ``tracer_buffered_spans`` and ``tracer_enabled`` gauges. Returns the
    counter.
    """
    tr = tracer if tracer is not None else _GLOBAL
    c = registry.counter(
        "tracer_dropped_spans_total",
        "spans dropped at the tracer max_spans cap")
    delta = tr.dropped - c.value()
    if delta > 0:
        c.inc(delta)
    registry.gauge(
        "tracer_buffered_spans",
        "finished spans buffered in the tracer").set(float(len(tr)))
    registry.gauge(
        "tracer_enabled",
        "1 when the span tracer records").set(1.0 if tr.enabled else 0.0)
    return c
