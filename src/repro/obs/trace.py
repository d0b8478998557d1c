"""Request tracing: nestable spans, a bounded ring of recent traces, and
Chrome trace-event export — zero dependencies beyond the stdlib.

The primitives:

* :class:`Span` — one named interval with attributes and a parent.
* :class:`Trace` — one request's (or flush's) span tree. Spans nest via
  a per-thread stack, so a trace that crosses threads (admitted on an
  HTTP handler thread, executed on the flush worker) still parents
  correctly on each side. ``add_span`` records an interval with explicit
  start/end times (queue wait is known only in hindsight).
* :class:`Tracer` — clock + bounded ring buffer (``deque(maxlen=...)``)
  of recently *ended* traces, exported as Chrome trace-event JSON
  (:meth:`Tracer.chrome_trace`) loadable in ``chrome://tracing`` or
  Perfetto.

Instrumentation points DO NOT thread tracer handles through every
signature. Instead the executing layer (the flush worker, a benchmark
harness) *attaches* an observation context — ``with attach(trace,
profiler): ...`` — and deep layers call ``with stage("planner.probe"):``
which records into whatever is attached. When nothing is attached,
``stage()`` returns a shared no-op context manager: the disabled cost is
one thread-local attribute read and a truthiness check, measured ≤5%
end-to-end by the serving bench's tracing gate. An attached stage also
holds a ``jax.profiler.TraceAnnotation`` of its name, so the stage shows
on the device trace's clock whenever a profiler session runs.

Observability must never cost an answer: a raise inside a stage's own
bookkeeping, or inside code run under :func:`contained`, is counted
(:func:`faults`) and dropped.
"""

from __future__ import annotations

import contextlib
import itertools
import logging
import threading
import time
from collections import deque
from typing import Callable

__all__ = [
    "Span", "Trace", "Tracer", "NULL_TRACER",
    "attach", "current_trace", "current_profiler", "stage", "tally",
    "chrome_events", "contained", "faults",
]

log = logging.getLogger(__name__)


def _jsonable(v):
    """Attrs must survive ``json.dumps``: keep native scalars, stringify
    the rest (numpy ints, arrays, dataclasses)."""
    if isinstance(v, bool) or v is None or isinstance(v, (str, int, float)):
        return v
    try:
        import numpy as np

        if isinstance(v, np.integer):
            return int(v)
        if isinstance(v, np.floating):
            return float(v)
    except Exception:  # pragma: no cover - numpy always present here
        pass
    return str(v)


class Span:
    """One named interval. ``end`` is None while the span is open."""

    __slots__ = ("name", "start", "end", "attrs", "parent")

    def __init__(self, name: str, start: float, parent: "Span | None" = None,
                 attrs: dict | None = None):
        self.name = name
        self.start = start
        self.end: float | None = None
        self.parent = parent
        self.attrs = {k: _jsonable(v) for k, v in (attrs or {}).items()}

    @property
    def duration(self) -> float:
        return (self.end - self.start) if self.end is not None else 0.0

    def set(self, **attrs) -> "Span":
        for k, v in attrs.items():
            self.attrs[k] = _jsonable(v)
        return self


class _SpanCtx:
    """Context manager closing one span (returned by ``Trace.span``)."""

    __slots__ = ("_trace", "span")

    def __init__(self, trace: "Trace", span: Span):
        self._trace = trace
        self.span = span

    def set(self, **attrs):
        self.span.set(**attrs)
        return self

    def __enter__(self):
        return self

    def __exit__(self, exc_type, exc, tb):
        if exc_type is not None:
            self.span.attrs["error"] = exc_type.__name__
        self._trace._close(self.span)
        return False


_trace_ids = itertools.count(1)


class Trace:
    """One span tree. Thread-safe: spans may be added from any thread;
    nesting follows each thread's own open-span stack (cross-thread
    spans parent on the root)."""

    def __init__(self, tracer: "Tracer", name: str, attrs: dict | None = None):
        self.tracer = tracer
        self.trace_id = next(_trace_ids)
        self._lock = threading.Lock()
        self._tls = threading.local()
        self.root = Span(name, tracer.clock(), attrs=attrs)
        self.spans: list[Span] = [self.root]
        self.ended = False

    # -- span plumbing -----------------------------------------------------

    def _stack(self) -> list:
        st = getattr(self._tls, "stack", None)
        if st is None:
            st = self._tls.stack = []
        return st

    def span(self, name: str, **attrs) -> _SpanCtx:
        """Open a nested span (context manager). Parent = the calling
        thread's innermost open span, else the root."""
        stack = self._stack()
        parent = stack[-1] if stack else self.root
        s = Span(name, self.tracer.clock(), parent=parent, attrs=attrs)
        with self._lock:
            self.spans.append(s)
        stack.append(s)
        return _SpanCtx(self, s)

    def _close(self, span: Span):
        span.end = self.tracer.clock()
        stack = self._stack()
        if stack and stack[-1] is span:
            stack.pop()

    def add_span(self, name: str, start: float, end: float, **attrs) -> Span:
        """Record an already-elapsed interval (e.g. queue wait, measured
        between two events the span API never bracketed)."""
        s = Span(name, start, parent=self.root, attrs=attrs)
        s.end = end
        with self._lock:
            self.spans.append(s)
        return s

    def set(self, **attrs) -> "Trace":
        self.root.set(**attrs)
        return self

    def end(self, **attrs) -> "Trace":
        """Close the root and push the finished trace into the tracer's
        ring buffer. Idempotent."""
        if self.ended:
            return self
        self.ended = True
        self.root.set(**attrs)
        self.root.end = self.tracer.clock()
        self.tracer._record(self)
        return self

    @property
    def duration(self) -> float:
        return self.root.duration


class Tracer:
    """Bounded ring of recent traces + the clock every span reads.

    ``capacity`` bounds memory: the ring keeps the most recent
    ``capacity`` *ended* traces (old ones fall off the left). The clock
    is injectable for deterministic tests.
    """

    enabled = True

    def __init__(self, capacity: int = 64,
                 clock: Callable[[], float] = time.monotonic):
        self.capacity = int(capacity)
        self.clock = clock
        self.traces_started = 0
        self.traces_ended = 0
        self._ring: deque[Trace] = deque(maxlen=self.capacity)
        self._lock = threading.Lock()

    def begin(self, name: str, **attrs) -> Trace:
        self.traces_started += 1
        return Trace(self, name, attrs=attrs)

    def _record(self, trace: Trace) -> None:
        with self._lock:
            self.traces_ended += 1
            self._ring.append(trace)

    def recent(self, n: int | None = None) -> list[Trace]:
        with self._lock:
            out = list(self._ring)
        return out if n is None else out[-int(n):]

    def clear(self) -> None:
        with self._lock:
            self._ring.clear()

    def chrome_trace(self, n: int | None = None) -> dict:
        """Chrome trace-event JSON ({"traceEvents": [...]}) of the ring's
        recent traces — load in ``chrome://tracing`` or ui.perfetto.dev.
        Each trace renders on its own thread row (tid = trace id)."""
        events = []
        for t in self.recent(n):
            events.extend(chrome_events(t))
        return {"traceEvents": events, "displayTimeUnit": "ms"}


class _NullSpanCtx:
    """Shared no-op for the disabled path — also the ``stage()`` no-op."""

    __slots__ = ()

    def set(self, **attrs):
        return self

    def sync(self, x):
        return x

    def __enter__(self):
        return self

    def __exit__(self, *exc):
        return False


_NULL_CTX = _NullSpanCtx()


class _NullTrace:
    trace_id = 0
    ended = True
    duration = 0.0
    spans: list = []

    def span(self, name, **attrs):
        return _NULL_CTX

    def add_span(self, name, start, end, **attrs):
        return None

    def set(self, **attrs):
        return self

    def end(self, **attrs):
        return self


class NullTracer(Tracer):
    """Tracing disabled: ``begin`` hands back a shared inert trace and
    nothing is ever retained. All methods are allocation-free."""

    enabled = False

    def __init__(self):
        super().__init__(capacity=1)
        self._null = _NullTrace()

    def begin(self, name: str, **attrs) -> Trace:
        return self._null  # type: ignore[return-value]

    def recent(self, n: int | None = None) -> list[Trace]:
        return []

    def chrome_trace(self, n: int | None = None) -> dict:
        return {"traceEvents": [], "displayTimeUnit": "ms"}


NULL_TRACER = NullTracer()


def chrome_events(trace: Trace) -> list[dict]:
    """One trace → Chrome "X" (complete) events, µs timestamps."""
    events = []
    for s in trace.spans:
        end = s.end if s.end is not None else s.start
        events.append({
            "name": s.name,
            "ph": "X",
            "ts": round(s.start * 1e6, 3),
            "dur": round((end - s.start) * 1e6, 3),
            "pid": 0,
            "tid": trace.trace_id,
            "args": dict(s.attrs),
        })
    return events


# ---------------------------------------------------------------------------
# Observation context: the executing layer attaches (trace, profiler);
# deep layers record stages without threading handles through signatures.
# ---------------------------------------------------------------------------

_ctx = threading.local()


def _stack_of_ctx() -> list:
    st = getattr(_ctx, "stack", None)
    if st is None:
        st = _ctx.stack = []
    return st


class attach:
    """``with attach(trace, profiler): ...`` — activate an observation
    context on this thread. Either handle may be None; attaching
    (None, None) is a no-op context."""

    __slots__ = ("trace", "profiler", "_pushed")

    def __init__(self, trace: Trace | None = None, profiler=None):
        self.trace = trace
        self.profiler = profiler
        self._pushed = False

    def __enter__(self):
        if self.trace is not None or self.profiler is not None:
            _stack_of_ctx().append((self.trace, self.profiler))
            self._pushed = True
        return self

    def __exit__(self, *exc):
        if self._pushed:
            _stack_of_ctx().pop()
        return False


def current_trace() -> Trace | None:
    st = getattr(_ctx, "stack", None)
    return st[-1][0] if st else None


def current_profiler():
    st = getattr(_ctx, "stack", None)
    return st[-1][1] if st else None


# Process-wide count of contained observability faults: stages run on
# every thread and inside any library call, so the count cannot belong
# to one server object. Read it as a delta where that matters.
_faults = 0
_faults_lock = threading.Lock()


def faults() -> int:
    """Observability faults contained so far in this process."""
    return _faults


@contextlib.contextmanager
def contained():
    """Run observability code whose raise must not reach the caller: the
    raise is logged at debug level, counted in :func:`faults`, and
    dropped."""
    global _faults
    try:
        yield
    except Exception:
        log.debug("observability fault contained", exc_info=True)
        with _faults_lock:
            _faults += 1


_trace_annotation = None


def _annotation(name: str):
    """An un-entered ``jax.profiler.TraceAnnotation(name)``; jax is
    imported on first use so this module stays importable without it."""
    global _trace_annotation
    if _trace_annotation is None:
        from jax.profiler import TraceAnnotation

        _trace_annotation = TraceAnnotation
    return _trace_annotation(name)


class _Stage:
    """Times one stage into the attached trace span AND the attached
    profiler histogram, and holds a profiler annotation of its name
    (entered and exited on the calling thread; attributes stay in the
    trace). ``sync(x)`` blocks on a device value so the stage's wall
    time covers its device work (identity off-context)."""

    __slots__ = ("name", "attrs", "_trace", "_prof", "_span", "_t0", "_ann")

    def __init__(self, trace, prof, name, attrs):
        self.name = name
        self.attrs = attrs
        self._trace = trace
        self._prof = prof
        self._span = None
        self._ann = None

    def set(self, **attrs):
        if self._span is not None:
            self._span.set(**attrs)
        return self

    def sync(self, x):
        try:
            import jax

            jax.block_until_ready(x)
        except Exception:
            pass
        return x

    def __enter__(self):
        self._t0 = (self._trace.tracer.clock() if self._trace is not None
                    else time.perf_counter())
        with contained():
            if self._trace is not None:
                self._span = self._trace.span(self.name, **self.attrs).span
        with contained():
            ann = _annotation(self.name)
            ann.__enter__()
            self._ann = ann
        return self

    def __exit__(self, exc_type, exc, tb):
        if self._ann is not None:
            with contained():
                self._ann.__exit__(exc_type, exc, tb)
        with contained():
            if self._trace is not None:
                now = self._trace.tracer.clock()
                if self._span is not None:
                    if exc_type is not None:
                        self._span.attrs["error"] = exc_type.__name__
                    self._trace._close(self._span)
            else:
                now = time.perf_counter()
            if self._prof is not None:
                self._prof.observe(self.name, max(now - self._t0, 0.0))
        return False


def stage(name: str, **attrs):
    """Record one named stage into the active observation context.

    The hot-path contract: with nothing attached this returns a SHARED
    no-op context manager — no allocation, no clock read — so
    instrumented library code costs one thread-local read when
    observability is off.
    """
    st = getattr(_ctx, "stack", None)
    if not st:
        return _NULL_CTX
    trace, prof = st[-1]
    return _Stage(trace, prof, name, attrs)


def tally(name: str, amount: float = 1.0) -> None:
    """Count one event of ``name`` into the attached profiler: a
    histogram whose count is the events and whose sum is ``amount``
    over them (a route's batches and their queries, a probe's blocks).
    A no-op with nothing attached; a raise is contained."""
    st = getattr(_ctx, "stack", None)
    if not st or st[-1][1] is None:
        return
    with contained():
        st[-1][1].tally(name, amount)
