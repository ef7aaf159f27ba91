"""Structured engine tracing: nestable spans, counters, instant events.

The serving engine's user-transparency promise cuts both ways — users never
see the runtime, but operators must be able to see *inside* it.  The
TensorFlow whitepaper leans on built-in timeline tracing (EEG) to attribute
step time; this module is that capability for the serving stack: every
engine cycle decomposes into phase spans (preemption check, admission,
host planning, dispatch, waiting on the device at retire), every request
gets a lifecycle track (queued -> prefill chunks -> decode -> complete),
and the page pool emits cache events (alloc, COW, ring rotation, LRU
traffic, prefix hit/miss).

Two sinks, one span API:

  * **the engine's own ring** — opt-in (``ServeConfig(trace=True)``): a
    :class:`Tracer` per engine, bounded (``capacity``, default 64Ki; when
    full the *oldest* events drop and ``dropped`` counts them), on an
    injectable clock (``Tracer(clock=...)``, the pattern ``ServingMetrics``
    uses) so tests drive exact timelines.  ``save_trace`` and ``--trace``
    export it.
  * **the profiler's session** — whatever ``ServeConfig.trace`` says, while
    a JAX profiler session records (``jax.profiler.start_trace``), every
    lexical span is also a ``jax.profiler.TraceAnnotation`` named
    ``serve.<span>`` (so it lands on the device trace's own clock, beside
    the ops it dispatched), and spans, instants and counters also go into
    the process-wide bounded record :func:`profiled` — as the profiler's
    own TraceMe recorder keeps what a session captured.  Its timestamps are
    ``time.perf_counter_ns()``, which the profile's clock is a constant
    offset from.  It holds what was recorded since its last ``clear()``.

Cost when off: tracing is never fenced (a traced engine dispatches exactly
like an untraced one), and with ``trace=False`` and no profiler session
the disabled path is :data:`NULL_TRACER`: each emit costs one
``TraceMe.is_enabled()`` check, and ``span()`` returns one shared
context-manager singleton — no allocation, no clock read.

Span APIs:

  * ``with tracer.span("decode.dispatch"): ...`` — lexically scoped phases
    (the engine loop).  Nesting is just lexical nesting; the exporter
    renders it as stacked slices.
  * ``tracer.begin("decode", track=...)`` / ``tracer.end("decode",
    track=...)`` — spans that open and close in *different* engine cycles
    (a request's queued / prefill / decode lifecycle).  ``end`` of a span
    that is not open is a silent no-op (returns False), so preemption
    paths can close "whichever of prefill/decode is open" without
    bookkeeping; balance is checked via :meth:`Tracer.open_spans`.  They
    need per-engine open-span state, so they go to the engine's ring only;
    the profiled record carries the lifecycle's instants
    (``request.admit`` / ``request.first_token`` / ``request.complete``),
    whose args hold the durations.

Per-phase attribution: every closed span accumulates into
``phase_seconds[name]`` / ``phase_counts[name]`` *for the engine track
only* — per-request spans overlap engine phases wall-clock-wise and would
double count.  ``repro.obs.export.phase_snapshot`` flattens those totals
into the dict ``ServingMetrics.summary()`` merges.

Events are stored as plain tuples ``(ph, name, track, ts, value, args)``
with Chrome trace-event phase codes (``"X"`` complete span with
``value=duration``, ``"i"`` instant, ``"C"`` counter with
``value=counter``); ``repro.obs.export`` turns a ring into a
Perfetto-loadable Chrome trace JSON.  The ring's ``ts`` and durations are
seconds on the tracer's clock; the profiled record's are integer
nanoseconds of ``time.perf_counter_ns()``.
"""
from __future__ import annotations

import time
from collections import deque
from typing import Any, Callable, Dict, List, Optional, Tuple

from jax.profiler import TraceAnnotation

# The profiler's own TraceMe: ``is_enabled()`` is true only while a
# profiler session records.  The one import of it in the program.
from jax._src.lib import _profiler

#: True while a JAX profiler session records (0.1-0.2 us per call)
profiling: Callable[[], bool] = _profiler.TraceMe.is_enabled

#: the engine-loop track; phase attribution accumulates spans on it only
ENGINE_TRACK = "engine"

#: prefix of the TraceAnnotation each engine span becomes in a profiler
#: session
ANNOTATION_PREFIX = "serve."

#: the training loop's track; its spans and counters carry their full
#: ``train.*`` names
TRAIN_TRACK = "train"

#: event tuple layout (ph, name, track, ts, value, args) — ph follows the
#: Chrome trace-event phase codes so the exporter is a dumb transcription
Event = Tuple[str, str, str, float, float, Optional[Dict[str, Any]]]


class Profiled:
    """The process-wide record of what the serving stack emitted while a
    profiler session ran: ``events`` in the ring's tuple layout with
    ``time.perf_counter_ns()`` timestamps (span durations in ns too),
    bounded at ``capacity`` (oldest dropped, ``dropped`` counts them).  It
    keeps what was recorded since the last :meth:`clear`, so a reader of
    one session clears it before that session starts."""

    def __init__(self, capacity: int = 1 << 16):
        self.events: deque = deque(maxlen=capacity)
        self.dropped = 0

    def push(self, ev: Event) -> None:
        if len(self.events) == self.events.maxlen:
            self.dropped += 1
        self.events.append(ev)

    def clear(self) -> None:
        self.events.clear()
        self.dropped = 0


_PROFILED = Profiled()


def profiled() -> Profiled:
    """The process-wide record of what was emitted during profiler
    sessions (see :class:`Profiled`)."""
    return _PROFILED


def _record_profiled(ph: str, name: str, track: str, value: float,
                     args: Optional[Dict[str, Any]]) -> None:
    """An instant or counter into :func:`profiled`, while a profiler
    session records."""
    if profiling():
        _PROFILED.push((ph, name, track, time.perf_counter_ns(), value, args))


class _SpanCtx:
    """Lexically scoped span (``with tracer.span(...)``): into the tracer's
    ring when it records one, and — while a profiler session records — a
    ``serve.<name>`` TraceAnnotation plus an event in :func:`profiled`."""

    __slots__ = ("_tr", "_name", "_track", "_args", "_t0", "_ann", "_p0")

    def __init__(self, tr, name: str, track: str,
                 args: Optional[Dict[str, Any]]):
        self._tr = tr
        self._name = name
        self._track = track
        self._args = args

    def __enter__(self) -> "_SpanCtx":
        if profiling():
            self._ann = TraceAnnotation(self._tr.annotation_prefix
                                        + self._name)
            self._ann.__enter__()
            self._p0 = time.perf_counter_ns()
        else:
            self._ann = None
        if self._tr.enabled:
            self._t0 = self._tr._clock()
        return self

    def __exit__(self, *exc) -> bool:
        tr = self._tr
        if tr.enabled:
            tr._span(self._name, self._track, self._t0, tr._clock(),
                     self._args)
        if self._ann is not None:
            _PROFILED.push(("X", self._name, self._track, self._p0,
                            time.perf_counter_ns() - self._p0, self._args))
            self._ann.__exit__(None, None, None)
        return False


class Tracer:
    """Bounded-ring span/counter/instant recorder with per-phase totals
    (and, during a profiler session, the gated sink besides)."""

    enabled = True
    annotation_prefix = ANNOTATION_PREFIX

    def __init__(self, *, clock: Optional[Callable[[], float]] = None,
                 capacity: int = 1 << 16,
                 meta: Optional[Dict[str, Any]] = None):
        assert capacity >= 1, capacity
        self._clock = clock or time.perf_counter
        self.capacity = capacity
        self.meta: Dict[str, Any] = dict(meta or {})
        self.reset()

    def reset(self) -> None:
        """Drop every event and phase total (benchmarks reuse warm engines;
        the clock, capacity and meta survive)."""
        self.events: deque = deque()
        self.dropped = 0
        self._open: Dict[Tuple[str, str], Tuple[float,
                                                Optional[Dict[str, Any]]]] = {}
        self.phase_seconds: Dict[str, float] = {}
        self.phase_counts: Dict[str, int] = {}
        self.t0 = self._clock()

    def now(self) -> float:
        return self._clock()

    # -- recording ---------------------------------------------------------

    def _push(self, ev: Event) -> None:
        if len(self.events) >= self.capacity:
            self.events.popleft()
            self.dropped += 1
        self.events.append(ev)

    def _span(self, name: str, track: str, t0: float, t1: float,
              args: Optional[Dict[str, Any]]) -> None:
        self._push(("X", name, track, t0, t1 - t0, args))
        if track == ENGINE_TRACK:
            self.phase_seconds[name] = \
                self.phase_seconds.get(name, 0.0) + (t1 - t0)
            self.phase_counts[name] = self.phase_counts.get(name, 0) + 1

    def span(self, name: str, track: str = ENGINE_TRACK,
             **args: Any) -> _SpanCtx:
        """Lexically scoped span; nest freely (``with`` blocks)."""
        return _SpanCtx(self, name, track, args or None)

    def begin(self, name: str, track: str = ENGINE_TRACK,
              **args: Any) -> None:
        """Open a cross-cycle span.  Re-opening an already open (track,
        name) closes the stale one first (balance over silent leaks)."""
        key = (track, name)
        stale = self._open.pop(key, None)
        if stale is not None:
            self._span(name, track, stale[0], self._clock(),
                       dict(stale[1] or {}, reopened=True))
        self._open[key] = (self._clock(), args or None)

    def end(self, name: str, track: str = ENGINE_TRACK, **args: Any) -> bool:
        """Close a cross-cycle span; False (and no event) when it is not
        open — callers may unconditionally close alternatives."""
        o = self._open.pop((track, name), None)
        if o is None:
            return False
        merged = dict(o[1] or {})
        merged.update(args)
        self._span(name, track, o[0], self._clock(), merged or None)
        return True

    def instant(self, name: str, track: str = ENGINE_TRACK,
                **args: Any) -> None:
        """Point event (page alloc, COW, preemption, compile, ...)."""
        self._push(("i", name, track, self._clock(), 0.0, args or None))
        _record_profiled("i", name, track, 0, args or None)

    def counter(self, name: str, value: float,
                track: str = ENGINE_TRACK) -> None:
        """Sampled counter series (queue depth, pages held, ...)."""
        self._push(("C", name, track, self._clock(), float(value), None))
        _record_profiled("C", name, track, float(value), None)

    # -- inspection --------------------------------------------------------

    def open_spans(self) -> List[Tuple[str, str]]:
        """(track, name) of every begin() without a matching end() — the
        balance tests assert this drains to [] when the engine drains."""
        return sorted(self._open)

    def close_all(self, **args: Any) -> int:
        """Close every open cross-cycle span (export hygiene for traces
        snapshotted mid-flight); returns how many were closed."""
        n = 0
        for track, name in list(self._open):
            self.end(name, track=track, **args)
            n += 1
        return n


class _NullSpan:
    """The shared no-op context manager ``NULL_TRACER.span`` hands out."""

    __slots__ = ()

    def __enter__(self) -> "_NullSpan":
        return self

    def __exit__(self, *exc) -> bool:
        return False


_NULL_SPAN = _NullSpan()
_EMPTY_DICT: Dict[str, Any] = {}


class NullTracer:
    """Ring-less twin of :class:`Tracer` — the disabled hot path, gated on
    the profiler.

    With no profiler session every method returns after one
    ``TraceMe.is_enabled()`` check, reading no clock and recording
    nothing; ``span()`` returns one module-level singleton context
    manager.  During a session its spans, instants and counters go to the
    profiler and :func:`profiled` as :class:`Tracer`'s do.  ``events`` /
    ``phase_seconds`` present the empty shapes so consumers (metrics
    merge, exporters) need no enabled-check branches.
    """

    enabled = False
    events: Tuple[Event, ...] = ()
    dropped = 0
    capacity = 0
    t0 = 0.0
    meta = _EMPTY_DICT
    phase_seconds: Dict[str, float] = _EMPTY_DICT
    phase_counts: Dict[str, int] = _EMPTY_DICT

    def __init__(self, annotation_prefix: str = ANNOTATION_PREFIX):
        self.annotation_prefix = annotation_prefix

    def reset(self) -> None:
        pass

    def now(self) -> float:
        return 0.0

    def span(self, name: str, track: str = ENGINE_TRACK, **args: Any):
        if profiling():
            return _SpanCtx(self, name, track, args or None)
        return _NULL_SPAN

    def begin(self, name: str, track: str = ENGINE_TRACK,
              **args: Any) -> None:
        pass

    def end(self, name: str, track: str = ENGINE_TRACK, **args: Any) -> bool:
        return False

    def instant(self, name: str, track: str = ENGINE_TRACK,
                **args: Any) -> None:
        _record_profiled("i", name, track, 0, args or None)

    def counter(self, name: str, value: float,
                track: str = ENGINE_TRACK) -> None:
        _record_profiled("C", name, track, float(value), None)

    def open_spans(self) -> List[Tuple[str, str]]:
        return []

    def close_all(self, **args: Any) -> int:
        return 0


#: the one NullTracer every disabled engine shares
NULL_TRACER = NullTracer()

#: the training loop's tracer (``Session.train``): it records only while a
#: profiler session does, and its span names are the annotations' names
TRAIN_TRACER = NullTracer(annotation_prefix="")


def request_track(rid: int) -> str:
    """Track name of one request's lifecycle spans (one Perfetto row per
    request, per the whitepaper-style timeline view)."""
    return f"req{rid}"
