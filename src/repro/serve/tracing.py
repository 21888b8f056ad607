"""Span-level request tracing: the serving stack's flight recorder.

Aggregate metrics lie in exactly the regime this system serves: work is
proportional to the *cluster*, not the graph, so per-request latency spans
several decades and a p99 histogram cannot say why any individual deadline
was missed — queue wait, EDF planning, tick cost, ladder promotion, or sweep.
This module is the attribution layer: a thread-safe, dependency-free
:class:`Tracer` with a bounded ring-buffer flight recorder that emits a span
tree per request across its full lifecycle

    submit → queued → admitted → injected → tick* → harvest → sweep
           → resolved | expired

plus pool-scoped ``tick`` spans (refill / step / harvest / status_wait /
sweep_dispatch children; occupancy and cost-EMA snapshots at the start, the
tick's edge work at the end), ``compile`` events naming every XLA backend
compile, and algorithm-level annotations threaded up from the batched
layers (per-tick frontier sizes, push counts, capacity-ladder bucket hops,
overflow events, dist exchange volume — the paper-native work measures).

Design rules (docs/algorithms.md, guarantee #8):

  * **Tracing never changes answers.**  Every call site only *reads* state
    the engine already computed (or host numpy the harvest already pulled);
    a traced stream is bit-identical to an untraced one, enforced by
    ``tests/test_tracing.py``.
  * **Disabled means free.**  Engines hold ``tracer=None`` by default and
    guard every site with one ``is not None`` check; the ambient
    :func:`annotate` hook used by the batched drivers early-exits on one
    attribute lookup when no tracer is active.  The no-op cost is measured
    in ``tests/test_tracing.py``.
  * **Bounded.**  Finished spans live in a ``deque(maxlen=capacity)`` ring;
    evictions are counted (``Tracer.dropped``), never silent.  Per-request
    *phase accounting* (:class:`RequestTrace`) is kept separately in O(1)
    per request so latency attribution survives ring eviction.

Timeline view: with ``Tracer(device_annotations=True)`` the engine's tick
and its refill / step / status_wait / sweep_dispatch parts are also
``jax.profiler.TraceAnnotation`` scopes (:meth:`Tracer.device_span`), so a
profiler trace of the serving process (``jax.profiler.trace(dir,
create_perfetto_trace=True)``) shows them on the device trace's clock,
beside the device's executables, in Perfetto (https://ui.perfetto.dev).

On deadline expiry the scheduler dumps the victim's span tree
(:meth:`Tracer.request_tree`) into the telemetry snapshot as a bounded
postmortem (`repro.serve.telemetry.MetricsRegistry.add_postmortem`).
"""
from __future__ import annotations

import contextlib
import threading
import time
import weakref
from collections import deque
from typing import Any, Dict, List, Optional

__all__ = ["Span", "Tracer", "RequestTrace", "annotate", "current_scope",
           "watch_compiles", "TRACE_SCHEMA"]

TRACE_SCHEMA = "repro.serve.trace/v1"

_now = time.monotonic          # one clock for every span (and the scheduler)


class Span:
    """One timed interval (or instant event when ``t1 == t0``).

    ``sid`` is unique per tracer; ``parent`` nests spans; ``rid`` attaches
    the span to one request's tree (None = pool/driver scope).  ``attrs``
    are plain JSON-able values only.
    """

    __slots__ = ("sid", "parent", "rid", "name", "cat", "t0", "t1", "attrs")

    def __init__(self, sid: int, name: str, cat: str, t0: float,
                 parent: Optional[int], rid: Optional[int],
                 attrs: Dict[str, Any]):
        self.sid = sid
        self.name = name
        self.cat = cat
        self.t0 = t0
        self.t1: Optional[float] = None
        self.parent = parent
        self.rid = rid
        self.attrs = attrs

    @property
    def duration_ms(self) -> Optional[float]:
        return None if self.t1 is None else (self.t1 - self.t0) * 1e3

    def to_dict(self) -> Dict[str, Any]:
        return dict(sid=self.sid, parent=self.parent, rid=self.rid,
                    name=self.name, cat=self.cat, t0_ms=self.t0 * 1e3,
                    dur_ms=self.duration_ms, attrs=dict(self.attrs))


# ------------------------------------------------------------ ambient scope
# The batched host drivers (core/batched*.py) annotate ladder dispatches
# without holding a tracer reference: the engine (or any caller) pushes an
# active (tracer, parent span, rid) scope onto this thread-local stack and
# annotate() attaches events under it.  No scope → one attribute lookup and
# return, which is what keeps a disabled tracer near-free.

_scope = threading.local()


def current_scope():
    """(tracer, parent_sid, rid) of the innermost active scope, or None."""
    stack = getattr(_scope, "stack", None)
    return stack[-1] if stack else None


def annotate(name: str, **attrs) -> None:
    """Attach an instant event under the active trace scope (no-op without
    one).  This is the hook the batched drivers use for the paper-native
    work measures: ladder bucket hops, overflow events, per-tick frontier
    and push counts, dist exchange volume."""
    stack = getattr(_scope, "stack", None)
    if not stack:
        return
    tracer, parent, rid = stack[-1]
    tracer.event(name, cat="annotation", parent=parent, rid=rid, **attrs)


# ----------------------------------------------------------- compile events
# One jax.monitoring listener for the whole process, registered by the first
# watch_compiles() call, fans every XLA backend compile out to the live
# sinks.  Sinks are held weakly: a dropped engine stops receiving events and
# nothing outlives it.

_COMPILE_EVENT = "/jax/core/compile/backend_compile_duration"
_compile_lock = threading.Lock()
_compile_sinks: "weakref.WeakSet" = weakref.WeakSet()
_compile_listening = False


def _on_duration(event: str, duration: float, **kw) -> None:
    if event != _COMPILE_EVENT:
        return
    with _compile_lock:
        sinks = list(_compile_sinks)
    for sink in sinks:
        sink.on_compile(kw.get("fun_name", "?"), duration)


def watch_compiles(sink) -> None:
    """Call ``sink.on_compile(fun_name, seconds)`` for every XLA backend
    compile in this process, on the compiling thread, while ``sink`` lives
    (it is held weakly)."""
    global _compile_listening
    with _compile_lock:
        _compile_sinks.add(sink)
        if not _compile_listening:
            import jax
            jax.monitoring.register_event_duration_secs_listener(
                _on_duration)
            _compile_listening = True


class Tracer:
    """Thread-safe bounded flight recorder of :class:`Span` records.

    ``capacity`` bounds the *finished*-span ring; evicted spans increment
    ``dropped``.  ``device_annotations=True`` makes :meth:`device_span`
    emit ``jax.profiler.TraceAnnotation`` scopes (host spans then line up
    with device traces); off by default so the tracer stays import-free of
    jax.
    """

    def __init__(self, capacity: int = 8192,
                 device_annotations: bool = False):
        if capacity < 1:
            raise ValueError("capacity must be >= 1")
        self.capacity = capacity
        self.device_annotations = device_annotations
        self.dropped = 0
        self._lock = threading.Lock()
        self._ring: deque = deque(maxlen=capacity)   # finished spans
        self._open: Dict[int, Span] = {}
        self._next_sid = 0
        self._next_rid = 0

    # -- span primitives -----------------------------------------------------

    def begin(self, name: str, cat: str = "span", *,
              parent: Optional[int] = None, rid: Optional[int] = None,
              t0: Optional[float] = None, **attrs) -> int:
        """Open a span; returns its sid (pass to :meth:`end`)."""
        t0 = _now() if t0 is None else t0
        with self._lock:
            sid = self._next_sid
            self._next_sid += 1
            self._open[sid] = Span(sid, name, cat, t0, parent, rid, attrs)
        return sid

    def end(self, sid: int, t1: Optional[float] = None, **attrs) -> None:
        """Close an open span and move it into the ring (unknown/already
        closed sids are ignored — a ring this size never blocks serving)."""
        t1 = _now() if t1 is None else t1
        with self._lock:
            span = self._open.pop(sid, None)
            if span is None:
                return
            span.t1 = t1
            if attrs:
                span.attrs.update(attrs)
            if len(self._ring) == self.capacity:
                self.dropped += 1
            self._ring.append(span)

    def event(self, name: str, cat: str = "event", *,
              parent: Optional[int] = None, rid: Optional[int] = None,
              **attrs) -> None:
        """Record an instant event (a zero-duration span)."""
        t = _now()
        with self._lock:
            span = Span(self._next_sid, name, cat, t, parent, rid, attrs)
            self._next_sid += 1
            span.t1 = t
            if len(self._ring) == self.capacity:
                self.dropped += 1
            self._ring.append(span)

    @contextlib.contextmanager
    def span(self, name: str, cat: str = "span", *,
             parent: Optional[int] = None, rid: Optional[int] = None,
             **attrs):
        """``with tracer.span("step"): ...`` — begin/end around a block;
        yields the sid so children can nest under it."""
        sid = self.begin(name, cat, parent=parent, rid=rid, **attrs)
        try:
            yield sid
        finally:
            self.end(sid)

    @contextlib.contextmanager
    def scope(self, parent: Optional[int] = None,
              rid: Optional[int] = None):
        """Activate this tracer for ambient :func:`annotate` calls made
        anywhere below this frame (the engine wraps each pool tick so the
        batched layers' annotations land under the tick span)."""
        stack = getattr(_scope, "stack", None)
        if stack is None:
            stack = _scope.stack = []
        stack.append((self, parent, rid))
        try:
            yield
        finally:
            stack.pop()

    def device_span(self, name: str):
        """A ``jax.profiler.TraceAnnotation`` scope when device annotations
        are enabled (and jax provides one), else a null context.  Lets the
        host-side tick spans line up with device traces in Perfetto."""
        if not self.device_annotations:
            return contextlib.nullcontext()
        try:
            from jax.profiler import TraceAnnotation
        except Exception:       # pragma: no cover - jax without profiler
            return contextlib.nullcontext()
        return TraceAnnotation(name)

    def _scope_parent(self) -> Optional[int]:
        """Parent sid of the active scope when it is this tracer's."""
        top = current_scope()
        return top[1] if top is not None and top[0] is self else None

    @contextlib.contextmanager
    def region(self, name: str, **attrs):
        """``with tracer.region("step"): ...`` — a ``pool`` span under the
        active scope (inside a tick: a child of the tick span) that is also
        a :meth:`device_span` of the same name; yields the sid."""
        with self.span(name, cat="pool", parent=self._scope_parent(),
                       **attrs) as sid, self.device_span(name):
            yield sid

    def compile_event(self, fun_name: str, seconds: float) -> None:
        """Record one XLA backend compile under the active scope (the tick
        span when the compile happens inside a tick)."""
        self.event("compile", cat="compile", parent=self._scope_parent(),
                   fun_name=fun_name, seconds=seconds)

    # -- request lifecycle ---------------------------------------------------

    def request(self, **attrs) -> "RequestTrace":
        """Open a request-root span and return its :class:`RequestTrace`
        handle (the engine/scheduler drive its phase transitions)."""
        with self._lock:
            rid = self._next_rid
            self._next_rid += 1
        root = self.begin("request", cat="request", rid=rid, **attrs)
        return RequestTrace(self, rid, root)

    # -- read side -----------------------------------------------------------

    def spans(self, rid: Optional[int] = None,
              include_open: bool = True) -> List[Span]:
        """Snapshot of recorded spans, oldest first (optionally one
        request's), finished ring plus still-open spans."""
        with self._lock:
            out = list(self._ring)
            if include_open:
                out.extend(self._open.values())
        out.sort(key=lambda s: (s.t0, s.sid))
        if rid is not None:
            out = [s for s in out if s.rid == rid]
        return out

    def request_tree(self, rid: int, max_spans: int = 256) -> Dict[str, Any]:
        """The request's span tree as a nested JSON-able dict — the
        postmortem payload dumped into the telemetry snapshot on a deadline
        miss.  Bounded: at most ``max_spans`` nodes (oldest kept, the
        lifecycle phases; a ``truncated`` count reports the rest)."""
        spans = self.spans(rid=rid)
        truncated = max(0, len(spans) - max_spans)
        spans = spans[:max_spans]
        nodes = {s.sid: dict(s.to_dict(), children=[]) for s in spans}
        roots = []
        for s in spans:
            node = nodes[s.sid]
            if s.parent in nodes:
                nodes[s.parent]["children"].append(node)
            else:
                roots.append(node)
        return dict(schema=TRACE_SCHEMA, rid=rid, spans=len(spans),
                    truncated=truncated, dropped_ring_total=self.dropped,
                    tree=roots)


class RequestTrace:
    """Drives one request's contiguous phase spans under its root span.

    Every :meth:`phase` call closes the open phase *at the same timestamp*
    the next one opens, so the phases tile [submit, resolve] with no gaps by
    construction — attribution coverage is then a measurement of how much
    of the resolved latency the recorded phases explain, not an artifact of
    instrumentation holes.  Phase durations are also accumulated into
    ``phase_ms`` (O(#phases) per request), so latency attribution survives
    ring-buffer eviction of the underlying spans.
    """

    __slots__ = ("tracer", "rid", "root", "t0", "t1", "phase_ms", "status",
                 "_phase_sid", "_phase_name", "_phase_t0", "_lock")

    def __init__(self, tracer: Tracer, rid: int, root: int):
        self.tracer = tracer
        self.rid = rid
        self.root = root
        self.t0 = _now()
        self.t1: Optional[float] = None
        self.phase_ms: Dict[str, float] = {}
        self.status: Optional[str] = None
        self._phase_sid: Optional[int] = None
        self._phase_name: Optional[str] = None
        self._phase_t0 = self.t0
        self._lock = threading.Lock()

    def _close_phase(self, t: float) -> None:
        if self._phase_sid is not None:
            self.tracer.end(self._phase_sid, t1=t)
            dt = (t - self._phase_t0) * 1e3
            name = self._phase_name
            self.phase_ms[name] = self.phase_ms.get(name, 0.0) + dt
            self._phase_sid = None

    def phase(self, name: str, **attrs) -> None:
        """Transition to phase ``name``: the previous phase ends and the new
        one begins at one shared timestamp."""
        t = _now()
        with self._lock:
            if self.t1 is not None:      # finished requests stay finished
                return
            self._close_phase(t)
            self._phase_sid = self.tracer.begin(
                name, cat="phase", parent=self.root, rid=self.rid, t0=t,
                **attrs)
            self._phase_name = name
            self._phase_t0 = t

    def event(self, name: str, **attrs) -> None:
        """Instant lifecycle event under the current phase (or the root)."""
        with self._lock:
            parent = (self._phase_sid if self._phase_sid is not None
                      else self.root)
        self.tracer.event(name, cat="lifecycle", parent=parent,
                          rid=self.rid, **attrs)

    def finish(self, status: str = "resolved", **attrs) -> None:
        """Close the open phase and the root span (idempotent)."""
        t = _now()
        with self._lock:
            if self.t1 is not None:
                return
            self._close_phase(t)
            self.t1 = t
            self.status = status
        self.tracer.end(self.root, t1=t, status=status, **attrs)

    def resolve_cached(self, **attrs) -> None:
        """Terminal sequence for a result-cache hit: a ``cache_hit``
        lifecycle event, a (zero-ish width) ``deliver`` phase, and a
        resolved finish — the flight-recorder shape of a request that never
        touched a lane (serve/result_cache.py).  ``attrs`` (cache key
        context, seed, …) land on both the event and the phase."""
        self.event("cache_hit", **attrs)
        self.phase("deliver", cached=True, **attrs)
        self.finish("resolved")

    # -- attribution ---------------------------------------------------------

    @property
    def latency_ms(self) -> Optional[float]:
        return None if self.t1 is None else (self.t1 - self.t0) * 1e3

    def coverage(self) -> Optional[float]:
        """Fraction of the root span's wall time the recorded phases
        account for (the attribution-gap acceptance gate reads this);
        None until finished."""
        if self.t1 is None:
            return None
        total = (self.t1 - self.t0) * 1e3
        if total <= 0.0:
            return 1.0
        return min(1.0, sum(self.phase_ms.values()) / total)

    def summary(self) -> Dict[str, Any]:
        """JSON-able per-request attribution record."""
        return dict(rid=self.rid, latency_ms=self.latency_ms,
                    status=self.status, coverage=self.coverage(),
                    phases_ms={k: round(v, 6)
                               for k, v in self.phase_ms.items()})
