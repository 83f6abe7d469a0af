"""Request-scoped span tracing (Dapper shape: ``trace_id`` / ``span_id``
/ parent links) with thread-local context propagation.

The contract that keeps instrumentation free when observability is off:

- A span is only ever recorded under an ACTIVE trace (or, below, by
  the profiler). The thread-local context holds the current
  :class:`Span`; :func:`start_span` with no current span and no
  profiler recording returns the shared :data:`NULL_CM` singleton —
  one function call, one thread-local read, one profiler check, **zero
  allocation** — so the hundreds of instrumentation sites across the
  engine cost nothing on workloads that never opened a trace.
- Traces are OPENED only at the two entry points that own a request's
  lifecycle: the serving daemon (per HTTP request, trace id =
  ``X-Request-Id``) and ``FugueWorkflow.run`` (embedded use, when no
  ambient trace is already active). Everything below them just calls
  :func:`start_span`.
- Crossing threads is explicit: the DAG runner captures the caller's
  current span at ``run()`` and re-attaches it inside each worker via
  :func:`activate`, so task/attempt/engine spans land in the right tree
  no matter which pool thread executes them.

Spans carry ``time.time_ns`` wall-clock bounds (exported as Chrome
trace-event microseconds) plus the executing thread id, so a Perfetto
load shows queue/compile/execute/transfer lanes per thread.

While ``jax.profiler`` records, every :func:`start_span` span is ALSO a
profiler event (a ``TraceAnnotation`` named like the span, its
attributes as event metadata), on the profiler's clock beside the
device's ops, whether or not an obs trace is open: with neither, a site
costs one more call, ``TraceMe.is_enabled()``. :func:`begin_span` spans,
whose ends may land on another thread, stay obs-only.
"""

import itertools
import threading
import time
import uuid
from typing import Any, Dict, List, Optional

from jax.profiler import TraceAnnotation

from fugue_tpu.testing.locktrace import tracked_lock

_TLS = threading.local()
# True while a jax.profiler session records on this process
_profiler_recording = TraceAnnotation.is_enabled


def current_span() -> Optional["Span"]:
    """This thread's active span (None = no trace → no-op sites)."""
    return getattr(_TLS, "span", None)


class Span:
    __slots__ = (
        "trace",
        "name",
        "span_id",
        "parent_id",
        "start_ns",
        "end_ns",
        "thread_id",
        "attrs",
        "_event",
    )

    def __init__(
        self,
        trace: "Trace",
        name: str,
        span_id: int,
        parent_id: Optional[int],
        attrs: Optional[Dict[str, Any]] = None,
    ):
        self.trace = trace
        self.name = name
        self.span_id = span_id
        self.parent_id = parent_id
        self.start_ns = time.time_ns()
        self.end_ns: Optional[int] = None
        self.thread_id = threading.get_ident()
        self.attrs: Dict[str, Any] = attrs or {}
        # the open profiler event of a start_span span (None otherwise)
        self._event: Optional[TraceAnnotation] = None

    def set_attr(self, **kv: Any) -> None:
        self.attrs.update(kv)
        if self._event is not None:
            self._event.set_metadata(**kv)

    def finish(self) -> None:
        """Idempotent end; the trace's open-span count drops on the
        first call only."""
        if self.end_ns is None:
            self.end_ns = time.time_ns()
            self.trace._note_end()

    @property
    def duration_ms(self) -> float:
        end = self.end_ns if self.end_ns is not None else time.time_ns()
        return (end - self.start_ns) / 1e6

    def __repr__(self) -> str:  # pragma: no cover - debug aid
        return (
            f"Span({self.name}, id={self.span_id}, "
            f"parent={self.parent_id}, {self.duration_ms:.3f}ms)"
        )


class _NullSpan:
    """The span-shaped no-op sites receive when nothing records: every
    method swallows its arguments; truthiness is False so guards can
    skip work that only a recorded span would keep."""

    __slots__ = ()

    def set_attr(self, **kv: Any) -> None:
        pass

    def finish(self) -> None:
        pass

    def __bool__(self) -> bool:
        return False


NULL_SPAN = _NullSpan()


class _NullCM:
    """Allocation-free ``with`` target for obs-off instrumentation."""

    __slots__ = ()

    def __enter__(self) -> _NullSpan:
        return NULL_SPAN

    def __exit__(self, *args: Any) -> bool:
        return False


NULL_CM = _NullCM()


class _ProfilerSpan:
    """What :func:`start_span` gives when no obs trace is open but the
    profiler records: a profiler event, its own context manager and
    span. ``set_attr`` reaches the event's metadata, and it is truthy
    like an obs span, so ``if sp:`` guards record attributes on it. The
    event's name is fixed when it starts: a site's later ``name`` change
    reaches only an obs span."""

    __slots__ = ("_event", "name")

    def __init__(self, name: str, attrs: Dict[str, Any]):
        self.name = name
        self._event = TraceAnnotation(name, **attrs)

    def __enter__(self) -> "_ProfilerSpan":
        self._event.__enter__()
        return self

    def __exit__(self, exc_type: Any, exc: Any, tb: Any) -> bool:
        if exc_type is not None:
            self._event.set_metadata(error=exc_type.__name__)
        self._event.__exit__(None, None, None)
        return False

    def set_attr(self, **kv: Any) -> None:
        self._event.set_metadata(**kv)

    def finish(self) -> None:
        pass


class Trace:
    """One request's span collection. Spans register at START (so a
    crashed run still exports what it saw); ``complete`` flips when the
    root ended and no span remains open — the exporter's trigger when
    two threads (HTTP handler, job worker) race to finish last."""

    __slots__ = (
        "trace_id",
        "spans",
        "root_span",
        "_lock",
        "_ids",
        "_open",
        "_exported",
    )

    def __init__(self, trace_id: Optional[str] = None):
        self.trace_id = trace_id or uuid.uuid4().hex[:16]
        self.spans: List[Span] = []
        self.root_span: Optional[Span] = None
        self._lock = tracked_lock("obs.trace.Trace._lock")
        self._ids = itertools.count(1)
        self._open = 0
        self._exported = False

    def start_span(
        self,
        name: str,
        parent: Optional[Span] = None,
        attrs: Optional[Dict[str, Any]] = None,
    ) -> Span:
        span = Span(
            self,
            name,
            next(self._ids),
            parent.span_id if parent is not None else None,
            attrs,
        )
        with self._lock:
            self.spans.append(span)
            self._open += 1
            if self.root_span is None:
                self.root_span = span
        return span

    def root(self, name: str, **attrs: Any) -> Span:
        return self.start_span(name, None, attrs)

    def _note_end(self) -> None:
        with self._lock:
            self._open -= 1

    @property
    def complete(self) -> bool:
        with self._lock:
            return (
                self.root_span is not None
                and self.root_span.end_ns is not None
                and self._open <= 0
            )

    def mark_exported(self) -> bool:
        """True exactly once — the exporter's claim when multiple
        threads observe completion concurrently."""
        with self._lock:
            if self._exported:
                return False
            self._exported = True
            return True

    def find(self, name: str) -> List[Span]:
        with self._lock:
            return [s for s in self.spans if s.name == name]

    def children_of(self, span: Span) -> List[Span]:
        with self._lock:
            return [s for s in self.spans if s.parent_id == span.span_id]


class _SpanCM:
    """``with start_span("x") as sp:`` — pushes the child as the
    thread's current span, restores the parent on exit, marks the span
    errored when the body raises."""

    __slots__ = ("_parent", "_name", "_attrs", "_span")

    def __init__(self, parent: Span, name: str, attrs: Dict[str, Any]):
        self._parent = parent
        self._name = name
        self._attrs = attrs
        self._span: Optional[Span] = None

    def __enter__(self) -> Span:
        span = self._parent.trace.start_span(
            self._name, self._parent, self._attrs or None
        )
        if _profiler_recording():
            span._event = TraceAnnotation(self._name, **self._attrs)
            span._event.__enter__()
        self._span = span
        _TLS.span = span
        return span

    def __exit__(self, exc_type: Any, exc: Any, tb: Any) -> bool:
        span = self._span
        if span is not None:
            if exc_type is not None:
                span.attrs.setdefault("error", exc_type.__name__)
            event = span._event
            if event is not None:
                span._event = None
                if exc_type is not None:
                    event.set_metadata(error=exc_type.__name__)
                event.__exit__(None, None, None)
            span.finish()
        _TLS.span = self._parent
        return False


class _ActivateCM:
    """Attach an EXISTING span as this thread's current context (cross-
    thread propagation); restores whatever was current before."""

    __slots__ = ("_span", "_prev")

    def __init__(self, span: Span):
        self._span = span
        self._prev: Optional[Span] = None

    def __enter__(self) -> Span:
        self._prev = current_span()
        _TLS.span = self._span
        return self._span

    def __exit__(self, *args: Any) -> bool:
        _TLS.span = self._prev
        return False


def start_span(name: str, **attrs: Any) -> Any:
    """Context manager for one child span of the thread's current span,
    also a profiler event while the profiler records. No active trace:
    a profiler event alone, or, with no profiler recording, the shared
    no-op singleton (nothing allocated)."""
    cur = getattr(_TLS, "span", None)
    if cur is None:
        if _profiler_recording():
            return _ProfilerSpan(name, attrs)
        return NULL_CM
    return _SpanCM(cur, name, attrs)


def begin_span(name: str, **attrs: Any) -> Any:
    """Manual (non-context-manager) child span for windows whose start
    and end live in different functions (the memory gate's
    ``before()``/``after()``); caller owns ``finish()``. The span is NOT
    pushed as the thread's current context. Returns :data:`NULL_SPAN`
    when no trace is active."""
    cur = getattr(_TLS, "span", None)
    if cur is None:
        return NULL_SPAN
    return cur.trace.start_span(name, cur, attrs or None)


def activate(span: Optional[Span]) -> Any:
    """Context manager attaching ``span`` to this thread; ``None`` (the
    obs-off carry) is the shared no-op."""
    if span is None or isinstance(span, _NullSpan):
        return NULL_CM
    return _ActivateCM(span)


class _SuppressCM:
    """Marks this thread as sampled-OUT: trace owners downstream
    (``FugueWorkflow.run``) must not open a trace of their own."""

    __slots__ = ("_prev",)

    def __enter__(self) -> None:
        self._prev = getattr(_TLS, "suppress", False)
        _TLS.suppress = True
        return None

    def __exit__(self, *args: Any) -> bool:
        _TLS.suppress = self._prev
        return False


def suppress_tracing() -> Any:
    """Scope in which downstream trace OWNERS stay quiet. The serving
    daemon wraps a job whose request lost the sampling draw in this, so
    the workflow layer does not re-enter sampling and export an
    uncorrelated trace at ~double the configured rate."""
    return _SuppressCM()


def tracing_suppressed() -> bool:
    return getattr(_TLS, "suppress", False)
