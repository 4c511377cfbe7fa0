"""Tracing: spans around task/actor submission and execution.

Reference analog: ``python/ray/util/tracing/tracing_helper.py`` —
opt-in OpenTelemetry spans wrapping ``submit_task``/``execute_task``
with trace context propagated inside the TaskSpec. Here spans are
in-process records exported as chrome://tracing events
(:meth:`Tracer.chrome_trace_events`), mergeable with the
``observability.state.timeline`` output.

Enable with ``tracing.enable()`` (or config flag ``tracing_enabled``);
``@trace_span("name")`` / ``with span("name"):`` for app code.

Two clocks, one module. ``span`` / ``record_span`` are per-request stage
attribution on the HOST's wall clock (``rt trace <id>``). ``step_span``
is for the hot paths that drive the device (an engine step, a stream
pull): it always opens a ``jax.profiler.TraceAnnotation``, which lands in
a profiler trace's ``/host:CPU`` plane on the clock the device events
carry, and records a ring span too only when the tracer is on. The
device's own time is never in a span: it is in the profiler trace, under
the ``jax.named_scope`` names the programs carry.
"""

from __future__ import annotations

import contextvars
import functools
import os
import sys
import threading
import time
from collections import deque
from dataclasses import dataclass, field
from typing import Any, Callable, Dict, List, Optional

_local = threading.local()

# Async-safe request context: the serve replica's event loop interleaves
# many requests on ONE thread, so the thread-local span stack cannot
# carry a per-request trace context across awaits. A ContextVar is
# task-local under asyncio — each request's handler task sees only its
# own (trace_id, span_id).
_request_ctx: contextvars.ContextVar = contextvars.ContextVar(
    "rt_request_trace_ctx", default=None)


@dataclass
class Span:
    name: str
    span_id: str
    parent_id: Optional[str]
    trace_id: str
    start_s: float
    end_s: Optional[float] = None
    attributes: Dict[str, Any] = field(default_factory=dict)

    @property
    def duration_ms(self) -> Optional[float]:
        if self.end_s is None:
            return None
        return (self.end_s - self.start_s) * 1000.0


class Tracer:
    """Process-wide span collector (bounded ring)."""

    def __init__(self, max_spans: int = 10_000):
        self.enabled = False
        self.max_spans = max_spans
        # deque(maxlen): a full ring drops the oldest span in O(1).
        # The list version re-sliced 10k elements on EVERY record once
        # full — ~15us/span of steady-state trim cost on the task hot
        # path (caught by the ISSUE 20 overhead A/B).
        self._spans: deque = deque(maxlen=max_spans)
        self._lock = threading.Lock()
        # Export plane (cluster telemetry): when a TelemetryExporter is
        # attached it flips export_enabled and drains finished spans on
        # each flush; bounded the same way so a stalled flusher can't
        # grow the process.
        self.export_enabled = False
        self._export: deque = deque(maxlen=max_spans)
        # Head-side sink: the trace store installs itself here so
        # spans recorded IN the head process (proxy/router) reach the
        # same per-trace index the telemetry plane feeds with shipped
        # worker spans. Called outside the lock with the finished span.
        self.on_record: Optional[Callable[[Span], None]] = None

    def enable(self) -> None:
        self.enabled = True

    def disable(self) -> None:
        self.enabled = False

    def record(self, span: Span) -> None:
        dropped = 0
        with self._lock:
            if len(self._spans) == self.max_spans:
                dropped += 1  # deque drops the oldest on append
            self._spans.append(span)
            if self.export_enabled:
                if len(self._export) == self.max_spans:
                    dropped += 1
                self._export.append(span)
        if dropped:
            # The ring used to trim SILENTLY — a truncated trace looked
            # identical to a quiet process. Counted + warn-once, same
            # policy as every other bounded telemetry buffer.
            from . import telemetry

            telemetry.count_dropped("tracer", dropped)
        hook = self.on_record
        if hook is not None:
            try:
                hook(span)
            except Exception:  # noqa: BLE001 — sink must not break apps
                pass

    def drain_export(self) -> List[Span]:
        """Finished spans recorded since the last drain (telemetry
        flush path; worker/daemon processes ship these to the head)."""
        with self._lock:
            out = list(self._export)
            self._export.clear()
        return out

    def spans(self, name_prefix: str = "") -> List[Span]:
        with self._lock:
            return [s for s in self._spans if s.name.startswith(name_prefix)]

    def clear(self) -> None:
        with self._lock:
            self._spans.clear()
            self._export.clear()  # cleared means cleared: nothing ships

    def chrome_trace_events(self) -> List[dict]:
        """Spans as chrome://tracing 'X' (complete) events, mergeable
        with ``observability.state.timeline`` output. The pid is THIS
        process's real pid so merged cluster timelines show one row per
        process (driver / workers / daemons)."""
        import os

        with self._lock:
            spans = list(self._spans)
        pid = os.getpid()
        return [span_chrome_event(s, pid) for s in spans
                if s.end_s is not None]


def span_chrome_event(s: Span, pid) -> dict:
    """One finished span as a chrome://tracing complete event; shared by
    the local dump and the telemetry export path (which stamps the
    ORIGIN process's pid before shipping)."""
    return {
        "name": s.name, "ph": "X", "cat": "span",
        "ts": s.start_s * 1e6,
        "dur": ((s.end_s or s.start_s) - s.start_s) * 1e6,
        "pid": pid, "tid": s.trace_id[:8],
        # Full trace id travels in args (the tid row label is truncated
        # for chrome://tracing readability): the head trace store keys
        # its per-request index on it.
        "args": {**s.attributes, "span_id": s.span_id,
                 "parent_id": s.parent_id, "trace_id": s.trace_id},
    }


_tracer = Tracer()


def get_tracer() -> Tracer:
    return _tracer


def enable() -> None:
    _tracer.enable()


def disable() -> None:
    _tracer.disable()


def current_span() -> Optional[Span]:
    stack = getattr(_local, "stack", None)
    return stack[-1] if stack else None


class _NullSpanCtx:
    """Shared no-op CM for the tracing-disabled fast path."""

    __slots__ = ()

    def __enter__(self):
        return None

    def __exit__(self, *exc):
        return False


_NULL_SPAN = _NullSpanCtx()


class _SpanCtx:
    """Hand-rolled context manager (the @contextmanager generator form
    costs ~3us/span of frame churn — this sits on the task hot path)."""

    __slots__ = ("_name", "_attributes", "_span")

    def __init__(self, name: str, attributes: Dict[str, Any]):
        self._name = name
        self._attributes = attributes
        self._span: Optional[Span] = None

    def __enter__(self) -> Span:
        # Parent resolution happens HERE, not in __init__: callers
        # build the span CM before entering remote_context (see
        # worker_main's `with trace_cm, span_cm:`), so resolving
        # eagerly would miss the adopted context.
        parent = current_span()
        # Same fallback chain as inject_context: thread-local remote
        # ctx (worker executing a task), then the asyncio request ctx
        # (serve replica handler) — so a span opened inside an async
        # handler joins the request's trace instead of minting a fresh
        # id.
        remote_ctx = (getattr(_local, "remote_context", None)
                      or _request_ctx.get())
        if parent is not None:
            trace_id, parent_id = parent.trace_id, parent.span_id
        elif remote_ctx is not None:
            trace_id, parent_id = remote_ctx
        else:
            trace_id, parent_id = os.urandom(16).hex(), None
        s = self._span = Span(
            name=self._name, span_id=os.urandom(8).hex(),
            parent_id=parent_id, trace_id=trace_id, start_s=time.time(),
            attributes=self._attributes)
        stack = getattr(_local, "stack", None)
        if stack is None:
            stack = _local.stack = []
        stack.append(s)
        return s

    def __exit__(self, *exc):
        s = self._span
        s.end_s = time.time()
        _local.stack.pop()
        _tracer.record(s)
        return False


def span(name: str, **attributes):
    """Context-managed span; nests under the thread's current span and
    continues a propagated remote context when present."""
    if not _tracer.enabled:
        return _NULL_SPAN
    return _SpanCtx(name, attributes)


_annotation = None  # jax.profiler.TraceAnnotation, once JAX is loaded here


class _BoundsSpanCtx:
    """Ring side of a span that crosses awaits on a shared event-loop
    thread: the thread's span stack cannot hold it, so it is recorded at
    its end from its bounds, on the task's request trace."""

    __slots__ = ("_name", "_attributes", "_start_s")

    def __init__(self, name: str, attributes: Dict[str, Any]):
        self._name = name
        self._attributes = attributes

    def __enter__(self):
        self._start_s = time.time()

    def __exit__(self, *exc):
        trace_id, parent_id = (_request_ctx.get()
                               or (os.urandom(16).hex(), None))
        record_span(self._name, trace_id, parent_id,
                    start_s=self._start_s, **self._attributes)
        return False


class _StepSpan:
    """What :func:`step_span` returns: the profiler annotation and, when
    the tracer is on, a ring span, entered and left together."""

    __slots__ = ("_ann", "_ctx", "_attributes")

    def __init__(self, ann, ctx, attributes):
        self._ann = ann
        self._ctx = ctx  # ring side: _SpanCtx, _BoundsSpanCtx or None
        self._attributes = attributes  # the ring span's dict too

    def __enter__(self) -> "_StepSpan":
        if self._ann is not None:
            self._ann.__enter__()
        if self._ctx is not None:
            self._ctx.__enter__()
        return self

    def __exit__(self, *exc):
        if self._ctx is not None:
            self._ctx.__exit__(*exc)
        if self._ann is not None:
            self._ann.__exit__(*exc)
        return False

    @property
    def recording(self) -> bool:
        """Somebody will read this span: a profiler trace is being
        recorded or the tracer is on. Callers compute an attribute that
        costs more than a few additions only when this is true."""
        return self._ctx is not None or (
            self._ann is not None and self._ann.is_enabled())

    def set(self, **attributes) -> None:
        """Attributes known only inside the span (counts at its end)."""
        if self._ctx is not None:
            self._attributes.update(attributes)
        if self._ann is not None:
            self._ann.set_metadata(**attributes)


def step_span(name: str, interleaved: bool = False,
              **attributes) -> _StepSpan:
    """A span round one step of a hot path (names start with ``rt.``).

    Always a ``jax.profiler.TraceAnnotation``: while a profiler trace is
    recorded it is an event of this thread in the trace's host plane,
    with ``attributes`` as its stats, on the device events' clock; while
    none is, constructing it checks one flag. A process that has not
    imported JAX (the head, the proxy) gets none — JAX is never imported
    from here. With the tracer on it is also a ring :class:`Span` under
    the thread's current span, so ``rt trace`` and the chrome export show
    steps beside requests. ``interleaved`` is for a span that crosses
    awaits on a shared event-loop thread: the annotation keeps its own
    start and end there; the ring span is a :class:`_BoundsSpanCtx`."""
    global _annotation
    if _annotation is None:
        jax = sys.modules.get("jax")
        if jax is not None:
            _annotation = jax.profiler.TraceAnnotation
    ctx = None
    if _tracer.enabled:
        ctx = (_BoundsSpanCtx if interleaved else _SpanCtx)(name,
                                                            attributes)
    return _StepSpan(
        None if _annotation is None else _annotation(name, **attributes),
        ctx, attributes)


def trace_span(name: Optional[str] = None, **attributes):
    """Decorator form of :func:`span`."""

    def wrap(fn):
        span_name = name or fn.__qualname__

        @functools.wraps(fn)
        def inner(*args, **kwargs):
            with span(span_name, **attributes):
                return fn(*args, **kwargs)

        return inner

    return wrap


# -- remote propagation (reference: trace context in TaskSpec) --------------

def inject_context() -> Optional[tuple]:
    """Capture (trace_id, span_id) to ship inside a TaskSpec.

    Resolution order mirrors :func:`span`: the thread's current span,
    then a remote context adopted from a submitted task, then the
    async request context set by the serve replica — so a nested
    ``.remote()`` inside an async handler still joins the request's
    trace even though no thread-local span is open across the await."""
    if not _tracer.enabled:
        return None
    s = current_span()
    if s is not None:
        return (s.trace_id, s.span_id)
    remote_ctx = getattr(_local, "remote_context", None)
    if remote_ctx is not None:
        return tuple(remote_ctx)
    req_ctx = _request_ctx.get()
    return tuple(req_ctx) if req_ctx is not None else None


class _RemoteCtx:
    """Class CM (not @contextmanager) — wraps every task execution."""

    __slots__ = ("_ctx",)

    def __init__(self, ctx: Optional[tuple]):
        self._ctx = ctx

    def __enter__(self):
        if self._ctx is not None:
            _local.remote_context = tuple(self._ctx)
        return None

    def __exit__(self, *exc):
        if self._ctx is not None:
            _local.remote_context = None
        return False


def remote_context(ctx: Optional[tuple]) -> "_RemoteCtx":
    """Worker-side: adopt the submitted task's trace context so execution
    spans join the submitter's trace."""
    return _RemoteCtx(ctx)


def set_request_context(ctx: Optional[tuple]):
    """Bind a request's (trace_id, span_id) to the CURRENT asyncio task
    (or thread, outside a loop). Returns a token for
    :func:`reset_request_context`. No-op (returns None) without a ctx."""
    if ctx is None:
        return None
    return _request_ctx.set(tuple(ctx))


def reset_request_context(token) -> None:
    if token is not None:
        _request_ctx.reset(token)


def get_request_context() -> Optional[tuple]:
    """The (trace_id, span_id) bound to this task/thread, if any."""
    return _request_ctx.get()


def new_span_id() -> str:
    return os.urandom(8).hex()


def record_span(name: str, trace_id: str,
                parent_id: Optional[str] = None,
                start_s: Optional[float] = None,
                end_s: Optional[float] = None,
                span_id: Optional[str] = None,
                **attributes) -> Optional[Span]:
    """Record a finished span with EXPLICIT identity and timestamps.

    The context-managed :func:`span` can't express two shapes this PR
    needs: spans synthesized after the fact from stage stamps (the LLM
    engine's timing breakdown) and spans whose lifetime crosses awaits
    on a shared event-loop thread (the proxy's root request span, the
    router's assign). Both know their trace id and wall-clock bounds up
    front; this records them without touching the thread-local stack."""
    if not _tracer.enabled:
        return None
    now = time.time()
    s = Span(name=name, span_id=span_id or new_span_id(),
             parent_id=parent_id, trace_id=trace_id,
             start_s=now if start_s is None else start_s,
             end_s=now if end_s is None else end_s,
             attributes=attributes)
    _tracer.record(s)
    return s
