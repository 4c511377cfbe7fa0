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
carry, records a ring span too only when the tracer is on, and, where
the caller names an account (``into=``), always adds its duration to it. The
device's own time is never in a span: it is in the profiler trace, under
the ``jax.named_scope`` names the programs carry.

Two process-wide events stop a loop whoever runs it, and are spans and
counts here too (:func:`watch_gc`, :func:`watch_compiles`): a garbage
collection (``rt.gc``) and a program built for the backend.
"""

from __future__ import annotations

import contextvars
import functools
import gc
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
        # Finished spans from where no lock may be taken (a ``gc.callbacks``
        # hook runs wherever the interpreter stops, inside ``_lock``'s
        # blocks too): appended lock-free, moved into the ring by the next
        # record or read from ordinary code.
        self._late: deque = deque(maxlen=1024)
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

    def record_later(self, span: Span) -> None:
        """``record`` for a caller that may be running INSIDE one of this
        tracer's locked blocks on the same thread: takes no lock."""
        self._late.append(span)

    def _flush_late(self) -> None:
        while True:
            try:
                span = self._late.popleft()
            except IndexError:
                return
            self._store(span)

    def record(self, span: Span) -> None:
        if self._late:
            self._flush_late()
        self._store(span)

    def _store(self, span: Span) -> None:
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
        if self._late:
            self._flush_late()
        with self._lock:
            out = list(self._export)
            self._export.clear()
        return out

    def spans(self, name_prefix: str = "") -> List[Span]:
        if self._late:
            self._flush_late()
        with self._lock:
            return [s for s in self._spans if s.name.startswith(name_prefix)]

    def clear(self) -> None:
        self._late.clear()
        with self._lock:
            self._spans.clear()
            self._export.clear()  # cleared means cleared: nothing ships

    def chrome_trace_events(self) -> List[dict]:
        """Spans as chrome://tracing 'X' (complete) events, mergeable
        with ``observability.state.timeline`` output. The pid is THIS
        process's real pid so merged cluster timelines show one row per
        process (driver / workers / daemons)."""
        import os

        if self._late:
            self._flush_late()
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


def _trace_annotation():
    global _annotation
    if _annotation is None:
        jax = sys.modules.get("jax")
        if jax is not None:
            _annotation = jax.profiler.TraceAnnotation
    return _annotation


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

    __slots__ = ("_ann", "_ctx", "_attributes", "_clocks", "_into",
                 "_name", "seconds")

    def __init__(self, ann, ctx, attributes, cpu=False, into=None,
                 name=""):
        self._ann = ann
        self._ctx = ctx  # ring side: _SpanCtx, _BoundsSpanCtx or None
        self._attributes = attributes  # the ring span's dict too
        # cpu: True until entered, then the two clocks read at entry, or
        # None where nobody records the span (nothing is read then)
        self._clocks = cpu
        # into: the caller's account. ``seconds`` is the clock read at
        # entry, then, once the span is left, how long it ran.
        self._into = into
        self._name = name
        self.seconds = 0.0

    def __enter__(self) -> "_StepSpan":
        if self._ann is not None:
            self._ann.__enter__()
        if self._ctx is not None:
            self._ctx.__enter__()
        if self._clocks:
            self._clocks = (time.perf_counter_ns(), time.thread_time_ns()
                            ) if self.recording else None
        if self._into is not None:
            self.seconds = time.perf_counter()
        return self

    def __exit__(self, *exc):
        if self._into is not None:
            self.seconds = time.perf_counter() - self.seconds
            self._into.record(self._name, self.seconds)
        if self._clocks:
            # the thread's clock inside the wall clock's interval, so a
            # clock that is exact never reads more CPU than wall
            on_cpu = time.thread_time_ns() - self._clocks[1]
            wall = time.perf_counter_ns() - self._clocks[0]
            self.set(wall_us=wall / 1e3, off_cpu_us=(wall - on_cpu) / 1e3)
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


def step_span(name: str, interleaved: bool = False, cpu: bool = False,
              into=None, **attributes) -> _StepSpan:
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
    start and end there; the ring span is a :class:`_BoundsSpanCtx`.

    ``cpu`` is for a span of a loop that should be WORKING, not waiting:
    where the span is recorded it gets ``wall_us`` and ``off_cpu_us``,
    its wall time and the part of it this thread was not on a CPU
    (blocked on the interpreter lock, another lock or the device). Wall
    time alone cannot tell a starved thread from a busy one. Read it as
    a SUM over many spans: where the kernel charges a thread its CPU
    time a scheduler tick at a time (10 ms on the machines the TPU is
    measured on), one span reads all of its wall time or less than none
    (``off_cpu_us`` is not clamped at 0, so that the sums stay true).

    ``into`` is a third sink, an ``event_stats.EventStats`` the caller
    owns, and the one that is always on: the span then reads
    ``time.perf_counter()`` at its two ends whether or not anyone records
    it, adds its duration to that account under its name (count, total,
    longest), and keeps it in ``.seconds`` for the caller. A loop that
    drives the chip has its time account that way with no profiler and no
    ring running (``llm/engine.py``: ``LLMServer.stats()["loop"]``)."""
    annotation = _trace_annotation()
    ctx = None
    if _tracer.enabled:
        ctx = (_BoundsSpanCtx if interleaved else _SpanCtx)(name,
                                                            attributes)
    return _StepSpan(
        None if annotation is None else annotation(name, **attributes),
        ctx, attributes, cpu, into, name)


# -- process-wide events that stop a loop -----------------------------------

# jax/_src/pxla.py times ``compile_or_get_cached`` under this name, so a
# program read back from the persistent cache is counted too, with the
# seconds the read took; jax/_src/compiler.py records the hit.
_COMPILE_EVENT = "/jax/core/compile/backend_compile_duration"
_CACHE_HIT_EVENT = "/jax/compilation_cache/cache_hits"


class ProcessEvents:
    """Cumulative counts of garbage collections and of programs built for
    the backend in this process, since :func:`watch_gc` /
    :func:`watch_compiles`. Read without a lock (a reader wants a count,
    not an instant). Collections come one at a time; two threads may
    compile at once, so those counts are added under a lock."""

    __slots__ = ("gc_pauses", "gc_pause_s", "compiles", "compile_s",
                 "compile_cache_hits", "_gc", "_watching", "_lock")

    def __init__(self):
        self.gc_pauses = 0
        self.gc_pause_s = 0.0
        # a program built for the backend, from the compiler or from the
        # persistent cache: either way the caller waited for it
        self.compiles = 0
        self.compile_s = 0.0
        self.compile_cache_hits = 0
        # (annotation, perf_counter, time) of the collection under way
        self._gc = None
        self._watching = set()
        self._lock = threading.Lock()

    def counters(self) -> Dict[str, Any]:
        return {"gc_pauses": self.gc_pauses,
                "gc_pause_s": round(self.gc_pause_s, 6),
                "compiles": self.compiles,
                "compile_s": round(self.compile_s, 6),
                "compile_cache_hits": self.compile_cache_hits}

    def _on_gc(self, phase: str, info: Dict[str, int]) -> None:
        # One collection at a time in a process, start and stop on the
        # thread that triggered it. The interpreter runs this wherever
        # that thread stops next, INSIDE the tracer's locked blocks too:
        # so it takes no lock and records nothing into the ring itself.
        # The profiler's annotation is a C++ TraceMe (no Python lock);
        # the ring's span is handed over lock-free.
        if phase == "start":
            ann = _trace_annotation()
            if ann is not None:
                ann = ann("rt.gc", generation=info["generation"])
                ann.__enter__()
            self._gc = (ann, time.perf_counter(), time.time())
        elif self._gc is not None:
            (ann, start, start_s), self._gc = self._gc, None
            self.gc_pauses += 1
            self.gc_pause_s += time.perf_counter() - start
            if ann is not None:
                ann.set_metadata(collected=info["collected"])
                ann.__exit__(None, None, None)
            if _tracer.enabled:
                parent = current_span()
                _tracer.record_later(Span(
                    name="rt.gc", span_id=new_span_id(),
                    parent_id=parent.span_id if parent else None,
                    trace_id=(parent.trace_id if parent
                              else os.urandom(16).hex()),
                    start_s=start_s, end_s=time.time(),
                    attributes={"generation": info["generation"],
                                "collected": info["collected"]}))

    def _on_compile(self, event: str, duration: float, **_) -> None:
        if event == _COMPILE_EVENT:
            with self._lock:
                self.compiles += 1
                self.compile_s += duration

    def _on_cache(self, event: str, **_) -> None:
        if event == _CACHE_HIT_EVENT:
            with self._lock:
                self.compile_cache_hits += 1


_events = ProcessEvents()


def process_events() -> ProcessEvents:
    return _events


def watch_gc() -> None:
    """Every garbage collection of this process from now on is an
    ``rt.gc`` span (``generation``, ``collected``) on the thread it
    stopped, and counted in ``gc_pauses`` / ``gc_pause_s``. Idempotent;
    costs nothing between collections."""
    if "gc" not in _events._watching:
        _events._watching.add("gc")
        gc.callbacks.append(_events._on_gc)


def watch_compiles() -> None:
    """Every program JAX builds for the backend from now on is counted in
    ``compiles`` / ``compile_s``, and in ``compile_cache_hits`` where the
    persistent cache had it. Idempotent; a process without JAX watches
    nothing (and may ask again once it has it)."""
    jax = sys.modules.get("jax")
    if jax is None or "compiles" in _events._watching:
        return
    _events._watching.add("compiles")
    jax.monitoring.register_event_duration_secs_listener(
        _events._on_compile)
    jax.monitoring.register_event_listener(_events._on_cache)


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
