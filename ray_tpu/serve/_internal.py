"""Serve internals: controller, replica, router, autoscaling.

Reference analog (call stack SURVEY §3.5):
  - ``serve/controller.py:61,229,330`` — ServeController actor with a
    reconcile loop driving DeploymentState replica scaling
  - ``serve/_private/deployment_state.py:942,1248`` — target-vs-actual
    replica reconciliation
  - ``serve/_private/router.py:62,221`` — replica set + assignment honoring
    ``max_concurrent_queries``
  - ``serve/_private/autoscaling_policy.py:93,127`` — queue-metric-based
    replica target (the policy math carries over unchanged)
  - ``serve/_private/replica.py`` — replica actor wrapping the user
    callable.

TPU note: replicas hosting pjit-compiled models are plain actors here —
model placement/sharding happens inside the replica via ``parallel``.
"""

from __future__ import annotations

import math
import threading
import time
from dataclasses import dataclass, field
from typing import Any, Callable, Dict, List, Optional

from ..core import get, kill, remote, wait
from ..core.actor import ActorHandle
from ..core.exceptions import (
    ActorError,
    DeadlineExceededError,
    OverloadedError,
    WorkerCrashedError,
)
from ..observability import tracing

# -- first-class Serve metrics (reference: serve/_private/metrics_utils +
# the serve_* series of metric_defs.cc). Created lazily in whichever
# process first serves traffic: replica processes observe request
# counts/latency (shipped to the head by worker telemetry, which tags
# node/worker), the controller process sets the replica-count gauge, and
# driver-side routers set queue depth directly in the head registry.
_serve_metrics_cache: Optional[Dict[str, Any]] = None
_serve_metrics_lock = threading.Lock()


def serve_metrics() -> Optional[Dict[str, Any]]:
    """The serve metric family, or None with telemetry disabled."""
    global _serve_metrics_cache

    from ..core.config import config
    from ..observability.metrics import (
        Counter,
        Gauge,
        Histogram,
        get_or_create,
    )

    if not config().telemetry_enabled:
        return None
    with _serve_metrics_lock:
        if _serve_metrics_cache is None:
            # get_or_create: the telemetry absorber may have minted
            # these names first (controller/replica flushes land before
            # the driver's first Router) — reconstructing would REPLACE
            # the registered metric and drop the absorbed series.
            _serve_metrics_cache = {
                "requests": get_or_create(
                    Counter, "rt_serve_requests",
                    "Serve requests handled per deployment",
                    ("deployment", "result")),
                "latency": get_or_create(
                    Histogram, "rt_serve_request_latency_seconds",
                    "Replica-side request latency",
                    boundaries=[0.001, 0.005, 0.01, 0.05, 0.1, 0.5,
                                1.0, 5.0],
                    tag_keys=("deployment",)),
                "queue_depth": get_or_create(
                    Gauge, "rt_serve_queue_depth",
                    "Router in-flight requests per deployment",
                    ("deployment",)),
                "replicas": get_or_create(
                    Gauge, "rt_serve_replicas",
                    "Live replicas per deployment", ("deployment",)),
                "restarts": get_or_create(
                    Counter, "rt_serve_replica_restarts_total",
                    "Replicas replaced after failed health checks",
                    ("deployment",)),
                "retries": get_or_create(
                    Counter, "rt_serve_retries_total",
                    "Requests re-dispatched after replica death",
                    ("deployment", "reason")),
                "unhealthy": get_or_create(
                    Gauge, "rt_serve_unhealthy_replicas",
                    "Replicas currently failing health checks",
                    ("deployment",)),
                "deadline_exceeded": get_or_create(
                    Counter, "rt_serve_deadline_exceeded_total",
                    "Requests that exceeded their end-to-end deadline"),
            }
        return _serve_metrics_cache


# Deployment-wide in-flight totals shared by EVERY driver-side router
# of a deployment (the proxy and each handle own separate Routers): the
# queue-depth gauge must report their sum, not whichever router wrote
# last. One tiny process-wide lock; the heavy per-request coordination
# stays on each router's own condvar.
_qd_lock = threading.Lock()
_qd_totals: Dict[str, int] = {}

# Deployment-wide BLOCKED-waiter totals (cluster-wide admission): when a
# deployment sets max_pending, an assign that would queue past the bound
# is shed with a typed OverloadedError instead of joining the condvar
# wait. Shares _qd_lock — both are two-instruction critical sections.
_pending_totals: Dict[str, int] = {}


def _pending_note(name: str, delta: int) -> int:
    """Update (delta != 0) or read (delta == 0) the deployment's blocked
    assign count across every router in this process."""
    with _qd_lock:
        total = max(0, _pending_totals.get(name, 0) + delta)
        if delta:
            _pending_totals[name] = total
        return total


def _queue_depth_note(name: str, delta: int, gauge=None,
                      key=None) -> int:
    """Update the deployment total and (when given) mirror it into the
    gauge UNDER the same lock — a set outside it can interleave with
    another router's update and publish a stale value (e.g. nonzero at
    idle). The metric lock is a leaf, so nesting it here is safe."""
    with _qd_lock:
        total = max(0, _qd_totals.get(name, 0) + delta)
        _qd_totals[name] = total
        if gauge is not None:
            gauge.set_key(key, float(total))
    return total


def _session_rendezvous(session_id: str, keys: List[bytes]) -> int:
    """Rendezvous (highest-random-weight) hash of a session id over
    replica actor-id keys. Deterministic and order-independent, so
    EVERY router — and the controller choosing a drain migration
    target — maps a session to the same surviving replica without any
    coordination: after a drain or crash the re-pinned replica is
    exactly the one the sessions were migrated to."""
    import hashlib

    sid = session_id.encode()
    best_i = 0
    best_h = b""
    for i, k in enumerate(keys):
        h = hashlib.sha1(sid + k).digest()
        if h > best_h:
            best_i, best_h = i, h
    return best_i


class SessionLog:
    """Head-side bounded transcript log for stateful LLM sessions.

    The proxy appends (transcript, seed) after every successful
    session-tagged generation. When a session's pinned replica dies
    WITHOUT exporting (SIGKILL — no drain, no page migration), the
    re-pinned replica reconstructs the session by re-prefilling this
    transcript (``restore_session``): cheap when its radix prefix cache
    hits, correct always. Bounded two ways: whole sessions are evicted
    LRU past ``max_sessions``, and a transcript is capped at
    ``max_tokens`` (the resident prefix is what recovery needs; an
    over-long tail would re-prefill past max_seq anyway)."""

    def __init__(self, max_sessions: int = 512, max_tokens: int = 8192):
        from collections import OrderedDict

        self.max_sessions = max_sessions
        self.max_tokens = max_tokens
        self._entries: "Dict[tuple, dict]" = OrderedDict()
        self._lock = threading.Lock()

    def note(self, deployment: str, session_id: str, transcript,
             seed=None, temperature: float = 0.0) -> None:
        toks = [int(t) for t in transcript][: self.max_tokens]
        with self._lock:
            self._entries[(deployment, session_id)] = {
                "transcript": toks,
                "seed": None if seed is None else int(seed),
                "temperature": float(temperature),
                "t": time.monotonic(),
            }
            self._entries.move_to_end((deployment, session_id))
            while len(self._entries) > self.max_sessions:
                self._entries.popitem(last=False)

    def get(self, deployment: str, session_id: str) -> Optional[dict]:
        with self._lock:
            entry = self._entries.get((deployment, session_id))
            return None if entry is None else dict(entry)

    def __len__(self) -> int:
        with self._lock:
            return len(self._entries)


@dataclass
class AutoscalingConfig:
    """Reference: serve/config.py AutoscalingConfig."""

    min_replicas: int = 1
    max_replicas: int = 4
    target_num_ongoing_requests_per_replica: float = 2.0
    upscale_delay_s: float = 0.5
    downscale_delay_s: float = 2.0


@dataclass
class DeploymentInfo:
    name: str
    deployment_def: Any  # class or function (cloudpickleable)
    init_args: tuple = ()
    init_kwargs: dict = field(default_factory=dict)
    num_replicas: int = 1
    max_concurrent_queries: int = 100
    route_prefix: Optional[str] = None
    autoscaling: Optional[AutoscalingConfig] = None
    ray_actor_options: dict = field(default_factory=dict)
    version: int = 0
    request_timeout_s: Optional[float] = None
    user_config: Optional[dict] = None
    # -- fault tolerance / admission (ISSUE 18) --------------------------
    # End-to-end deadline per request (queueing + retries + handler);
    # None = no deadline beyond request_timeout_s per attempt.
    request_deadline_s: Optional[float] = None
    # Safe-retry budget for requests that die with the replica BEFORE
    # any response byte; 0 disables. Non-idempotent deployments fail
    # fast with the typed actor error instead of re-dispatching.
    max_request_retries: int = 2
    retry_backoff_s: float = 0.05
    idempotent: bool = True
    # Cluster-wide admission: bound on blocked (queued) assigns across
    # every router of this deployment, and how long a queued request may
    # wait for a slot before being shed as OverloadedError -> HTTP 503.
    max_pending: Optional[int] = None
    queue_timeout_s: Optional[float] = None
    # Controller liveness probes: period between probes, per-probe
    # timeout, and consecutive failures before the replica is evicted
    # and replaced. None period disables health checking.
    health_check_period_s: Optional[float] = 1.0
    health_check_timeout_s: float = 5.0
    health_check_failure_threshold: int = 3


def _err_payload(e: BaseException):
    """Per-item batch error payload. Errors are stringified for
    transport (arbitrary app exceptions may not pickle) EXCEPT the typed
    control-flow errors the proxy must isinstance-match — admission
    sheds (-> 503) and deadline expiry (-> 504) — which are
    known-picklable and travel as live exceptions."""
    if isinstance(e, (OverloadedError, DeadlineExceededError)):
        return e
    return repr(e)


class _Replica:
    """Replica actor body (reference: RayServeReplica).

    Request methods are ASYNC: the actor machinery runs every coroutine
    method on the replica's ONE persistent asyncio event loop (see
    ``core/worker_main.py`` async-actor support), so concurrent requests
    interleave at awaits instead of each spinning up a throwaway loop —
    the asyncio request plane of ``serve/_private/replica.py``. Streaming
    responses register a (async) generator under a stream id which the
    caller drains with ``next_chunks`` (chunked-pull streaming).
    """

    def __init__(self, deployment_def, init_args, init_kwargs,
                 request_timeout_s: Optional[float] = None,
                 user_config: Optional[dict] = None,
                 deployment_name: str = ""):
        import inspect

        if inspect.isclass(deployment_def):
            self.callable = deployment_def(*init_args, **init_kwargs)
        else:
            self.callable = deployment_def
        if user_config is not None:
            # Applied during construction, BEFORE the replica is
            # routable — a post-creation reconfigure RPC could race with
            # routed requests on a concurrent actor.
            self.reconfigure(user_config)
        self._ongoing = 0
        self._total = 0
        self._timeout = request_timeout_s
        self._streams: Dict[int, Any] = {}
        self._stream_counter = 0
        # Request counter + latency histogram, deployment-tagged; the
        # worker telemetry flusher ships them to the head registry. Tag
        # keys interned once — this runs per request.
        self._deployment = deployment_name
        self._metrics = serve_metrics()
        if self._metrics is not None:
            self._key_ok = (("deployment", deployment_name),
                            ("result", "ok"))
            self._key_err = (("deployment", deployment_name),
                             ("result", "error"))
            self._key_lat = (("deployment", deployment_name),)

    def _observe(self, start: float, n: int, ok: bool) -> None:
        if self._metrics is None:
            return
        elapsed = time.perf_counter() - start
        self._metrics["requests"].inc_key(
            self._key_ok if ok else self._key_err, n)
        self._metrics["latency"].observe_key(self._key_lat, elapsed,
                                             count=n)

    def _observe_batch(self, start: float, n: int, results) -> None:
        """Coalesced-entry accounting: ``results`` is the final
        ("ok"|"err", value) list, or None when the whole batch raised —
        per-item errors must land in result="error", not "ok"."""
        if self._metrics is None:
            return
        elapsed = time.perf_counter() - start
        n_err = (sum(1 for tag, _ in results if tag == "err")
                 if results is not None else n)
        if n - n_err:
            self._metrics["requests"].inc_key(self._key_ok, n - n_err)
        if n_err:
            self._metrics["requests"].inc_key(self._key_err, n_err)
        self._metrics["latency"].observe_key(self._key_lat, elapsed,
                                             count=n)

    @staticmethod
    def _resolve_target(fn):
        import inspect

        return fn.__call__ if not inspect.isfunction(fn) and not \
            inspect.ismethod(fn) and callable(fn) else fn

    def _register_stream(self, gen):
        """Register a generator result under a stream id (must run on
        the replica's event loop — _streams is loop-confined)."""
        self._sweep_streams()
        self._stream_counter += 1
        self._streams[self._stream_counter] = (gen, time.monotonic(), 0)
        return ("__rt_stream__", self._stream_counter)

    def _limit(self, timeout_s: Optional[float]) -> Optional[float]:
        """Effective per-attempt timeout: the deployment's
        request_timeout_s bounded by the request's remaining deadline
        (propagated proxy -> router -> replica). None = unbounded."""
        if timeout_s is None:
            return self._timeout
        if self._timeout is None:
            return timeout_s
        return min(self._timeout, timeout_s)

    async def _invoke(self, fn, args, kwargs,
                      timeout_s: Optional[float] = None):
        import asyncio
        import functools
        import inspect

        limit = self._limit(timeout_s)
        try:
            target = self._resolve_target(fn)
            if inspect.iscoroutinefunction(target):
                coro = fn(*args, **kwargs)
                result = await (asyncio.wait_for(coro, limit)
                                if limit else coro)
            else:
                # Sync handlers run off-loop so concurrent requests (e.g.
                # @serve.batch coalescing) aren't serialized behind the
                # replica's event loop.
                loop = asyncio.get_running_loop()
                call = loop.run_in_executor(
                    None, functools.partial(fn, *args, **kwargs))
                result = await (asyncio.wait_for(call, limit)
                                if limit else call)
                if inspect.iscoroutine(result):
                    result = await (asyncio.wait_for(result, limit)
                                    if limit else result)
        except asyncio.TimeoutError:
            raise DeadlineExceededError(
                f"request exceeded its deadline ({limit:.3f}s) in "
                f"deployment {self._deployment!r}") from None
        if inspect.isgenerator(result) or inspect.isasyncgen(result):
            return self._register_stream(result)
        return result

    def _sweep_streams(self, idle_s: float = 300.0) -> None:
        """Close streams abandoned by their consumer (client disconnect,
        dropped StreamingResponse) so generators don't leak for the
        replica's lifetime. Lazy sweep on registration — no timers."""
        now = time.monotonic()
        for sid in [s for s, (_, t, _n) in self._streams.items()
                    if now - t > idle_s]:
            gen = self._streams.pop(sid)[0]
            try:
                close = getattr(gen, "close", None) or getattr(
                    gen, "aclose", None)
                if close is not None:
                    res = close()
                    if hasattr(res, "__await__"):
                        import asyncio

                        asyncio.ensure_future(res)
            except Exception:
                pass

    async def handle_request(self, args, kwargs,
                             timeout_s: Optional[float] = None,
                             trace_ctx: Optional[tuple] = None):
        # Sweep abandoned streams from the request path too: a replica
        # whose LAST streaming consumer disconnected would otherwise
        # leak that generator until another streaming request arrives.
        if self._streams:
            self._sweep_streams()
        self._ongoing += 1
        self._total += 1
        start = time.perf_counter()
        # ContextVar, not the thread-local span stack: this coroutine
        # interleaves with other requests on the replica's one event
        # loop, and the binding must follow THIS request across awaits
        # (nested .remote() calls and the LLM engine read it back).
        token = tracing.set_request_context(trace_ctx)
        t0 = time.time()
        ok = True
        try:
            fn = self.callable
            if not callable(fn):
                raise TypeError("deployment is not callable")
            return await self._invoke(fn, args, kwargs, timeout_s)
        except BaseException:
            ok = False
            raise
        finally:
            if trace_ctx is not None:
                tracing.record_span(
                    "replica.handle", trace_id=trace_ctx[0],
                    parent_id=trace_ctx[1], start_s=t0,
                    deployment=self._deployment,
                    **({} if ok else {"error": "handler raised"}))
            tracing.reset_request_context(token)
            self._observe(start, 1, ok)
            self._ongoing -= 1

    async def handle_request_batch(self, items,
                                   timeout_s: Optional[float] = None):
        """Coalesced entry: N requests in ONE actor RPC (the proxy's
        Nagle-style batching — on a host where the per-call actor hop is
        the serving bottleneck, coalescing divides it by the batch).
        Results are per-item isolated: ("ok", value) or ("err", repr).

        Async handlers run concurrently under asyncio.gather with full
        _invoke semantics. Sync handlers run in ONE executor task for
        the whole batch — a single thread hop instead of one per item
        (the per-item hop was the dominant serving cost on a contended
        host), with the event loop staying free for streams and async
        requests. Within-batch items of a sync handler are sequential;
        request_timeout_s bounds the whole batch on that path (a sync
        handler cannot be interrupted item-by-item anyway)."""
        import asyncio
        import inspect

        if self._streams:
            self._sweep_streams()
        # Items are (args, kwargs) or (args, kwargs, trace_ctx) — the
        # proxy ships per-request trace ctx as a third element; older
        # callers (tests, handle fan-out) still send pairs.
        items = [(it[0], it[1], it[2] if len(it) > 2 else None)
                 for it in items]
        self._ongoing += len(items)
        self._total += len(items)
        start = time.perf_counter()
        limit = self._limit(timeout_s)
        out = None
        try:
            fn = self.callable
            if callable(fn) and inspect.iscoroutinefunction(
                    self._resolve_target(fn)):
                async def one(args, kwargs, ctx):
                    # gather() wraps each coroutine in its own task with
                    # a COPY of the current context, so this binding is
                    # per-item even though all items share the loop.
                    token = tracing.set_request_context(ctx)
                    t0 = time.time()
                    err = None
                    try:
                        return ("ok", await self._invoke(fn, args,
                                                         kwargs,
                                                         timeout_s))
                    except Exception as e:  # noqa: BLE001 — isolation
                        err = type(e).__name__
                        return ("err", _err_payload(e))
                    finally:
                        if ctx is not None:
                            attrs = {"deployment": self._deployment}
                            if err:
                                attrs["error"] = err
                            tracing.record_span(
                                "replica.handle", trace_id=ctx[0],
                                parent_id=ctx[1], start_s=t0, **attrs)
                        tracing.reset_request_context(token)

                out = list(await asyncio.gather(
                    *(one(a, k, c) for a, k, c in items)))
                return out

            def run_all():
                out = []
                for a, k, ctx in items:
                    t0 = time.time()
                    try:
                        if not callable(fn):
                            raise TypeError("deployment is not callable")
                        # Sync handlers run on ONE executor thread, so
                        # the thread-local remote context is safe here.
                        with tracing.remote_context(ctx):
                            out.append(("ok", fn(*a, **k)))
                    except Exception as e:  # noqa: BLE001 — isolation
                        out.append(("err", _err_payload(e)))
                    if ctx is not None:
                        tracing.record_span(
                            "replica.handle", trace_id=ctx[0],
                            parent_id=ctx[1], start_s=t0,
                            deployment=self._deployment)
                return out

            loop = asyncio.get_running_loop()
            call = loop.run_in_executor(None, run_all)
            try:
                results = await (asyncio.wait_for(call, limit)
                                 if limit else call)
            except asyncio.TimeoutError:
                raise DeadlineExceededError(
                    f"batch exceeded its deadline ({limit:.3f}s) in "
                    f"deployment {self._deployment!r}") from None
            final = []
            for tag, val in results:
                if tag == "ok":
                    try:
                        if inspect.iscoroutine(val):
                            val = await (asyncio.wait_for(
                                val, limit) if limit
                                else val)
                        if inspect.isgenerator(val) or inspect.isasyncgen(
                                val):
                            val = self._register_stream(val)
                    except Exception as e:  # noqa: BLE001 — isolation
                        tag, val = "err", _err_payload(e)
                final.append((tag, val))
            out = final
            return out
        finally:
            self._observe_batch(start, len(items), out)
            self._ongoing -= len(items)

    async def call_method(self, method, args, kwargs,
                          timeout_s: Optional[float] = None,
                          trace_ctx: Optional[tuple] = None):
        self._ongoing += 1
        self._total += 1
        start = time.perf_counter()
        token = tracing.set_request_context(trace_ctx)
        ok = True
        try:
            return await self._invoke(
                getattr(self.callable, method), args, kwargs, timeout_s)
        except BaseException:
            ok = False
            raise
        finally:
            tracing.reset_request_context(token)
            self._observe(start, 1, ok)
            self._ongoing -= 1

    async def health_check(self) -> bool:
        """Controller liveness probe. A replica whose event loop is
        wedged (sync work on the loop, deadlocked handler) simply never
        answers — the controller counts the timeout. Deployments can add
        their own semantics via a ``check_health`` method (raise =
        unhealthy)."""
        fn = getattr(self.callable, "check_health", None)
        if fn is not None:
            res = fn()
            if hasattr(res, "__await__"):
                await res
        return True

    async def next_chunks(self, stream_id: int, max_n: int = 8):
        """Drain up to ``max_n`` items from a registered stream; returns
        (done, items). The stream is dropped when exhausted."""
        import inspect

        if self._streams:
            self._sweep_streams()
        entry = self._streams.get(stream_id)
        if entry is None:
            return True, []
        gen, _, pulls = entry
        self._streams[stream_id] = (gen, time.monotonic(), pulls + 1)
        items = []
        # The replica's side of the proxy's chunked pull. Pulls of many
        # streams interleave on this one thread; each annotation keeps
        # its own start and end (checked in a recorded trace).
        with tracing.step_span("rt.serve.next_chunks", interleaved=True,
                               first=int(pulls == 0)) as sp:
            done = True
            try:
                if inspect.isasyncgen(gen):
                    async for item in gen:
                        items.append(item)
                        if len(items) >= max_n:
                            done = False
                            break
                else:
                    for item in gen:
                        items.append(item)
                        if len(items) >= max_n:
                            done = False
                            break
            finally:
                if len(items) < max_n:
                    self._streams.pop(stream_id, None)
                sp.set(items=len(items), done=int(done))
        return done, items

    def metrics(self):
        # "streams" lets the controller's drain verb wait for handed-off
        # streaming responses (no longer "ongoing") to finish before the
        # replica is terminated — killing earlier severs them mid-stream.
        return {"ongoing": self._ongoing, "total": self._total,
                "streams": len(self._streams)}

    def reconfigure(self, user_config):
        if hasattr(self.callable, "reconfigure"):
            self.callable.reconfigure(user_config)
        return True


class ServeController:
    """Controller actor: owns deployment state, reconciles replicas.

    Reference: serve/controller.py — ``deploy`` (:330) +
    ``run_control_loop`` (:229). The control loop runs INSIDE the actor
    (``start_loop`` spawns it), so Serve keeps reconciling after driver
    handles are GC'd; routers learn of replica-set changes through the
    blocking ``listen_for_change`` long-poll (reference:
    long_poll.py:184 LongPollHost snapshot-ids), not interval polling.
    """

    def __init__(self):
        import threading

        self.deployments: Dict[str, DeploymentInfo] = {}
        self.replicas: Dict[str, List[Any]] = {}
        # Per-deployment, per-replica (actor-id keyed) probe state:
        # {"probe": outstanding ref|None, "sent": ts, "fails": n,
        #  "ok": answered-at-least-once}. See _health_sweep_locked.
        self._health: Dict[str, Dict[bytes, dict]] = {}
        # Replicas removed from the routable set by drain() but still
        # alive finishing in-flight work; killed once quiescent.
        self._draining: Dict[str, List[Any]] = {}
        self._metrics: Dict[str, List[float]] = {}
        self._last_scale_up: Dict[str, float] = {}
        self._last_scale_down: Dict[str, float] = {}
        self._lock = threading.RLock()
        self._change = threading.Condition(self._lock)
        self._versions: Dict[str, int] = {}
        self._loop_stop = threading.Event()
        self._loop_thread = None

    def _bump_locked(self, name: str) -> None:
        self._versions[name] = self._versions.get(name, 0) + 1
        self._change.notify_all()

    # -- control loop (runs inside the actor process) -----------------------
    def start_loop(self, interval_s: float = 0.25) -> bool:
        import threading

        if self._loop_thread is not None:
            return False

        def loop():
            while not self._loop_stop.wait(interval_s):
                try:
                    self.reconcile()
                except Exception:
                    pass

        self._loop_thread = threading.Thread(
            target=loop, daemon=True, name="serve-control-loop")
        self._loop_thread.start()
        return True

    def stop_loop(self) -> bool:
        self._loop_stop.set()
        return True

    # -- deploy API ----------------------------------------------------------
    def deploy(self, info: DeploymentInfo) -> bool:
        with self._lock:
            existing = self.deployments.get(info.name)
            if existing is not None:
                info.version = existing.version + 1
            self.deployments[info.name] = info
            self._reconcile_deployment(info.name,
                                       redeploy=existing is not None)
        return True

    def delete_deployment(self, name: str) -> bool:
        with self._lock:
            info = self.deployments.pop(name, None)
            victims = self.replicas.pop(name, [])
            victims += self._draining.pop(name, [])
            self._health.pop(name, None)
            self._bump_locked(name)
        metrics = serve_metrics()
        if metrics is not None:
            metrics["replicas"].set(0.0, tags={"deployment": name})
        for r in victims:
            try:
                kill(r)
            except Exception:
                pass
        return info is not None

    # -- graceful drain (ISSUE 19) -------------------------------------------
    def drain(self, name: str, replica_actor_id: Optional[str] = None,
              timeout_s: float = 30.0, migrate: bool = True) -> dict:
        """Gracefully remove ONE replica: stop new assignments (routers
        learn on the next long-poll push; target-count reconciliation
        spawns the replacement), migrate resident LLM sessions to the
        surviving replicas they will re-pin to (same rendezvous hash
        the routers use), let in-flight requests AND handed-off streams
        finish, then terminate. Zero dropped requests, zero 503s
        attributable to the drain — the stateful counterpart to the
        health sweep's kill-and-replace."""
        t0 = time.monotonic()
        report: dict = {"deployment": name, "sessions_migrated": 0,
                        "migrate_errors": 0, "migrate_ms": [],
                        "sessions": [], "timed_out": False}
        with self._lock:
            current = self.replicas.get(name, [])
            victim = None
            if replica_actor_id is None:
                victim = current[0] if current else None
            else:
                for r in current:
                    if r._actor_id.hex() == replica_actor_id:
                        victim = r
                        break
            if victim is None:
                report["error"] = (f"no such replica in deployment "
                                   f"{name!r}")
                return report
            current.remove(victim)
            self._health.get(name, {}).pop(victim._actor_id.binary(),
                                           None)
            self._draining.setdefault(name, []).append(victim)
            report["replica"] = victim._actor_id.hex()
            self._bump_locked(name)
        # Let reconciliation register the replacement handle before
        # choosing migration targets: the rendezvous set must match
        # what routers will re-pin against (calls on a replica still
        # constructing queue in its mailbox, so import can proceed).
        target_wait = min(5.0, timeout_s / 2)
        while time.monotonic() - t0 < target_wait:
            with self._lock:
                info = self.deployments.get(name)
                have = len(self.replicas.get(name, []))
                want = self._target_replicas(name) if info else 0
            if have >= want or have == 0:
                break
            time.sleep(0.05)
        if migrate:
            self._migrate_sessions(name, victim, report,
                                   deadline=t0 + timeout_s)
        # Quiesce: both the request counter and handed-off streams must
        # reach zero on a few consecutive polls (a request may be
        # between router assignment and handle_request entry).
        zero_polls = 0
        while time.monotonic() - t0 < timeout_s:
            try:
                m = get(victim.metrics.remote(), timeout=5)
            except Exception:
                break  # already dead: nothing left to wait for
            if m.get("ongoing", 0) <= 0 and m.get("streams", 0) <= 0:
                zero_polls += 1
                if zero_polls >= 3:
                    break
            else:
                zero_polls = 0
            time.sleep(0.05)
        else:
            report["timed_out"] = True
        report["drained_ms"] = round((time.monotonic() - t0) * 1e3, 3)
        try:
            kill(victim)
        except Exception:
            pass
        with self._lock:
            lst = self._draining.get(name, [])
            if victim in lst:
                lst.remove(victim)
        return report

    def _migrate_sessions(self, name: str, victim, report: dict,
                          deadline: float) -> None:
        """Export every resident session from the draining replica and
        import each into the surviving replica its id rendezvous-hashes
        to. Deployments without session methods (anything that isn't an
        LLM server) drain without migration."""
        try:
            snaps = get(victim.call_method.remote("export_sessions",
                                                  (), {}),
                        timeout=max(5.0, deadline - time.monotonic()))
        except Exception as e:  # noqa: BLE001 — non-LLM deployment
            report["export_skipped"] = repr(e)[:200]
            return
        if not snaps:
            return
        with self._lock:
            targets = list(self.replicas.get(name, []))
        if not targets:
            report["migrate_errors"] = len(snaps)
            report["export_skipped"] = "no surviving replicas"
            return
        keys = [r._actor_id.binary() for r in targets]
        for snap in snaps:
            sid = snap.get("session_id")
            tgt = targets[_session_rendezvous(str(sid), keys)]
            t1 = time.monotonic()
            try:
                get(tgt.call_method.remote("import_session", (snap,),
                                           {}),
                    timeout=max(5.0, deadline - time.monotonic()))
                report["sessions_migrated"] += 1
                report["migrate_ms"].append(
                    round((time.monotonic() - t1) * 1e3, 3))
                report["sessions"].append(sid)
            except Exception as e:  # noqa: BLE001 — keep draining
                report["migrate_errors"] += 1
                report.setdefault("migrate_error_detail",
                                  repr(e)[:200])

    # -- long-poll config push ----------------------------------------------
    def listen_for_change(self, name: str, known_version: int,
                          timeout_s: float = 30.0):
        """Block until the replica set of ``name`` changes past
        ``known_version`` (or timeout); returns (version, replicas,
        router_cfg). Reference: LongPollHost.listen_for_change — routers
        hold one of these calls open instead of polling on an interval.
        router_cfg carries the deployment's retry/admission/deadline
        knobs so every config change reaches routers on the same push
        that delivers replica-set changes."""
        deadline = time.monotonic() + timeout_s
        with self._change:
            while self._versions.get(name, 0) <= known_version:
                remaining = deadline - time.monotonic()
                if remaining <= 0:
                    break
                self._change.wait(remaining)
            return (self._versions.get(name, 0),
                    list(self.replicas.get(name, [])),
                    self._router_cfg_locked(name))

    def _router_cfg_locked(self, name: str) -> dict:
        info = self.deployments.get(name)
        if info is None:
            return {}
        return {
            "max_request_retries": info.max_request_retries,
            "retry_backoff_s": info.retry_backoff_s,
            "idempotent": info.idempotent,
            "max_pending": info.max_pending,
            "queue_timeout_s": info.queue_timeout_s,
            "request_deadline_s": info.request_deadline_s,
        }

    def reconfigure_deployment(self, name: str, user_config) -> int:
        """Push a new user_config to every live replica in parallel;
        returns how many acknowledged (reference: controller.py
        deploy-with-user_config → replica reconfigure; the config-file
        ops path sets this per deployment). New replicas pick the config
        up at creation (_reconcile_deployment)."""
        with self._lock:
            info = self.deployments.get(name)
            if info is None:
                return -1
            info.user_config = user_config
            replicas = list(self.replicas.get(name, []))
        if not replicas:
            return 0
        from ..core import wait as _wait

        refs = [r.reconfigure.remote(user_config) for r in replicas]
        done, _pending = _wait(refs, num_returns=len(refs), timeout=30)
        return len(done)

    def list_deployments(self) -> Dict[str, dict]:
        with self._lock:
            return self._list_deployments_locked()

    def _list_deployments_locked(self) -> Dict[str, dict]:
        return {
            name: {
                "num_replicas": len(self.replicas.get(name, [])),
                "target": self._target_replicas(name),
                "route_prefix": info.route_prefix,
                "version": info.version,
            }
            for name, info in self.deployments.items()
        }

    def get_replicas(self, name: str) -> List[Any]:
        with self._lock:
            return list(self.replicas.get(name, []))

    def get_replica_snapshot(self, name: str):
        with self._lock:
            return (self._versions.get(name, 0),
                    list(self.replicas.get(name, [])),
                    self._router_cfg_locked(name))

    def get_deployment_names(self) -> List[str]:
        with self._lock:
            return list(self.deployments)

    # -- reconciliation ------------------------------------------------------
    def _target_replicas(self, name: str) -> int:
        info = self.deployments.get(name)
        if info is None:
            return 0
        if info.autoscaling is None:
            return info.num_replicas
        return self._autoscale_target(name, info)

    def _autoscale_target(self, name: str, info: DeploymentInfo) -> int:
        """Reference: autoscaling_policy.py:127 get_decision_num_replicas —
        target = ceil(total_ongoing / target_per_replica), clamped, with
        up/downscale delay."""
        cfg = info.autoscaling
        current = len(self.replicas.get(name, []))
        ongoing = self._collect_ongoing(name)
        desired = math.ceil(
            ongoing / max(cfg.target_num_ongoing_requests_per_replica, 1e-9)
        )
        desired = max(cfg.min_replicas, min(cfg.max_replicas, desired))
        now = time.monotonic()
        if desired > current:
            first = self._last_scale_up.setdefault(name, now)
            if now - first >= cfg.upscale_delay_s:
                self._last_scale_up.pop(name, None)
                return desired
            return current
        self._last_scale_up.pop(name, None)
        if desired < current:
            first = self._last_scale_down.setdefault(name, now)
            if now - first >= cfg.downscale_delay_s:
                self._last_scale_down.pop(name, None)
                return desired
            return current
        self._last_scale_down.pop(name, None)
        return current

    def _collect_ongoing(self, name: str) -> float:
        total = 0.0
        refs = []
        replicas = self.replicas.get(name, [])
        for r in replicas:
            refs.append(r.metrics.remote())
        if refs:
            ready, _ = wait(refs, num_returns=len(refs), timeout=1.0)
            for ref in ready:
                try:
                    total += get(ref)["ongoing"]
                except Exception:
                    pass
        return total

    def reconcile(self) -> Dict[str, int]:
        """One control-loop tick (reference: run_control_loop body)."""
        out = {}
        with self._lock:
            names = list(self.deployments)
        for name in names:
            with self._lock:
                if name not in self.deployments:
                    continue
                out[name] = self._reconcile_deployment(name)
        return out

    def _health_sweep_locked(self, name: str, info: DeploymentInfo,
                             current: List[Any]) -> bool:
        """Probe every replica's liveness; evict the ones past the
        failure threshold. Returns True when the replica set changed
        (the caller's target loop then creates replacements — target-
        count reconciliation, never in-place restart, so routers can't
        keep dispatching to a stale handle).

        Probe outcomes per replica (actor-id keyed state):
          - probe resolves OK          -> fails = 0, mark responsive
          - probe resolves with error  -> dead/raising: evict NOW (the
            runtime already knows the actor died; waiting out the
            threshold only extends the outage)
          - probe outstanding past health_check_timeout_s -> hung: count
            one failure, but ONLY once the replica has answered at least
            one probe — a replica still constructing (LLM warmup can
            compile for many seconds) must not be culled mid-warmup.
        """
        now = time.monotonic()
        hstate = self._health.setdefault(name, {})
        threshold = max(1, info.health_check_failure_threshold)
        live_keys = set()
        dead: List[Any] = []
        for r in current:
            key = r._actor_id.binary()
            live_keys.add(key)
            st = hstate.setdefault(key, {"probe": None, "sent": now,
                                         "fails": 0, "ok": False})
            probe = st["probe"]
            if probe is not None:
                ready, _ = wait([probe], num_returns=1, timeout=0)
                if ready:
                    st["probe"] = None
                    try:
                        get(ready[0])
                        st["fails"] = 0
                        st["ok"] = True
                    except Exception:
                        st["fails"] = threshold
                elif now - st["sent"] > info.health_check_timeout_s:
                    st["probe"] = None
                    if st["ok"]:
                        st["fails"] += 1
            if (st["probe"] is None and st["fails"] < threshold
                    and now - st["sent"] >= info.health_check_period_s):
                try:
                    st["probe"] = r.health_check.remote()
                    st["sent"] = now
                except Exception:
                    st["fails"] = threshold
            if st["fails"] >= threshold:
                dead.append((r, key))
        for key in [k for k in hstate if k not in live_keys]:
            hstate.pop(key)
        metrics = serve_metrics()
        if metrics is not None:
            metrics["unhealthy"].set(float(len(dead)),
                                     tags={"deployment": name})
        if not dead:
            return False
        for r, key in dead:
            current.remove(r)
            hstate.pop(key, None)
            try:
                kill(r)  # hung replicas hold a worker process hostage
            except Exception:
                pass
            if metrics is not None:
                metrics["restarts"].inc(1.0, tags={"deployment": name})
        return True

    def _reconcile_deployment(self, name: str, redeploy: bool = False) -> int:
        info = self.deployments[name]
        current = self.replicas.setdefault(name, [])
        if redeploy:
            for r in current:
                try:
                    kill(r)
                except Exception:
                    pass
            current.clear()
            self._health.pop(name, None)
        target = self._target_replicas(name)
        replica_cls = remote(_Replica)
        changed = redeploy
        if not redeploy and info.health_check_period_s is not None:
            changed = self._health_sweep_locked(name, info,
                                                current) or changed
        while len(current) < target:
            changed = True
            opts = dict(info.ray_actor_options)
            actor = replica_cls.options(
                max_concurrency=max(2, info.max_concurrent_queries),
                **opts,
            ).remote(info.deployment_def, info.init_args, info.init_kwargs,
                     request_timeout_s=info.request_timeout_s,
                     user_config=info.user_config,
                     deployment_name=name)
            current.append(actor)
        while len(current) > target:
            victim = current.pop()
            changed = True
            try:
                kill(victim)
            except Exception:
                pass
        metrics = serve_metrics()
        if metrics is not None:
            # Runs in the controller process; telemetry ships it head-ward.
            metrics["replicas"].set(float(len(current)),
                                    tags={"deployment": name})
        if changed:
            self._bump_locked(name)
        return len(current)


class Router:
    """Client-side replica selection (reference: router.py ReplicaSet).

    Round-robin with ENFORCED per-replica in-flight caps: each assigned
    request registers a completion watcher (``core.on_ref_ready``) that
    releases the slot when the result lands, so a replica never holds
    more than ``max_concurrent_queries`` outstanding requests
    (router.py:62,221). Replica-set updates arrive through the
    controller's blocking ``listen_for_change`` long-poll held open by a
    background listener thread (long_poll.py:67 LongPollClient), not
    interval polling.
    """

    def __init__(self, controller, deployment_name: str,
                 max_concurrent_queries: int = 100):
        import threading

        self._controller = controller
        self._name = deployment_name
        self._max_cq = max_concurrent_queries
        self._replicas: List[Any] = []
        # Parallel to _replicas: cached actor-id keys, so the pick loop
        # never re-derives ``_actor_id.binary()`` per replica per
        # request (an O(replicas) allocation storm at 8 replicas that
        # helped INVERT handle throughput vs 1 replica).
        self._keys: List[bytes] = []
        self._version = -1
        self._rr = 0  # sticky pick: index of the previous replica
        self._slack = 16  # see _pick_slot_locked sticky-with-slack
        # keyed by replica actor id (stable across replica-set updates)
        self._inflight: Dict[bytes, int] = {}
        # Router-wide in-flight total -> rt_serve_queue_depth gauge.
        # DRIVER routers only: gauges keep producer tags through absorb,
        # so a nested replica-worker router shipping the same
        # {deployment} key would clobber the driver's live value with
        # its own (usually near-zero) count. The driver (proxy +
        # handles) is the authoritative ingress queue.
        from ..core.runtime import is_worker_process

        self._nq = 0
        self._metrics = None if is_worker_process() else serve_metrics()
        if self._metrics is not None:
            self._qd_key = (("deployment", deployment_name),)
        # Deployment retry/admission/deadline knobs, pushed by the
        # controller on the same long-poll as replica-set changes.
        self._cfg: Dict[str, Any] = {}
        # oid-binary -> replica that ACTUALLY served a retried request
        # (bounded; see replica_for) — streaming consumers must drain
        # next_chunks from the replica that holds the stream, not the
        # dead one originally picked.
        self._retried_replica: Dict[bytes, Any] = {}
        # session id -> pinned replica key (sticky routing). Lazy
        # re-pin: a pin whose replica left the set is re-resolved with
        # the rendezvous hash on next use — the same hash the
        # controller's drain verb used to place the migrated sessions.
        self._sticky: Dict[str, bytes] = {}
        self._waiters = 0  # blocked assigners; gate for notify_all
        self._lock = threading.Lock()
        self._slot_free = threading.Condition(self._lock)
        self._stop = threading.Event()
        self._listener = threading.Thread(
            target=self._listen_loop, daemon=True,
            name=f"serve-router-{deployment_name}")
        self._listener.start()

    def _listen_loop(self):
        """Long-poll: one blocking listen_for_change call held open."""
        while not self._stop.is_set():
            try:
                version, replicas, cfg = get(
                    self._controller.listen_for_change.remote(
                        self._name, self._version),
                    timeout=45,
                )
                with self._slot_free:
                    self._cfg = cfg or {}
                    if version != self._version:
                        self._version = version
                        self._set_replicas_locked(replicas)
                        self._slot_free.notify_all()
            except Exception:
                if self._stop.is_set():
                    return
                time.sleep(0.5)

    def _set_replicas_locked(self, replicas) -> None:
        self._replicas = replicas
        self._keys = [r._actor_id.binary() for r in replicas]
        # Evicted-replica cleanup: in-flight counts keyed by a replica
        # that left the set would otherwise linger forever — its
        # requests fail (actor death) and their _release clamps to the
        # popped key's 0, so the router-wide total (_nq and the shared
        # queue-depth gauge) stays permanently offset: the phantom-
        # queue-depth leak. Give the residual back NOW; late _release
        # calls on the popped key no-op against the clamp.
        live = set(self._keys)
        for key in [k for k in self._inflight if k not in live]:
            residual = self._inflight.pop(key)
            if residual:
                self._note_inflight(-residual)
        if self._waiters:
            self._slot_free.notify_all()

    def _ensure_replicas(self, timeout: float = 5.0) -> None:
        """First-use bootstrap: snapshot directly (the long-poll only
        reports CHANGES past our version)."""
        if self._replicas:
            return
        try:
            version, replicas, cfg = get(
                self._controller.get_replica_snapshot.remote(self._name),
                timeout=timeout,
            )
            with self._slot_free:
                self._cfg = cfg or {}
                if version >= self._version and replicas:
                    self._version = version
                    self._set_replicas_locked(replicas)
        except Exception:
            pass

    def stop(self):
        self._stop.set()
        # Give back this router's outstanding queue-depth contribution:
        # serve.shutdown() drops routers with requests still in flight,
        # and their _release callbacks may never run — without this the
        # deployment-wide total (_qd_totals) stays offset forever and a
        # restarted serve instance inherits a phantom queue depth.
        # Clearing _inflight makes any late _release a no-op (its clamp
        # sees 0), so the residual can't be subtracted twice.
        with self._slot_free:
            residual, self._nq = self._nq, 0
            self._inflight.clear()
        if residual and self._metrics is not None:
            _queue_depth_note(self._name, -residual,
                              self._metrics["queue_depth"], self._qd_key)

    def stats(self) -> Dict[str, Any]:
        """Router-local routing state (for tests/diagnostics)."""
        with self._slot_free:
            return {"replicas": len(self._replicas),
                    "sticky_index": self._rr,
                    "queue_depth": self._nq,
                    "inflight": dict(self._inflight)}

    def _note_inflight(self, delta: int) -> None:
        """Under self._slot_free: track this router's in-flight count
        and mirror the DEPLOYMENT-WIDE total (summed across routers via
        _queue_depth_note) into the gauge — interned key, so the added
        hot-path cost is two uncontended dict stores."""
        self._nq = max(0, self._nq + delta)
        if self._metrics is not None:
            _queue_depth_note(self._name, delta,
                              self._metrics["queue_depth"], self._qd_key)

    def assign(self, method: Optional[str], args, kwargs):
        return self.assign_with_replica(method, args, kwargs)[0]

    def _pick_slot_locked(self, avoid: Optional[bytes] = None):
        """Under self._slot_free: least-loaded pick with a sticky tie
        break. Pure round-robin spreads consecutive requests across
        actors, defeating the core runtime's per-actor submission
        batching and bouncing worker processes in and out of the kernel
        run queue — on a single-core host that HALVED the handle path at
        8 replicas. Preferring the last-used replica while it is no more
        loaded than the least-loaded keeps one worker hot at low load,
        while genuine concurrency (inflight ties broken) still spreads
        by load exactly like the reference's availability-set routing
        (router.py:221). None when all are at capacity.

        REPLICA-LINEAR: the common case is O(1) — when the sticky
        replica's load is already within ``_slack`` of zero it beats or
        ties any scan result (best_load >= 0), so no scan runs and the
        pick cost no longer grows with the replica count. The full
        least-loaded scan (over cached keys) only runs once the hot
        replica is loaded beyond the slack — i.e. under saturation,
        where spreading is the point."""
        n = len(self._replicas)
        if n == 0:
            return None
        if avoid is not None and n > 1:
            # Retry re-dispatch: least-loaded scan SKIPPING the replica
            # that just failed the request. Soft exclusion — when every
            # other replica is at capacity we fall through to the
            # normal pick (retrying the suspect beats shedding).
            best = best_key = best_load = None
            for idx in range(n):
                key = self._keys[idx]
                if key == avoid:
                    continue
                load = self._inflight.get(key, 0)
                if load >= self._max_cq:
                    continue
                if best_load is None or load < best_load:
                    best, best_key, best_load = idx, key, load
            if best is not None:
                self._inflight[best_key] = best_load + 1
                self._note_inflight(1)
                return self._replicas[best], best_key
        if self._rr >= n:
            self._rr = 0
        skey = self._keys[self._rr]
        sload = self._inflight.get(skey, 0)
        if sload < self._max_cq and sload <= self._slack:
            # Equivalent to the scan outcome: sload - best_load <= slack
            # holds for every possible best_load >= 0.
            self._inflight[skey] = sload + 1
            self._note_inflight(1)
            return self._replicas[self._rr], skey
        best = best_key = best_load = None
        for idx in range(n):
            key = self._keys[idx]
            load = self._inflight.get(key, 0)
            if load >= self._max_cq:
                continue
            if best_load is None or load < best_load:
                best, best_key, best_load = idx, key, load
        if best is None:
            return None
        # Sticky-with-slack: keep the previous replica while its load is
        # within `_slack` of the least loaded; spill beyond. Bursts stay
        # packed on one hot replica (per-actor submission batching +
        # worker cache locality), while sustained saturation still
        # spreads by load like the reference's availability-set routing.
        if self._rr != best:
            if sload < self._max_cq and sload - best_load <= self._slack:
                best, best_key, best_load = self._rr, skey, sload
            elif sload < self._max_cq:
                # Slack-overflow spill: route THIS call to the least
                # loaded but keep the anchor — moving it handed the
                # next whole burst to a cold replica (anchor ping-pong
                # was part of the 8-replica handle inversion). The
                # anchor only migrates when it is at hard capacity.
                self._inflight[best_key] = best_load + 1
                self._note_inflight(1)
                return self._replicas[best], best_key
        self._rr = best
        self._inflight[best_key] = best_load + 1
        self._note_inflight(1)
        return self._replicas[best], best_key

    # -- deadlines / admission ----------------------------------------------
    def _deadlines(self, deadline: Optional[float]):
        """(request_deadline, queue_deadline): the end-to-end deadline
        (explicit per-request, else the deployment's request_deadline_s,
        else None) and how long this assign may wait for a slot — the
        deployment's queue_timeout_s (default 30s, the old hardcoded
        bound) clamped so queueing never outlives the deadline."""
        now = time.monotonic()
        if deadline is None:
            rd = self._cfg.get("request_deadline_s")
            deadline = now + rd if rd is not None else None
        qt = self._cfg.get("queue_timeout_s")
        queue_deadline = now + (qt if qt is not None else 30.0)
        if deadline is not None:
            queue_deadline = min(queue_deadline, deadline)
        return deadline, queue_deadline

    def _timeout_for(self, deadline: Optional[float]) -> Optional[float]:
        if deadline is None:
            return None
        return max(0.0, deadline - time.monotonic())

    def _admit_locked(self, queued: bool) -> bool:
        """First time an assign is about to block: check the
        deployment-wide pending bound and register the waiter. Raises
        OverloadedError when the queue is already full."""
        if queued:
            return True
        mp = self._cfg.get("max_pending")
        if mp is not None and _pending_note(self._name, 0) >= mp:
            raise OverloadedError(
                f"deployment {self._name!r} overloaded: pending queue "
                f"is full (max_pending={mp})")
        _pending_note(self._name, 1)
        return True

    def _count_retry(self, reason: str) -> None:
        if self._metrics is not None:
            self._metrics["retries"].inc(
                1.0, tags={"deployment": self._name, "reason": reason})

    def _count_deadline(self) -> None:
        if self._metrics is not None:
            self._metrics["deadline_exceeded"].inc(1.0)

    def _overloaded(self) -> OverloadedError:
        detail = (f" (all at max_concurrent_queries={self._max_cq})"
                  if self._replicas else "")
        return OverloadedError(
            f"deployment {self._name!r} overloaded: no replica "
            f"available{detail}")

    def _submit(self, replica, key, method, args, kwargs,
                deadline: Optional[float] = None,
                ctx: Optional[tuple] = None):
        timeout_s = self._timeout_for(deadline)
        try:
            # remote_context: the actor-submit span this .remote() opens
            # (actor.py) adopts the REQUEST's trace, not a fresh one —
            # the router runs on the proxy loop / executor threads where
            # no thread-local span is open. The ctx also rides as an
            # explicit arg so the replica can stamp its handler span and
            # bind the asyncio request context.
            with tracing.remote_context(ctx):
                if method:
                    ref = replica.call_method.remote(
                        method, args, kwargs, timeout_s, ctx)
                else:
                    ref = replica.handle_request.remote(
                        args, kwargs, timeout_s, ctx)
        except Exception:
            self._release(key)
            raise

        from ..core import on_ref_ready

        on_ref_ready(ref, lambda k=key: self._release(k))
        self._arm_retry(ref, key, ("unary", method, args, kwargs),
                        deadline)
        return ref, replica

    # -- safe retry (replica died before any response byte) -----------------
    def _arm_retry(self, ref, key, call, deadline: Optional[float],
                   slots: int = 1) -> None:
        """Register a one-shot failure interceptor on the request's
        return oid: if the replica dies before the result lands, the
        request is re-dispatched to a healthy replica while the caller
        keeps waiting on the ORIGINAL ref. Zero cost on the success
        path. Disabled for non-idempotent deployments (a duplicate side
        effect is worse than a typed error) and in worker processes
        (the interceptor needs the head runtime's object table)."""
        if self._cfg.get("max_request_retries", 0) <= 0:
            return
        if not self._cfg.get("idempotent", True):
            return
        from ..core.runtime import get_head_runtime

        rt = get_head_runtime()
        if rt is None:
            return
        ctx = {
            "call": call,
            "user_deadline": deadline,
            # Retry chains are always bounded, even with no user
            # deadline: a replacement replica that never comes up must
            # not park the caller forever.
            "deadline": (deadline if deadline is not None
                         else time.monotonic() + 60.0),
            "bad": key,
            "slots": slots,
        }
        rt.intercept_failure(
            ref.id, lambda err, o=ref.id, c=ctx: self._maybe_retry(
                o, c, err))

    def _maybe_retry(self, oid, ctx, error) -> bool:
        """Failure-interceptor body. Runs on whatever thread delivered
        the failure (possibly holding the runtime lock): decide and
        hand off, never block. True = we own completing the oid."""
        if not isinstance(error, (ActorError, WorkerCrashedError)):
            return False  # app exception: not retryable, fail normally
        if time.monotonic() >= ctx["deadline"]:
            return False
        threading.Thread(
            target=self._retry_loop, args=(oid, ctx, error),
            daemon=True, name=f"serve-retry-{self._name}").start()
        return True

    def _pick_for_retry(self, avoid: bytes, deadline: float):
        while True:
            with self._slot_free:
                chosen = self._pick_slot_locked(avoid=avoid)
                if chosen is not None:
                    return chosen
                remaining = deadline - time.monotonic()
                if remaining <= 0:
                    return None
                self._waiters += 1
                try:
                    self._slot_free.wait(min(remaining, 0.5))
                finally:
                    self._waiters -= 1
            self._ensure_replicas()

    def _retry_loop(self, oid, ctx, error) -> None:
        """Re-dispatch a dead request until it lands, the retry budget
        runs out, or the deadline passes. Owns the original oid's
        completion (fail_object / transfer_result)."""
        from ..core import on_ref_ready
        from ..core.runtime import get_head_runtime

        rt = get_head_runtime()
        budget = int(self._cfg.get("max_request_retries", 0))
        backoff0 = float(self._cfg.get("retry_backoff_s", 0.05))
        n_slots = int(ctx.get("slots", 1))
        avoid = ctx["bad"]
        last_err = error
        attempt = 0
        while True:
            attempt += 1
            if attempt > budget:
                rt.fail_object(oid, last_err)
                return
            self._count_retry("actor_died")
            delay = min(backoff0 * (2 ** (attempt - 1)), 1.0)
            if time.monotonic() + delay >= ctx["deadline"]:
                self._count_deadline()
                rt.fail_object(oid, DeadlineExceededError(
                    f"request to {self._name!r} exceeded its deadline "
                    f"while retrying after replica death"))
                return
            time.sleep(delay)
            picked = self._pick_for_retry(avoid, ctx["deadline"])
            if picked is None:
                self._count_deadline()
                rt.fail_object(oid, DeadlineExceededError(
                    f"request to {self._name!r} exceeded its deadline "
                    f"waiting for a healthy replica"))
                return
            replica, key = picked
            if n_slots > 1:
                with self._slot_free:
                    self._inflight[key] = (
                        self._inflight.get(key, 0) + n_slots - 1)
                    self._note_inflight(n_slots - 1)
            timeout_s = self._timeout_for(ctx["user_deadline"])
            kind = ctx["call"][0]
            try:
                if kind == "batch":
                    ref2 = replica.handle_request_batch.remote(
                        ctx["call"][1], timeout_s)
                elif ctx["call"][1]:
                    ref2 = replica.call_method.remote(
                        ctx["call"][1], ctx["call"][2], ctx["call"][3],
                        timeout_s)
                else:
                    ref2 = replica.handle_request.remote(
                        ctx["call"][2], ctx["call"][3], timeout_s)
            except Exception as e:  # noqa: BLE001
                self._release(key, n_slots)
                last_err, avoid = e, key
                continue
            on_ref_ready(ref2, lambda k=key, c=n_slots: self._release(
                k, c))
            done = threading.Event()
            rt.add_ready_watcher(ref2.id, done.set)
            remaining = ctx["deadline"] - time.monotonic()
            if not done.wait(timeout=max(remaining, 0.0)):
                self._count_deadline()
                rt.fail_object(oid, DeadlineExceededError(
                    f"request to {self._name!r} exceeded its deadline "
                    f"mid-retry"))
                return
            status, err = rt.object_status(ref2.id)
            if status == "ready":
                self._note_final_replica(oid, replica)
                rt.transfer_result(ref2.id, oid)
                return
            if isinstance(err, (ActorError, WorkerCrashedError)):
                last_err, avoid = err, key
                continue
            rt.fail_object(oid, err if err is not None else last_err)
            return

    def _note_final_replica(self, oid, replica) -> None:
        with self._slot_free:
            if len(self._retried_replica) > 256:
                self._retried_replica.clear()
            self._retried_replica[oid.binary()] = replica

    def replica_for(self, ref, default):
        """The replica that actually served ``ref`` — the original pick
        unless a safe retry moved the request (streaming consumers must
        drain next_chunks from the live replica holding the stream)."""
        with self._slot_free:
            return self._retried_replica.get(ref.id.binary(), default)

    def try_assign_with_replica(self, method: Optional[str], args,
                                kwargs, deadline: Optional[float] = None):
        """Non-blocking assign: (ref, replica) or None when every
        replica is at capacity — lets the HTTP proxy submit inline on
        its event loop in the common unsaturated case instead of paying
        a thread-pool hop per request. STRICTLY non-blocking: an empty
        replica set returns None (the caller's off-loop slow path runs
        the bootstrap RPC) so a slow controller can never stall the
        proxy's event loop."""
        if not self._replicas:
            return None
        if deadline is None:
            deadline, _ = self._deadlines(None)
        with self._slot_free:
            chosen = self._pick_slot_locked()
        if chosen is None:
            return None
        replica, key = chosen
        return self._submit(replica, key, method, args, kwargs, deadline)

    def assign_with_replica(self, method: Optional[str], args, kwargs,
                            deadline: Optional[float] = None):
        """Pick a replica with a free slot; block (condvar, woken by
        completions and replica-set updates) when all are at capacity.
        Returns (result_ref, replica_handle) — the replica is needed to
        drain streaming responses (``_Replica.next_chunks``).

        Queue-wait is bounded by the deployment's queue_timeout_s (and
        the request deadline); expiry sheds with a typed error —
        OverloadedError (-> 503) for queue timeout, DeadlineExceededError
        (-> 504) when the end-to-end deadline itself passed. max_pending
        bounds how many assigns may block deployment-wide."""
        # Bootstrap BEFORE resolving deadlines: on a fresh router the
        # deployment cfg (request_deadline_s etc.) arrives with the
        # first replica snapshot — resolving first would silently run
        # the request unbounded.
        self._ensure_replicas()
        deadline, queue_deadline = self._deadlines(deadline)
        queued = False
        try:
            while True:
                with self._slot_free:
                    chosen = self._pick_slot_locked()
                    if chosen is None:
                        now = time.monotonic()
                        if deadline is not None and now >= deadline:
                            self._count_deadline()
                            raise DeadlineExceededError(
                                f"request to {self._name!r} exceeded "
                                f"its deadline while queued")
                        if now >= queue_deadline:
                            raise self._overloaded()
                        queued = self._admit_locked(queued)
                        self._waiters += 1
                        try:
                            self._slot_free.wait(
                                min(queue_deadline - now, 1.0))
                        finally:
                            self._waiters -= 1
                if chosen is None:
                    self._ensure_replicas()
                    continue
                replica, key = chosen
                return self._submit(replica, key, method, args, kwargs,
                                    deadline)
        finally:
            if queued:
                _pending_note(self._name, -1)

    # -- sticky sessions (ISSUE 19) ------------------------------------------
    def _pick_session_locked(self, session_id: str):
        """Under self._slot_free: resolve the session's pinned replica
        (rendezvous hash on first use or after its replica left the
        set) and take one slot on it. Returns (replica, key, rerouted)
        or None when the pinned replica is at capacity — session
        affinity means we WAIT for its slot rather than spill the
        session's KV-cache locality to a cold replica."""
        n = len(self._replicas)
        if n == 0:
            return None
        key = self._sticky.get(session_id)
        rerouted = False
        if key is not None and key not in set(self._keys):
            rerouted = True  # pinned replica drained or crashed
            key = None
        if key is None:
            if len(self._sticky) > 4096:
                self._sticky.clear()
            key = self._keys[_session_rendezvous(session_id, self._keys)]
            self._sticky[session_id] = key
        idx = self._keys.index(key)
        load = self._inflight.get(key, 0)
        if load >= self._max_cq:
            return None
        self._inflight[key] = load + 1
        self._note_inflight(1)
        return self._replicas[idx], key, rerouted

    def acquire_session_slot(self, session_id: str,
                             deadline: Optional[float] = None):
        """Two-phase session assign, step 1: pin (or re-pin) the
        session's replica and reserve one slot on it, WITHOUT
        submitting. Returns (replica, key, rerouted, deadline). The
        caller restores crashed sessions on reroute before submitting
        with ``submit_on``; on failure in between it must give the slot
        back via ``release_slot``. Blocking/shedding semantics match
        assign_with_replica (typed 503/504)."""
        self._ensure_replicas()
        deadline, queue_deadline = self._deadlines(deadline)
        queued = False
        try:
            while True:
                with self._slot_free:
                    got = self._pick_session_locked(session_id)
                    if got is not None:
                        replica, key, rerouted = got
                        return replica, key, rerouted, deadline
                    now = time.monotonic()
                    if deadline is not None and now >= deadline:
                        self._count_deadline()
                        raise DeadlineExceededError(
                            f"session request to {self._name!r} "
                            f"exceeded its deadline while queued")
                    if now >= queue_deadline:
                        raise self._overloaded()
                    queued = self._admit_locked(queued)
                    self._waiters += 1
                    try:
                        self._slot_free.wait(
                            min(queue_deadline - now, 1.0))
                    finally:
                        self._waiters -= 1
                self._ensure_replicas()
        finally:
            if queued:
                _pending_note(self._name, -1)

    def submit_on(self, replica, key, method, args, kwargs,
                  deadline: Optional[float] = None,
                  ctx: Optional[tuple] = None):
        """Two-phase session assign, step 2: submit on the slot taken
        by acquire_session_slot. Rides _submit, so the safe-retry
        interceptor still re-dispatches if the pinned replica dies
        before any response byte (re-prefill recovery makes the retried
        request bit-for-bit correct on the survivor)."""
        return self._submit(replica, key, method, args, kwargs, deadline,
                            ctx)

    def release_slot(self, key: bytes) -> None:
        """Give back a slot reserved by acquire_session_slot that was
        never submitted (restore failed, caller bailed)."""
        self._release(key)

    def session_replica(self, session_id: str):
        """Diagnostics: the session's pinned replica key hex, or None."""
        with self._slot_free:
            key = self._sticky.get(session_id)
            return None if key is None else key.hex()

    def assign_session(self, method: Optional[str], args, kwargs,
                       session_id: str,
                       deadline: Optional[float] = None):
        """One-call sticky assign (handle path): acquire + submit.
        Returns (ref, replica, rerouted)."""
        replica, key, rerouted, deadline = self.acquire_session_slot(
            session_id, deadline)
        # _submit gives the slot back itself if the dispatch raises.
        ref, replica = self.submit_on(replica, key, method, args,
                                      kwargs, deadline)
        return ref, replica, rerouted

    def try_assign_batch(self, items, deadline: Optional[float] = None):
        """Assign a COALESCED batch to ONE replica in a single actor
        RPC. Takes as many items as the replica's free slots allow
        (>= 1). Returns (ref, replica, n_taken) or None when every
        replica is at capacity / the set is empty."""
        if not self._replicas:
            return None
        if deadline is None:
            deadline, _ = self._deadlines(None)
        with self._slot_free:
            picked = self._pick_slot_locked()  # takes one slot
            if picked is None:
                return None
            replica, key = picked
            free = self._max_cq - self._inflight.get(key, 0)
            extra = min(len(items) - 1, max(free, 0))
            self._inflight[key] += extra
            self._note_inflight(extra)
            n = 1 + extra
        taken = list(items[:n])
        try:
            ref = replica.handle_request_batch.remote(
                taken, self._timeout_for(deadline))
        except Exception:
            self._release(key, n)
            raise

        from ..core import on_ref_ready

        on_ref_ready(ref, lambda k=key, c=n: self._release(k, c))
        self._arm_retry(ref, key, ("batch", taken), deadline, slots=n)
        return ref, replica, n

    def assign_batch(self, items, deadline: Optional[float] = None):
        """Blocking form of try_assign_batch (saturation path)."""
        self._ensure_replicas()  # cfg before deadlines, as in assign
        deadline, queue_deadline = self._deadlines(deadline)
        queued = False
        try:
            while True:
                got = self.try_assign_batch(items, deadline)
                if got is not None:
                    return got
                with self._slot_free:
                    now = time.monotonic()
                    if deadline is not None and now >= deadline:
                        self._count_deadline()
                        raise DeadlineExceededError(
                            f"batch for {self._name!r} exceeded its "
                            f"deadline while queued")
                    if now >= queue_deadline:
                        raise self._overloaded()
                    queued = self._admit_locked(queued)
                    self._waiters += 1
                    try:
                        self._slot_free.wait(
                            min(queue_deadline - now, 1.0))
                    finally:
                        self._waiters -= 1
                self._ensure_replicas()
        finally:
            if queued:
                _pending_note(self._name, -1)

    def _release(self, key: bytes, n: int = 1) -> None:
        with self._slot_free:
            c = self._inflight.get(key, 0)
            # Clamp ONCE and apply the same released amount to both the
            # per-replica map and the router/deployment totals, so a
            # spurious double-release can't make them diverge.
            released = n if n < c else c
            self._inflight[key] = c - released
            self._note_inflight(-released)
            if self._waiters:
                # Gate the wake: _release runs on EVERY request
                # completion, and an unconditional notify_all was a
                # futex storm with zero waiters in the common
                # unsaturated case.
                self._slot_free.notify_all()
