"""Worker process: task execution loop.

Reference analog: ``python/ray/_private/workers/default_worker.py`` +
``_raylet.pyx`` ``run_task_loop``/``execute_task`` — a worker registers with
its node, then loops receiving task pushes, resolving args, executing, and
storing results (small results inline in the reply, large ones sealed into
the shared-memory store directly, as in ``core_worker.h`` Put/SealOwned).

Transport: a ``multiprocessing`` duplex pipe to the node's worker pool. A
reader thread routes messages: task pushes go to an execution queue; replies
to nested ``get``/``put``/``submit``/``wait`` RPCs (issued from inside user
code via the worker-side runtime) resolve waiting futures by request id.
This mirrors the core worker's own gRPC service + client pair.
"""

from __future__ import annotations

import os
import queue
import sys
import threading
import time
import traceback
from concurrent.futures import Future, ThreadPoolExecutor
from typing import Any, Dict, Optional

from . import serialization
from .exceptions import ActorError, TaskError
from .ids import ObjectID, TaskID
from .object_ref import ObjectRef, install_refcount_hooks
from .object_store import ShmClient
from .serialization import Serializer
from .task_spec import TaskType

_INLINE_LIMIT_ENV = "RT_MAX_DIRECT_CALL_OBJECT_SIZE"


class _ArgSentinel:
    """Placeholder for a top-level ObjectRef arg, replaced before execution."""

    __slots__ = ("index",)

    def __init__(self, index: int):
        self.index = index


class WorkerRuntime:
    """The in-worker runtime backing the public API inside tasks.

    Supports nested ``remote``/``get``/``put``/``wait`` by RPC to the owner
    process over the pipe (the reference routes these through the raylet and
    owner core worker; single-host we go straight to the head runtime).
    """

    def __init__(self, conn, worker_id_hex: str, node_id_hex: str):
        self.conn = conn
        self.worker_id_hex = worker_id_hex
        self.node_id_hex = node_id_hex
        self.shm = ShmClient(node_id_hex)
        self.serializer = Serializer(ref_class=ObjectRef)
        self._send_lock = threading.Lock()
        self._pending_rpcs: Dict[int, Future] = {}
        self._rpc_counter = 0
        self._rpc_lock = threading.Lock()
        self._task_queue: "queue.Queue" = queue.Queue()
        # Count of exec msgs routed to the loop thread but not yet
        # re-routed/executed; the reader's direct-to-executor fast path
        # is only taken at zero (ordering guard, see _route_exec).
        self._route_lock = threading.Lock()
        self._loop_pending = 0
        self._actors: Dict[str, Any] = {}
        self._actor_executors: Dict[str, ThreadPoolExecutor] = {}
        # (actor_hex, group_name) -> that group's own capped executor
        self._group_executors: Dict[tuple, ThreadPoolExecutor] = {}
        self._actor_method_groups: Dict[str, Dict[str, str]] = {}
        # actor_hex -> persistent asyncio loop (async actors)
        self._actor_loops: Dict[str, Any] = {}
        self._shutdown = threading.Event()
        self.current_task_id: Optional[TaskID] = None
        self._put_counter = 0
        self._out_q: list = []
        self._out_cond = threading.Condition()
        self._sending = False
        self._sender_thread = threading.Thread(
            target=self._sender_loop, daemon=True, name="rt-worker-sender")
        self._sender_thread.start()
        # Telemetry plane (reference: per-node metrics agent): a flusher
        # ships this process's metric deltas + finished spans to the head
        # every metrics_report_interval_ms over the existing pipe, plus a
        # final flush at clean exit (run_task_loop teardown).
        from .config import config as _config

        self._telemetry_exporter = None
        self._task_latency = None
        if _config().telemetry_enabled:
            from ..observability.metrics import core_metrics
            from ..observability.telemetry import TelemetryExporter

            self._task_latency = core_metrics()["task_latency_s"]
            self._telemetry_exporter = TelemetryExporter(
                node=node_id_hex[:8], worker=worker_id_hex[:8],
                proc=f"worker {worker_id_hex[:8]}")
            threading.Thread(
                target=self._telemetry_loop, daemon=True,
                name="rt-worker-telemetry").start()
        # Borrower protocol (reference_count.h borrower reports): every ref
        # held in this worker pins the object at the owner; GC of the local
        # ref releases the pin via a fire-and-forget message.
        install_refcount_hooks(
            add=self._ref_add, remove=self._ref_del, borrow=self._ref_add
        )

    def _ref_add(self, oid) -> None:
        try:
            self._send(("refadd", oid.binary()))
        except Exception:
            pass

    def _ref_del(self, oid) -> None:
        try:
            self._send(("refdel", oid.binary()))
        except Exception:
            pass

    # -- transport -----------------------------------------------------------
    def _send(self, msg) -> None:
        """Send inline when idle; enqueue for the sender thread under
        load (it coalesces bursts — e.g. a run of task-done replies —
        into one pipe frame). The inline path skips a cross-thread
        handoff that cost sync 1:1 calls ~half their throughput on
        1-core hosts (r3 regression). FIFO is preserved: inline runs
        only when nothing is queued, the sender is not mid-drain
        (_sending), and the pipe lock is free."""
        with self._out_cond:
            if (self._out_q or self._sending
                    or not self._send_lock.acquire(False)):
                self._out_q.append(msg)
                self._out_cond.notify()
                return
        try:
            self.conn.send(msg)
        except (BrokenPipeError, OSError):
            # Same contract as the sender loop: a mute-but-alive worker
            # would hang its callers forever — die loudly.
            os._exit(1)
        finally:
            self._send_lock.release()

    def _sender_loop(self) -> None:
        while True:
            with self._out_cond:
                self._sending = False
                self._out_cond.notify_all()  # wake flush_outbound
                while not self._out_q and not self._shutdown.is_set():
                    self._out_cond.wait()
                if self._shutdown.is_set() and not self._out_q:
                    return
                msgs, self._out_q = self._out_q, []
                self._sending = True
            try:
                with self._send_lock:
                    self.conn.send(
                        msgs[0] if len(msgs) == 1 else ("batch", msgs))
            except (BrokenPipeError, OSError):
                # The pipe to the owner is gone: a mute-but-alive worker
                # would hang its callers forever — die loudly so the
                # owner's death path fails/retries our tasks.
                os._exit(1)

    def _telemetry_loop(self) -> None:
        from .config import config as _config

        interval = max(0.05, _config().metrics_report_interval_ms / 1000.0)
        while not self._shutdown.wait(interval):
            self._flush_telemetry()

    def _flush_telemetry(self) -> None:
        exporter = self._telemetry_exporter
        if exporter is None:
            return
        try:
            payload = exporter.collect()
            if payload is not None:
                self._send(("telemetry", payload))
        except Exception:  # noqa: BLE001 — telemetry must never kill work
            pass

    def flush_outbound(self, timeout: float = 5.0) -> None:
        """Block until every queued outbound message hit the pipe (or
        timeout). Called on worker exit so final replies aren't lost."""
        import time as _time

        deadline = _time.monotonic() + timeout
        with self._out_cond:
            self._out_cond.notify_all()
            while ((self._out_q or self._sending)
                   and _time.monotonic() < deadline):
                self._out_cond.wait(0.05)

    def _rpc(self, kind: str, *payload) -> Any:
        with self._rpc_lock:
            self._rpc_counter += 1
            req_id = self._rpc_counter
            fut: Future = Future()
            self._pending_rpcs[req_id] = fut
        self._send((kind, req_id) + payload)
        return fut.result()

    def _reader_loop(self) -> None:
        try:
            while not self._shutdown.is_set():
                frame = self.conn.recv()
                msgs = frame[1] if frame[0] == "batch" else (frame,)
                for msg in msgs:
                    kind = msg[0]
                    if kind == "aexec":
                        self._route_aexec(msg)
                    elif kind == "exec":
                        self._route_exec(msg)
                    elif kind == "reply":
                        _, req_id, ok, value = msg
                        with self._rpc_lock:
                            fut = self._pending_rpcs.pop(req_id, None)
                        if fut is not None:
                            if ok:
                                fut.set_result(value)
                            else:
                                fut.set_exception(value)
                    elif kind == "revoke":
                        # Owner recall of queued-but-unstarted tasks
                        # (sent while this worker blocks in get/wait):
                        # pull matching execs out of the local queue so
                        # the scheduler can run them on another worker
                        # instead of starving them behind the blocked
                        # head-of-line task. Races benignly with the
                        # exec loop: a task it already popped is simply
                        # not revoked.
                        _, wanted = msg
                        wanted = set(wanted)
                        kept, revoked = [], []
                        while True:
                            try:
                                q = self._task_queue.get_nowait()
                            except queue.Empty:
                                break
                            if (q is not None and q[0] == "exec"
                                    and q[1] in wanted):
                                revoked.append(q[1])
                            else:
                                kept.append(q)
                        for q in kept:
                            self._task_queue.put(q)
                        if revoked:
                            # These were counted at _route_exec time but
                            # will never be popped by the loop thread.
                            with self._route_lock:
                                self._loop_pending -= len(revoked)
                        self._send(("revoked", revoked))
                    elif kind == "exit":
                        self._shutdown.set()
                        self._task_queue.put(None)
                    elif kind == "drain_exit":
                        # Graceful: already-queued tasks run first, then
                        # the loop stops (reference: __ray_terminate__).
                        self._task_queue.put(None)
                    elif kind == "destroy_actor":
                        # Shared-process actor eviction: rides the task
                        # queue so queued methods drain first; the host
                        # worker itself lives on.
                        with self._route_lock:
                            self._loop_pending += 1
                        self._task_queue.put(msg)
        except (EOFError, OSError):
            self._shutdown.set()
            self._task_queue.put(None)
            os._exit(1)

    # -- public-API backing (called via ray_tpu.get/put/... inside tasks) ----
    def get(self, refs, timeout=None):
        single = isinstance(refs, ObjectRef)
        ref_list = [refs] if single else list(refs)
        payload = self._rpc("get", [r.id.binary() for r in ref_list], timeout)
        values = [self._materialize(entry) for entry in payload]
        for v in values:
            if isinstance(v, Exception):
                raise v
        return values[0] if single else values

    def put(self, value):
        serialized = self.serializer.serialize(value)
        size = serialized.frame_bytes()
        self._put_counter += 1
        inline_limit = int(os.environ.get(_INLINE_LIMIT_ENV, 100 * 1024))
        task_id = self.current_task_id or TaskID.nil()
        object_id = ObjectID.for_put(task_id, self._put_counter)
        if size <= inline_limit:
            oid_bin = self._rpc("put", object_id.binary(),
                                ("inline", serialized.to_bytes()))
        else:
            # Zero-copy: buffers memcpy straight into the shm arena.
            self.shm.create_and_seal_serialized(object_id, serialized)
            oid_bin = self._rpc("put", object_id.binary(), ("shm", size))
        ref = ObjectRef(ObjectID(oid_bin), _register=False)
        ref._counted = True  # head's put handler took the +1
        return ref

    def wait(self, refs, num_returns=1, timeout=None, fetch_local=True):
        ids = [r.id.binary() for r in refs]
        ready_bins = self._rpc("wait", ids, num_returns, timeout)
        ready_set = set(ready_bins)
        ready = [r for r in refs if r.id.binary() in ready_set]
        not_ready = [r for r in refs if r.id.binary() not in ready_set]
        return ready, not_ready

    def submit_task(self, spec_blob: bytes):
        """Nested task/actor submission; owner stays the head runtime (v1).

        The head pins each return id on this worker's behalf before
        replying, so the refs are constructed unregistered-but-counted:
        their GC sends the matching release.
        """
        return_bins = self._rpc("submit", spec_blob)
        refs = []
        for b in return_bins:
            ref = ObjectRef(ObjectID(b), _register=False)
            ref._counted = True
            refs.append(ref)
        return refs

    def submit_spec(self, spec):
        return self.submit_task(serialization.dumps(spec))

    def kill_actor(self, actor_id_bin: bytes, no_restart: bool = True):
        return self._rpc("kill_actor", actor_id_bin, no_restart)

    def cancel(self, object_id_bin: bytes, force: bool):
        return self._rpc("cancel", object_id_bin, force)

    def _materialize(self, entry, priority: int = 0):
        """priority: 0 = blocking get, 2 = task-arg prefetch — consumed
        by the daemon's PullManager (get > wait > task-args ordering,
        reference: ``pull_manager.h:47``)."""
        kind, payload = entry
        if kind == "inline":
            return self.serializer.deserialize(payload)
        if kind == "shm":
            oid_bin, size = payload[0], payload[1]
            node_hex = payload[2] if len(payload) > 2 else None
            try:
                view = self.shm.read(ObjectID(oid_bin), size, node_hex)
            except Exception:
                # Object lives on another HOST (arena not attachable).
                # Daemon-backed workers: the daemon intercepts this RPC
                # and pulls PEER-TO-PEER from the holder's ObjectServer
                # (node_daemon.PullManager); the head relay is only the
                # fallback (reference: PullManager -> remote
                # ObjectManager push).
                frame = self._rpc("fetch_object", oid_bin, priority)
                return self.serializer.deserialize(frame)
            return self.serializer.deserialize(view)
        if kind == "error":
            return payload
        raise ValueError(f"bad entry kind {kind}")

    # -- task execution ------------------------------------------------------
    def _resolve_args(self, args_frame: bytes, resolved: Dict[int, Any]):
        args, kwargs = self.serializer.deserialize(args_frame)

        def sub(x):
            return resolved[x.index] if isinstance(x, _ArgSentinel) else x

        args = [sub(a) for a in args]
        kwargs = {k: sub(v) for k, v in kwargs.items()}
        return args, kwargs

    def _store_results(self, task_id_hex: str, values, num_returns: int):
        """Serialize results; inline small, seal large into shm."""
        if num_returns == 1:
            values = [values]
        elif num_returns == 0:
            values = []
        else:
            values = list(values)
            if len(values) != num_returns:
                raise ValueError(
                    f"task declared num_returns={num_returns} but returned "
                    f"{len(values)} values"
                )
        inline_limit = int(os.environ.get(_INLINE_LIMIT_ENV, 100 * 1024))
        out = []
        task_id = TaskID.from_hex(task_id_hex)
        for i, v in enumerate(values):
            serialized = self.serializer.serialize(v)
            size = serialized.frame_bytes()
            oid = ObjectID.for_return(task_id, i)
            if size <= inline_limit:
                out.append(("inline", serialized.to_bytes()))
            else:
                # Zero-copy seal straight into the shm arena.
                self.shm.create_and_seal_serialized(oid, serialized)
                out.append(("shm", size))
        return out

    def _execute_one(self, msg) -> None:
        (_, task_id_hex, payload) = msg
        task_type = TaskType(payload["task_type"])
        prev_task = self.current_task_id
        self.current_task_id = TaskID.from_hex(task_id_hex)
        env_undo = None
        exec_start = time.perf_counter()
        try:
            if payload.get("runtime_env"):
                from ..runtime_env import apply_runtime_env

                env_undo = apply_runtime_env(payload["runtime_env"])
            resolved = {
                i: self._materialize(entry, priority=2)
                for i, entry in payload.get("resolved_args", {}).items()
            }
            args, kwargs = self._resolve_args(payload["args_frame"], resolved)
            from ..observability import tracing

            trace_cm = tracing.remote_context(payload.get("trace_ctx"))
            span_cm = tracing.span(f"task.execute {payload.get('name', '')}",
                                   task_id=task_id_hex)
            if task_type == TaskType.NORMAL_TASK:
                fn = serialization.loads(payload["function_blob"])
                with trace_cm, span_cm:
                    result = fn(*args, **kwargs)
            elif task_type == TaskType.ACTOR_CREATION_TASK:
                cls = serialization.loads(payload["function_blob"])
                with trace_cm, span_cm:
                    instance = cls(*args, **kwargs)
                actor_hex = payload["actor_id"]
                self._actors[actor_hex] = instance
                maxc = payload.get("max_concurrency", 1)
                # Serial actors get a 1-thread executor too: the single
                # executor thread preserves call order AND lets the
                # reader submit methods directly (_route_exec fast
                # path) instead of bouncing through the loop thread.
                self._actor_executors[actor_hex] = ThreadPoolExecutor(
                    max(1, maxc))
                # Concurrency groups: each named group gets its OWN
                # executor with its own cap; methods carry their group via
                # the @method(concurrency_group=...) annotation (reference:
                # transport/concurrency_group_manager.h).
                groups = payload.get("concurrency_groups") or {}
                for gname, limit in groups.items():
                    self._group_executors[(actor_hex, gname)] = (
                        ThreadPoolExecutor(max(1, int(limit))))
                self._actor_method_groups[actor_hex] = {
                    name: getattr(attr, "_concurrency_group")
                    for name, attr in vars(cls).items()
                    if hasattr(attr, "_concurrency_group")
                }
                # Async actors: ONE persistent event loop for the actor's
                # lifetime; every coroutine call lands on it and awaits
                # interleave (reference: fiber/asyncio per-actor loop,
                # transport/fiber.h — NOT a throwaway loop per call).
                import inspect as _inspect

                if any(_inspect.iscoroutinefunction(v)
                       for v in vars(cls).values()):
                    self._actor_loops[actor_hex] = self._start_actor_loop()
                result = None
            elif task_type == TaskType.ACTOR_TASK:
                actor_hex = payload["actor_id"]
                instance = self._actors.get(actor_hex)
                if instance is None:
                    raise ActorError(msg="actor instance not found on worker")
                method = getattr(instance, payload["method_name"])
                with trace_cm, span_cm:
                    result = method(*args, **kwargs)
                import inspect

                if inspect.iscoroutine(result):
                    import asyncio

                    loop = self._actor_loops.get(actor_hex)
                    if loop is None:
                        loop = self._start_actor_loop()
                        self._actor_loops[actor_hex] = loop
                    # run on the actor's persistent loop: concurrent calls
                    # (one executor slot each) interleave at awaits
                    result = asyncio.run_coroutine_threadsafe(
                        result, loop).result()
            else:
                raise ValueError(f"bad task type {task_type}")
            results = self._store_results(
                task_id_hex, result, payload["num_returns"]
            )
            self._send(("done", task_id_hex, results))
        except BaseException as e:  # noqa: BLE001 — report, owner decides retry
            err = TaskError.from_exception(e, payload.get("name", ""))
            self._send(("error", task_id_hex, serialization.dumps(err),
                        isinstance(e, Exception)))
        finally:
            if env_undo:
                from ..runtime_env import restore_runtime_env

                restore_runtime_env(env_undo)
            if self._task_latency is not None:
                self._task_latency.observe(time.perf_counter() - exec_start)
                self._telemetry_exporter.record_flight(
                    task_id_hex, time.perf_counter() - exec_start)
            self.current_task_id = prev_task

    def _start_actor_loop(self):
        """Persistent asyncio loop on its own thread (async actors)."""
        import asyncio

        loop = asyncio.new_event_loop()
        t = threading.Thread(target=loop.run_forever, daemon=True,
                             name="actor-asyncio-loop")
        t.start()
        return loop

    def _pick_executor(self, payload) -> Optional[ThreadPoolExecutor]:
        actor_hex = payload.get("actor_id")
        if actor_hex is None:
            return None
        return self._pick_executor_fast(actor_hex, payload.get("method_name"))

    def _pick_executor_fast(self, actor_hex: str,
                            method_name) -> Optional[ThreadPoolExecutor]:
        group = self._actor_method_groups.get(actor_hex, {}).get(method_name)
        if group is not None:
            executor = self._group_executors.get((actor_hex, group))
            if executor is not None:
                return executor
        return self._actor_executors.get(actor_hex)

    def _route_exec(self, msg) -> None:
        """Route an exec push from the reader thread. Fast path: an
        actor task whose executor already exists is submitted straight
        from the reader, skipping the reader→loop-thread handoff (one
        fewer context switch per sync call on 1-core hosts). Ordering
        guard: direct submission is only taken when NOTHING is pending
        in the loop queue (_loop_pending == 0), so a method can never
        overtake its actor's creation or an earlier queued method."""
        payload = msg[2]
        if TaskType(payload["task_type"]) == TaskType.ACTOR_TASK:
            with self._route_lock:
                if self._loop_pending == 0:
                    executor = self._pick_executor(payload)
                    if executor is not None:
                        try:
                            executor.submit(self._execute_one, msg)
                            return
                        except RuntimeError:
                            # Executor shut down mid-drain: tell the
                            # owner so it can reschedule; a raised
                            # RuntimeError would kill the reader thread
                            # and leave the owner hanging instead.
                            err = TaskError.from_exception(
                                RuntimeError("worker draining"),
                                payload.get("name", ""))
                            self._send(("error", msg[1],
                                        serialization.dumps(err), True))
                            return
                self._loop_pending += 1
        else:
            with self._route_lock:
                self._loop_pending += 1
        self._task_queue.put(msg)

    def _route_aexec(self, msg) -> None:
        """Route a compact actor-call frame: ("aexec", task_id_hex,
        actor_hex, method_name, args_frame, resolved|None, num_returns,
        trace_ctx). Same ordering guard as _route_exec; the fallback
        re-wraps into a legacy exec payload so the loop thread's queue
        stays uniform (creation-before-method ordering preserved)."""
        actor_hex = msg[2]
        with self._route_lock:
            if self._loop_pending == 0:
                executor = self._pick_executor_fast(actor_hex, msg[3])
                if executor is not None:
                    try:
                        executor.submit(self._execute_actor_fast, msg)
                        return
                    except RuntimeError:
                        err = TaskError.from_exception(
                            RuntimeError("worker draining"), msg[3] or "")
                        self._send(("error", msg[1],
                                    serialization.dumps(err), True))
                        return
            self._loop_pending += 1
        self._task_queue.put(("exec", msg[1], {
            "task_type": TaskType.ACTOR_TASK.value,
            "function_blob": None,
            "method_name": msg[3],
            "actor_id": actor_hex,
            "args_frame": msg[4],
            "resolved_args": msg[5] or {},
            "num_returns": msg[6],
            "name": f"actor.{msg[3]}",
            "trace_ctx": msg[7],
        }))

    def _execute_actor_fast(self, msg) -> None:
        """Execute one aexec frame on the actor's executor thread —
        the sync-call hot path: no payload dict, no runtime_env check,
        and tracing contexts only materialize when tracing is on."""
        (_, task_id_hex, actor_hex, method_name, args_frame,
         resolved_entries, num_returns, trace_ctx) = msg
        prev_task = self.current_task_id
        self.current_task_id = TaskID.from_hex(task_id_hex)
        exec_start = time.perf_counter()
        try:
            instance = self._actors.get(actor_hex)
            if instance is None:
                raise ActorError(msg="actor instance not found on worker")
            method = getattr(instance, method_name)
            resolved = ({i: self._materialize(entry, priority=2)
                         for i, entry in resolved_entries.items()}
                        if resolved_entries else {})
            args, kwargs = self._resolve_args(args_frame, resolved)
            from ..observability import tracing

            if trace_ctx is not None or tracing.get_tracer().enabled:
                with tracing.remote_context(trace_ctx), \
                        tracing.span(f"task.execute actor.{method_name}",
                                     task_id=task_id_hex):
                    result = method(*args, **kwargs)
            else:
                result = method(*args, **kwargs)
            import inspect

            if inspect.iscoroutine(result):
                import asyncio

                loop = self._actor_loops.get(actor_hex)
                if loop is None:
                    loop = self._start_actor_loop()
                    self._actor_loops[actor_hex] = loop
                result = asyncio.run_coroutine_threadsafe(
                    result, loop).result()
            results = self._store_results(task_id_hex, result, num_returns)
            self._send(("done", task_id_hex, results))
        except BaseException as e:  # noqa: BLE001 — report, owner decides
            err = TaskError.from_exception(
                e, f"actor.{method_name}")
            self._send(("error", task_id_hex, serialization.dumps(err),
                        isinstance(e, Exception)))
        finally:
            if self._task_latency is not None:
                self._task_latency.observe(time.perf_counter() - exec_start)
                self._telemetry_exporter.record_flight(
                    task_id_hex, time.perf_counter() - exec_start)
            self.current_task_id = prev_task

    def _destroy_actor(self, actor_hex: str) -> None:
        """Evict one shared-process actor instance; the worker lives on.
        In-flight methods keep their instance reference and finish;
        later arrivals fail with "actor instance not found"."""
        self._actors.pop(actor_hex, None)
        ex = self._actor_executors.pop(actor_hex, None)
        if ex is not None:
            ex.shutdown(wait=False)
        for key in [k for k in self._group_executors
                    if k[0] == actor_hex]:
            self._group_executors.pop(key).shutdown(wait=False)
        self._actor_method_groups.pop(actor_hex, None)
        loop = self._actor_loops.pop(actor_hex, None)
        if loop is not None:
            # Stop only once idle: in-flight async methods still run on
            # this loop (their executor threads block on
            # run_coroutine_threadsafe(...).result()); stopping now
            # would strand those futures and leak the blocked threads.
            import asyncio

            def _stop_when_idle():
                if any(not t.done() for t in asyncio.all_tasks(loop)):
                    loop.call_later(0.05, _stop_when_idle)
                else:
                    loop.stop()

            try:
                loop.call_soon_threadsafe(_stop_when_idle)
            except Exception:  # noqa: BLE001 — loop already closed
                pass

    def run_task_loop(self) -> None:
        reader = threading.Thread(target=self._reader_loop, daemon=True,
                                  name="worker-reader")
        reader.start()
        self._send(("register", os.getpid()))
        while not self._shutdown.is_set():
            msg = self._task_queue.get()
            if msg is None:
                break
            if msg[0] == "destroy_actor":
                with self._route_lock:
                    self._loop_pending -= 1
                self._destroy_actor(msg[1])
                continue
            payload = msg[2]
            executor = None
            if TaskType(payload["task_type"]) == TaskType.ACTOR_TASK:
                executor = self._pick_executor(payload)
            if executor is not None:
                executor.submit(self._execute_one, msg)
                with self._route_lock:
                    self._loop_pending -= 1
            else:
                # Decrement before executing: the routing decision is
                # made, and a long-running inline task must not park the
                # reader's actor fast path behind it.
                with self._route_lock:
                    self._loop_pending -= 1
                self._execute_one(msg)
        if not self._shutdown.is_set():
            # drain_exit: let already-submitted actor tasks finish so
            # their replies aren't lost (graceful __ray_terminate__
            # semantics); hard "exit" skips straight to teardown.
            for ex in (list(self._actor_executors.values())
                       + list(self._group_executors.values())):
                ex.shutdown(wait=True)
        # Final telemetry flush AFTER the executors drained, so the last
        # tasks' latency observations and spans ship before the process
        # exits (a worker that finishes and exits between periodic
        # flushes must still appear in the head's timeline/metrics).
        # collect() consumes the deltas, so the outbound drain runs on
        # BOTH exit paths — bounded short on hard exit, where the owner
        # may already have torn the pipe down.
        self._flush_telemetry()
        self.flush_outbound(
            timeout=5.0 if not self._shutdown.is_set() else 1.0)
        self.shm.close()


_worker_runtime: Optional[WorkerRuntime] = None


def get_worker_runtime() -> Optional[WorkerRuntime]:
    return _worker_runtime


def worker_entry(conn, worker_id_hex: str, node_id_hex: str, env: dict) -> None:
    """Child-process entrypoint (spawned by the worker pool)."""
    global _worker_runtime
    # The worker's JAX platform and compile cache come from here and
    # from the inherited environment (JAX_PLATFORMS,
    # JAX_COMPILATION_CACHE_DIR): JAX reads both when it is imported.
    os.environ.update(env or {})
    # Make this process identifiable in `ps` (reference: setproctitle).
    sys.argv[0] = f"rt::worker::{worker_id_hex[:8]}"
    from .log_monitor import redirect_worker_streams

    redirect_worker_streams(worker_id_hex)
    from .config import config as _config

    if _config().tracing_enabled:
        from ..observability import tracing

        tracing.enable()
    _worker_runtime = WorkerRuntime(conn, worker_id_hex, node_id_hex)
    # Route the public API to this runtime inside the worker process.
    from . import runtime as runtime_mod

    runtime_mod._set_worker_mode(_worker_runtime)
    try:
        _worker_runtime.run_task_loop()
    except KeyboardInterrupt:
        pass
    finally:
        # Outbound replies are sent by an async sender thread: flush the
        # tail (final task-done replies on drain_exit) before the process
        # exits, or callers hang on results that were computed but never
        # hit the pipe.
        _worker_runtime.flush_outbound()
