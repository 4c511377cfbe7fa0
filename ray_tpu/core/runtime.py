"""Head-process runtime: driver core worker + control plane composition.

Reference analog: ``src/ray/core_worker/core_worker.h`` (task submission,
object put/get/wait, reference counting, recovery) fused with the driver-side
bootstrap of ``python/ray/_private/worker.py``. One :class:`Runtime` instance
per driver composes:

  - :class:`~.gcs.GlobalControlStore` — cluster metadata authority
  - :class:`~.scheduler.ClusterScheduler` + per-node :class:`NodeManager`s
  - object directory + ownership/reference counting (reference_count.h:61)
  - task manager with lineage retention + retries (task_manager.h:105)
  - actor manager with restart FT (gcs_actor_manager.h:214)
  - object recovery via lineage re-execution (object_recovery_manager.h:41)

Worker processes talk to it over pipes (see ``worker_main.py``); inside a
worker the module-level API routes to the worker's own runtime adapter.
"""

from __future__ import annotations

import importlib.util
import json
import os
import subprocess
import sys
import threading
import time
from concurrent.futures import Future
from concurrent.futures import TimeoutError as _FutTimeout
from dataclasses import dataclass, field
from typing import Any, Callable, Dict, List, Optional, Tuple

from . import serialization
from .config import (
    Config,
    config,
    export_compile_cache_dir,
    jax_pinned_to_cpu,
)
from .exceptions import (
    ActorDiedError,
    ActorError,
    GetTimeoutError,
    ObjectLostError,
    TaskCancelledError,
    TaskError,
    WorkerCrashedError,
)
from .gcs import ActorInfo, ActorState, GcsClient, GlobalControlStore, JobInfo
from .ids import ActorID, JobID, NodeID, ObjectID, TaskID, WorkerID
from .object_ref import ObjectRef, install_refcount_hooks
from .object_store import MemoryStore
from .scheduler import ClusterScheduler, NodeManager, PendingLease
from .serialization import Serializer
from .task_spec import SchedulingStrategy, TaskSpec, TaskType
from ..observability import event_stats as _event_stats
from ..observability import hotpath as _hotpath
from .worker_pool import WorkerHandle


class _ObjStatus:
    PENDING = "PENDING"
    READY = "READY"
    FAILED = "FAILED"
    LOST = "LOST"


@dataclass
class _ObjectEntry:
    status: str = _ObjStatus.PENDING
    # location: ("memory", frame) | ("shm", node_id, size)
    location: Optional[tuple] = None
    error: Optional[Exception] = None
    futures: List[Future] = field(default_factory=list)
    waiting_tasks: List[TaskID] = field(default_factory=list)
    creating_task: Optional[TaskID] = None
    # one-shot callbacks fired (outside the lock) on READY/FAILED — the
    # async wait/watch path; unlike futures these don't materialize values
    watchers: List = field(default_factory=list)
    # one-shot hook consulted BEFORE a failure is finalized (serve-plane
    # safe retry): fn(error) -> True takes ownership of completing the
    # oid later, so futures/watchers stay parked instead of seeing the
    # transient error. See Runtime.intercept_failure.
    failure_interceptor: Optional[Callable] = None


@dataclass
class _TaskRecord:
    spec: TaskSpec
    retries_left: int
    node: Optional[NodeManager] = None
    worker: Optional[WorkerHandle] = None
    lease: Optional[PendingLease] = None
    state: str = "PENDING"  # PENDING|RUNNING|DONE|FAILED|CANCELLED
    deps_remaining: int = 0
    resources_released: bool = False
    # Flight recorder: monotonic stamp per lifecycle transition
    # (submitted/queued/scheduled/dispatched/finished|failed). None when
    # the recorder is off — one attribute slot, zero dict cost.
    state_ts: Optional[Dict[str, float]] = None


@dataclass
class _ActorRecord:
    actor_id: ActorID
    creation_spec: TaskSpec
    state: str = ActorState.PENDING
    node: Optional[NodeManager] = None
    worker: Optional[WorkerHandle] = None
    pending: List[TaskSpec] = field(default_factory=list)
    in_flight: Dict[bytes, TaskSpec] = field(default_factory=dict)
    restarts_left: int = 0
    seq: int = 0
    methods: Dict[str, dict] = field(default_factory=dict)
    creation_pins_released: bool = False
    resources_released: bool = False
    termination_requested: bool = False


class Runtime:
    """The head runtime (driver process)."""

    def __init__(self, num_cpus: Optional[float] = None,
                 num_nodes: int = 1,
                 resources: Optional[Dict[str, float]] = None,
                 object_store_memory: Optional[int] = None,
                 env: Optional[dict] = None):
        self.job_id = JobID.next()
        self.driver_task_id = TaskID.for_driver(self.job_id)
        from .gcs import make_control_store

        self.gcs = make_control_store()
        self.gcs_client = GcsClient(self.gcs)
        self.scheduler = ClusterScheduler(self.gcs)
        self.serializer = Serializer(ref_class=ObjectRef)
        self.memory_store = MemoryStore()
        self._lock = threading.RLock()
        # Signalled on every object READY/FAILED transition; wait() blocks
        # on this instead of polling (reference: WaitManager wakeups).
        self._obj_cond = threading.Condition(self._lock)
        self._objects: Dict[ObjectID, _ObjectEntry] = {}
        self._tasks: Dict[TaskID, _TaskRecord] = {}
        self._lineage: Dict[TaskID, TaskSpec] = {}
        self._lineage_bytes = 0
        self._actors: Dict[ActorID, _ActorRecord] = {}
        self._refcounts: Dict[ObjectID, int] = {}
        # worker_id -> TaskIDs assigned to it (1 running + pipelined
        # same-key tasks queued in its pipe, scheduler.PIPELINE_DEPTH)
        self._worker_tasks: Dict[bytes, set] = {}
        self._blocked_workers: Dict[bytes, NodeManager] = {}
        self._put_counter = 0
        self._env = dict(env or {})
        # Before any worker starts, so that each inherits it from its
        # first instruction (a spawned child imports the driver's main
        # module, and with it possibly JAX, before worker_entry runs).
        export_compile_cache_dir()
        self._stopped = threading.Event()
        self._submit_buf: List[_TaskRecord] = []
        self._submit_cv = threading.Condition()
        self._submit_flusher = threading.Thread(
            target=self._submit_flush_loop, daemon=True,
            name="rt-submit-flush")
        self._submit_flusher.start()
        # Before any worker starts: tracing on the driver + inherited by
        # every worker via env (config flag tracing_enabled).
        if config().tracing_enabled:
            from ..observability import tracing

            tracing.enable()
            self._env.setdefault("RT_TRACING_ENABLED", "1")
        # Core runtime metrics (reference: stats/metric_defs.cc wired
        # through the core worker): counters + tag KEYS cached once —
        # the submit path is hot, so no per-call dict build/sort.
        # None when the telemetry plane is disabled (overhead A/B).
        if config().telemetry_enabled:
            from ..observability.metrics import core_metrics

            self._metrics: Optional[Dict[str, Any]] = core_metrics()
            self._ctr_submitted = self._metrics["tasks_submitted"]
            self._ctr_finished = self._metrics["tasks_finished"]
            self._key_task = (("type", "task"),)
            self._key_actor = (("type", "actor"),)
            self._key_creation = (("type", "actor_creation"),)
            self._finished_keys: Dict[tuple, tuple] = {}
        else:
            self._metrics = None
            self._ctr_submitted = self._ctr_finished = None
        # Flight recorder (per-task stage stamps -> observability.flight).
        # The aggregator is module-global: clear it so a runtime that
        # replaces a dead one in this process (head failover, test
        # re-init) starts with a clean event store instead of inheriting
        # the previous head's possibly-torn records.
        from ..observability import flight as _flight

        self._flight_on = _flight.enabled()
        if self._flight_on:
            _flight.clear()
        # Head trace store: same replacement-head rule as the flight
        # recorder (start clean, never inherit a dead head's traces),
        # plus the tracer sink that routes HEAD-local spans (proxy,
        # router — this process has no TelemetryExporter) into the
        # per-request index that `rt trace` queries.
        if config().telemetry_enabled:
            from ..observability import tracestore as _tracestore

            _tracestore.clear()
            _tracestore.install_head_sink()
        # Session log dir: workers redirect stdout/stderr there; the log
        # monitor tails the files and republishes to the driver
        # (reference: log_monitor.py + session_latest/logs layout).
        from .log_monitor import ENV_LOG_DIR, make_session_log_dir

        if config().worker_redirect_logs:
            self.session_log_dir: Optional[str] = make_session_log_dir()
            self._env.setdefault(ENV_LOG_DIR, self.session_log_dir)
        else:
            self.session_log_dir = None
        self.gcs.add_job(JobInfo(self.job_id, entrypoint="driver"))
        from .placement_group import PlacementGroupManager

        self.placement_group_manager = PlacementGroupManager(self)

        import multiprocessing

        ncpu = num_cpus if num_cpus is not None else multiprocessing.cpu_count()
        node_resources = {"CPU": float(ncpu)}
        node_resources.update(resources or {})
        if "TPU" not in node_resources:
            node_resources["TPU"] = float(_local_chip_count())
        for i in range(num_nodes):
            self.add_node(node_resources, object_store_memory=object_store_memory)
        self.scheduler.start()
        self.gcs.start_health_check(
            config().heartbeat_period_ms / 1000.0,
            config().num_heartbeats_timeout,
        )
        # Heartbeat loop for in-process node managers (reference: each
        # raylet reports to GcsHeartbeatManager; here one thread beats for
        # every node still registered with the scheduler).
        self._hb_stop = threading.Event()

        def _heartbeats():
            period = config().heartbeat_period_ms / 1000.0
            while not self._hb_stop.wait(period):
                for node in self.scheduler.nodes():
                    if node.alive:
                        try:
                            self.gcs.heartbeat(node.node_id)
                        except Exception:
                            # Native backend does TCP I/O; one timeout must
                            # not kill the loop (a dead loop -> every node
                            # eventually marked dead by the health checker).
                            pass

        self._hb_thread = threading.Thread(target=_heartbeats, daemon=True,
                                           name="rt-heartbeats")
        self._hb_thread.start()
        # Node OOM guard (reference: MemoryMonitor + raylet worker-killing
        # policy — kill the newest retriable task instead of letting the
        # kernel OOM-killer take the node).
        from .memory_monitor import MemoryMonitor

        self.memory_monitor = MemoryMonitor(
            threshold=config().memory_usage_threshold,
            on_high=self._on_memory_pressure,
        )
        if config().memory_monitor_enabled:
            self.memory_monitor.start()
        self.log_monitor = None
        self._log_unsub = None
        if self.session_log_dir is not None:
            from .log_monitor import LogMonitor, attach_driver_printer

            self.log_monitor = LogMonitor(
                self.session_log_dir,
                publish=self.gcs.pubsub.publish,
            )
            self.log_monitor.start()
            if config().log_to_driver:
                self._log_unsub = attach_driver_printer(self.gcs.pubsub)
        install_refcount_hooks(
            add=self._ref_added, remove=self._ref_removed, borrow=self._ref_added
        )
        # Head failover: a replacement head started on the same WAL
        # persist path reloads every control-plane table and reconciles
        # (see _recover_control_plane). No-op without durable tables.
        self.recovery_report: Optional[Dict[str, Any]] = None
        self._recover_control_plane()

    # ------------------------------------------------------------------ nodes
    def add_node(self, resources: Dict[str, float],
                 object_store_memory: Optional[int] = None,
                 labels: Optional[dict] = None,
                 topology: Optional[dict] = None,
                 remote: Optional[bool] = None) -> NodeID:
        node_id = NodeID.from_random()
        if remote is None:
            remote = config().node_daemons
        if remote:
            from .remote_node import RemoteNode

            self._ensure_cluster_listener()
            node = RemoteNode(
                node_id, resources, self._handle_worker_message,
                self._handle_worker_death, self._on_daemon_node_death,
                self._cluster_addr, self._accept_daemon_conn,
                object_store_memory=object_store_memory,
                env=self._env, labels=labels,
                on_change=self.scheduler.notify,
                on_locate=self._handle_daemon_locate,
            )
        else:
            node = NodeManager(
                node_id, resources, self._handle_worker_message,
                self._handle_worker_death,
                object_store_memory=object_store_memory,
                env=self._env, labels=labels,
            )
        node.start()
        self.scheduler.add_node(node, topology=topology)
        if hasattr(self, "placement_group_manager"):
            self.placement_group_manager.retry_pending()
        return node_id

    # -- node-daemon attach plane (reference: raylet -> GCS registration) --
    def _ensure_cluster_listener(self, host: Optional[str] = None,
                                 port: Optional[int] = None) -> None:
        if getattr(self, "_cluster_listener", None) is not None:
            return
        import socket as socket_mod

        from .node_protocol import FrameConn

        srv = socket_mod.socket(socket_mod.AF_INET,
                                socket_mod.SOCK_STREAM)
        srv.setsockopt(socket_mod.SOL_SOCKET, socket_mod.SO_REUSEADDR, 1)
        # Fixed port (cluster_listener_port) lets daemons that outlive a
        # dead head re-dial the SAME address and rejoin its replacement.
        srv.bind((host or "127.0.0.1",
                  port or config().cluster_listener_port or 0))
        srv.listen(64)
        self._cluster_listener = srv
        self._cluster_addr = "%s:%d" % srv.getsockname()[:2]
        self._daemon_conns: Dict[bytes, object] = {}
        self._daemon_cv = threading.Condition()

        def accept_loop():
            while True:
                try:
                    sock, _ = srv.accept()
                except OSError:
                    return
                sock.setsockopt(socket_mod.IPPROTO_TCP,
                                socket_mod.TCP_NODELAY, 1)
                conn = FrameConn(sock)
                try:
                    msg = conn.recv()
                except (EOFError, OSError):
                    # daemon died mid-handshake: drop IT, not the loop
                    continue
                if msg[0] != "register_node":
                    conn.close()
                    continue
                info = msg[3] if len(msg) > 3 and isinstance(msg[3], dict) \
                    else {}
                if info.get("self_register"):
                    # Shell-started daemon (``rt start --address=...``):
                    # adopt it as a cluster node.
                    try:
                        self._adopt_daemon(NodeID(msg[1]), conn, info)
                    except Exception:
                        conn.close()
                    continue
                with self._daemon_cv:
                    self._daemon_conns[msg[1]] = (conn, info)
                    self._daemon_cv.notify_all()

        threading.Thread(target=accept_loop, daemon=True,
                         name="rt-cluster-accept").start()

    def _adopt_daemon(self, node_id: NodeID, conn, info: dict) -> None:
        """Adopt a self-registered daemon into the cluster (reference:
        GCS node registration from ``ray start --address=...`` raylets)."""
        from .remote_node import RemoteNode

        resources = dict(info.get("resources") or {"CPU": 1.0})
        node = RemoteNode.adopt(
            node_id, resources, self._handle_worker_message,
            self._handle_worker_death, self._on_daemon_node_death,
            conn, int(info.get("num_workers") or 2),
            labels=info.get("labels"), on_change=self.scheduler.notify,
            object_addr=info.get("object_addr"),
            on_locate=self._handle_daemon_locate,
        )
        node.start()
        self.scheduler.add_node(node, topology=info.get("topology"))
        if hasattr(self, "placement_group_manager"):
            self.placement_group_manager.retry_pending()

    def _accept_daemon_conn(self, node_id: NodeID, timeout: float = 30.0):
        deadline = time.monotonic() + timeout
        with self._daemon_cv:
            while node_id.binary() not in self._daemon_conns:
                remaining = deadline - time.monotonic()
                if remaining <= 0:
                    raise TimeoutError(
                        f"node daemon {node_id.hex()[:8]} did not register")
                self._daemon_cv.wait(remaining)
            return self._daemon_conns.pop(node_id.binary())

    def _fetch_frame_blocking(self, oid: ObjectID,
                              timeout: float = 120.0) -> bytes:
        """Serve an object's raw frame, riding out loss: a LOST object
        (holder daemon died mid-pull) triggers lineage reconstruction
        (``_recover_object``) and the wait resumes until the recomputed
        copy seals (reference: ObjectRecoveryManager + PullManager
        retry)."""
        deadline = time.monotonic() + timeout
        while True:
            with self._lock:
                entry = self._objects.get(oid)
                status = entry.status if entry is not None else None
                location = entry.location if entry is not None else None
                error = entry.error if entry is not None else None
            if entry is None:
                raise ObjectLostError(oid, "unknown object")
            if status == _ObjStatus.FAILED:
                raise error
            if status == _ObjStatus.READY and location is not None:
                try:
                    if location[0] == "memory":
                        frame = self.memory_store.get(oid)
                        if frame is None:
                            raise ObjectLostError(oid)
                        return frame
                    _, node_id, _size = location
                    node = self.scheduler.get_node(node_id)
                    if node is None:
                        raise ObjectLostError(oid, "holding node gone")
                    return self._store_read_bytes(node.store, oid)
                except (ObjectLostError, ConnectionError):
                    # ConnectionError: the holder's daemon died and its
                    # disconnect is not processed yet; the copy is lost.
                    with self._lock:
                        entry.status = _ObjStatus.LOST
                        entry.location = None
            with self._lock:
                lost = entry.status == _ObjStatus.LOST
            if lost:
                self._recover_object(oid)
            ev = threading.Event()
            self.add_ready_watcher(oid, ev.set)
            remaining = deadline - time.monotonic()
            if remaining <= 0 or not ev.wait(min(remaining, 10.0)):
                if time.monotonic() >= deadline:
                    raise GetTimeoutError(
                        f"fetch of {oid.hex()[:8]} timed out")

    def _handle_daemon_locate(self, node, req_id: int,
                              oid_bin: bytes) -> None:
        """Answer a daemon's P2P locate: ("inline", frame) for memory-
        store objects, else ("shm", holder_hex, size, object_addr) so the
        daemon pulls straight from the holder's ObjectServer (reference:
        OwnershipBasedObjectDirectory — the owner answers locations)."""
        try:
            oid = ObjectID(oid_bin)
            with self._lock:
                entry = self._objects.get(oid)
                location = entry.location if entry is not None else None
            if location is None:
                raise ObjectLostError(oid, "no known location")
            if location[0] == "memory":
                payload = ("inline", self.memory_store.get(oid))
            else:
                _, holder_id, size = location
                holder = self.scheduler.get_node(holder_id)
                if holder is None:
                    raise ObjectLostError(oid, "holding node is gone")
                addr = getattr(holder, "object_addr", None)
                if addr is None:
                    # Holder is the head-local NodeManager (no object
                    # server): ship the frame inline.
                    payload = ("inline",
                               self._store_read_bytes(holder.store, oid))
                else:
                    payload = ("shm", holder_id.hex(), size, addr)
            node.conn.send(("locate_reply", req_id, True, payload))
        except Exception as e:  # noqa: BLE001
            try:
                node.conn.send(("locate_reply", req_id, False, repr(e)))
            except Exception:
                pass

    def _on_daemon_node_death(self, node_id: NodeID) -> None:
        """Connection to a daemon dropped => the host is gone (chaos or
        crash): run the standard node-failure path."""
        try:
            self.gcs.mark_node_dead(node_id)
        except Exception:
            pass
        self.remove_node(node_id)

    def remove_node(self, node_id: NodeID) -> None:
        """Simulated node failure: kills its workers and destroys its store.

        Objects whose only copy lived there become LOST; subsequent access
        triggers lineage reconstruction (reference: ObjectRecoveryManager).
        """
        node = self.scheduler.remove_node(node_id)
        if node is None:
            return
        with self._lock:
            for oid, entry in self._objects.items():
                if (
                    entry.status == _ObjStatus.READY
                    and entry.location
                    and entry.location[0] == "shm"
                    and entry.location[1] == node_id
                ):
                    entry.status = _ObjStatus.LOST
                    entry.location = None
        # Kill first, then fail-or-retry: kill() marks the handle DEAD
        # (suppressing the pool's on_worker_death callback) and stops the
        # process, so a worker can't race a late "done" against the retry
        # we schedule below. Without the explicit death pass, in-flight
        # tasks would stay RUNNING forever (reference: NodeManager
        # node-death cleanup fails leases; GCS actor manager restarts).
        for worker in node.pool.all_workers():
            worker.kill()
            self._handle_worker_death(worker)
        node.shutdown()
        self.scheduler.notify()

    # ------------------------------------------------------- refcounting
    def _ref_added(self, oid: ObjectID) -> None:
        with self._lock:
            self._refcounts[oid] = self._refcounts.get(oid, 0) + 1

    def _ref_removed(self, oid: ObjectID) -> None:
        free = False
        with self._lock:
            n = self._refcounts.get(oid, 0) - 1
            if n <= 0:
                self._refcounts.pop(oid, None)
                entry = self._objects.get(oid)
                if entry is not None and not entry.waiting_tasks and not entry.futures:
                    free = entry.status in (_ObjStatus.READY, _ObjStatus.FAILED)
            else:
                self._refcounts[oid] = n
        if free:
            self._free_object(oid)

    def _free_object(self, oid: ObjectID) -> None:
        with self._lock:
            entry = self._objects.pop(oid, None)
        if entry is None:
            return
        self.memory_store.delete(oid)
        if entry.location and entry.location[0] == "shm":
            node = self.scheduler.get_node(entry.location[1])
            if node is not None:
                node.store.delete(oid)

    # ------------------------------------------------------------------- put
    def put(self, value: Any) -> ObjectRef:
        with self._lock:
            self._put_counter += 1
            oid = ObjectID.for_put(self.driver_task_id, self._put_counter)
        serialized = self.serializer.serialize(value)
        size = serialized.frame_bytes()
        if size <= config().max_direct_call_object_size:
            self._store_frame(oid, serialized.to_bytes())
        else:
            # Zero-copy: out-of-band buffers memcpy straight into the
            # shm arena extent, no intermediate flat bytes object.
            node = self.scheduler.nodes()[0]
            if hasattr(node.store, "put_serialized"):
                node.store.put_serialized(oid, serialized)
            else:  # daemon-backed store: chunked network push
                node.store.put_bytes(oid, serialized.to_bytes())
            self._mark_ready(oid, ("shm", node.node_id, size))
        return ObjectRef(oid)

    def _store_frame(self, oid: ObjectID, frame: bytes,
                     node: Optional[NodeManager] = None) -> None:
        if len(frame) <= config().max_direct_call_object_size:
            self.memory_store.put(oid, frame)
            location = ("memory",)
        else:
            node = node or self.scheduler.nodes()[0]
            node.store.put_bytes(oid, frame)
            location = ("shm", node.node_id, len(frame))
        self._mark_ready(oid, location)

    def _mark_ready(self, oid: ObjectID, location: tuple) -> None:
        with self._lock:
            entry = self._objects.setdefault(oid, _ObjectEntry())
            entry.status = _ObjStatus.READY
            entry.location = location
            entry.error = None
            futures = entry.futures
            entry.futures = []
            waiting = entry.waiting_tasks
            entry.waiting_tasks = []
            watchers = entry.watchers
            entry.watchers = []
            self._obj_cond.notify_all()
        for fut in futures:
            try:
                fut.set_result(self._materialize_value(oid))
            except Exception as e:  # noqa: BLE001
                fut.set_exception(e)
        for task_id in waiting:
            self._dep_ready(task_id)
        for cb in watchers:
            cb()

    def _mark_failed(self, oid: ObjectID, error: Exception) -> None:
        with self._lock:
            icept_entry = self._objects.setdefault(oid, _ObjectEntry())
            icept = icept_entry.failure_interceptor
            icept_entry.failure_interceptor = None
        if icept is not None:
            # Consulted OUTSIDE the finalization: an accepting
            # interceptor (serve router re-dispatching to a healthy
            # replica) suppresses the failure entirely — the oid stays
            # PENDING and is completed later via transfer_result /
            # fail_object. The hook must not block (it spawns its retry
            # work on another thread): some _mark_failed callers hold
            # the runtime RLock.
            try:
                if icept(error):
                    return
            except Exception:  # noqa: BLE001 — a broken hook must not
                pass  # suppress the underlying failure
        with self._lock:
            entry = self._objects.setdefault(oid, _ObjectEntry())
            entry.status = _ObjStatus.FAILED
            entry.error = error
            futures = entry.futures
            entry.futures = []
            waiting = entry.waiting_tasks
            entry.waiting_tasks = []
            watchers = entry.watchers
            entry.watchers = []
            self._obj_cond.notify_all()
        for fut in futures:
            fut.set_exception(error)
        for task_id in waiting:
            self._dep_ready(task_id)
        for cb in watchers:
            cb()

    # ------------------------------------------------------------------- get
    def get(self, refs, timeout: Optional[float] = None):
        single = isinstance(refs, ObjectRef)
        ref_list = [refs] if single else list(refs)
        futures = [self.object_future(r) for r in ref_list]
        deadline = None if timeout is None else time.monotonic() + timeout
        values = []
        for fut in futures:
            remaining = None if deadline is None else max(0.0, deadline - time.monotonic())
            try:
                values.append(fut.result(timeout=remaining))
            except (TimeoutError, _FutTimeout):
                # futures.TimeoutError is a distinct class before py3.11.
                raise GetTimeoutError(
                    f"get() timed out after {timeout}s waiting for objects"
                ) from None
        return values[0] if single else values

    def add_ready_watcher(self, oid: ObjectID, callback) -> None:
        """Run ``callback()`` when the object reaches READY/FAILED (fires
        immediately if it already has). Status-only: never materializes."""
        with self._lock:
            entry = self._objects.setdefault(oid, _ObjectEntry())
            if entry.status not in (_ObjStatus.READY, _ObjStatus.FAILED):
                entry.watchers.append(callback)
                return
        callback()

    # ------------------------------------------------- serve-plane safe retry
    # The serve router retries actor-death failures by re-dispatching the
    # request to a healthy replica while the CALLER keeps waiting on the
    # original ObjectRef. These four hooks make that possible without any
    # cost on the success path: a one-shot failure interceptor parks the
    # failure, and the retry loop later completes the original oid from a
    # fresh attempt's result (transfer_result) or finalizes the error
    # (fail_object).

    def intercept_failure(self, oid: ObjectID, fn) -> None:
        """Register a one-shot hook consulted before ``oid`` is failed.

        ``fn(error) -> bool``: returning True takes ownership — the
        failure is suppressed, futures/watchers stay parked, and the
        caller must later finish the oid via :meth:`transfer_result` or
        :meth:`fail_object`. Must not block (may run under the runtime
        lock).

        If the oid has ALREADY failed (actor-death fast path: submitting
        to a DEAD actor fails return oids before the caller can register
        a hook), ``fn`` is consulted immediately; on acceptance the
        entry is revived to PENDING — safe here because the router
        registers before handing the ref to any waiter.
        """
        with self._lock:
            entry = self._objects.setdefault(oid, _ObjectEntry())
            if entry.status != _ObjStatus.FAILED:
                entry.failure_interceptor = fn
                return
            error = entry.error
        try:
            accepted = bool(fn(error))
        except Exception:  # noqa: BLE001
            accepted = False
        if accepted:
            with self._lock:
                entry = self._objects.setdefault(oid, _ObjectEntry())
                if entry.status == _ObjStatus.FAILED:
                    entry.status = _ObjStatus.PENDING
                    entry.error = None

    def fail_object(self, oid: ObjectID, error: Exception) -> None:
        """Finalize ``oid`` as failed (retry budget / deadline exhausted).

        Public wrapper over the normal failure path, so any interceptor
        registered since is honored too."""
        self._mark_failed(oid, error)

    def object_status(self, oid: ObjectID):
        """``(status_name, error)`` snapshot for an object id."""
        with self._lock:
            entry = self._objects.get(oid)
            if entry is None:
                return ("unknown", None)
            return (entry.status.lower(), entry.error)

    def transfer_result(self, src_oid: ObjectID, dst_oid: ObjectID) -> None:
        """Complete ``dst_oid`` with the outcome of READY/FAILED ``src_oid``.

        Used by the retry loop: the fresh attempt's return object becomes
        the original request's result. Copies the serialized frame (no
        deserialize round-trip) so large payloads stay one memcpy."""
        with self._lock:
            entry = self._objects.get(src_oid)
            status = entry.status if entry is not None else None
            error = entry.error if entry is not None else None
            location = entry.location if entry is not None else None
        if status == _ObjStatus.FAILED:
            self._mark_failed(dst_oid, error)
            return
        if status != _ObjStatus.READY:
            self._mark_failed(dst_oid, ObjectLostError(
                src_oid, f"transfer_result: source object "
                         f"{src_oid.hex()[:8]} not ready ({status})"))
            return
        try:
            if location[0] == "memory":
                frame = self.memory_store.get(src_oid)
                if frame is None:
                    raise ObjectLostError(src_oid)
            else:
                _, node_id, _size = location
                node = self.scheduler.get_node(node_id)
                if node is None:
                    raise ObjectLostError(
                        src_oid, f"node {node_id.hex()[:8]} holding "
                                 f"retried result is gone")
                frame = self._store_read_bytes(node.store, src_oid)
        except Exception as e:  # noqa: BLE001
            self._mark_failed(dst_oid, e)
            return
        self._store_frame(dst_oid, frame)

    def object_future(self, ref: ObjectRef) -> Future:
        if self._submit_buf:
            self._flush_submissions()
        fut: Future = Future()
        recover = False
        ready = False
        with self._lock:
            entry = self._objects.get(ref.id)
            if entry is None:
                entry = self._objects.setdefault(ref.id, _ObjectEntry())
            if entry.status == _ObjStatus.READY:
                ready = True
            elif entry.status == _ObjStatus.FAILED:
                fut.set_exception(entry.error)
            elif entry.status == _ObjStatus.LOST:
                entry.futures.append(fut)
                recover = True
            else:
                entry.futures.append(fut)
        if ready:
            # Materialize OUTSIDE the runtime lock: for daemon-backed
            # nodes this is a chunked network pull that must not stall
            # every other runtime operation.
            try:
                fut.set_result(self._materialize_value(ref.id))
            except ObjectLostError:
                with self._lock:
                    entry.status = _ObjStatus.LOST
                    entry.location = None
                    fut = Future()
                    entry.futures.append(fut)
                recover = True
        if recover:
            self._recover_object(ref.id)
        return fut

    @staticmethod
    def _store_read_bytes(store, oid: ObjectID) -> bytes:
        """Private copy of a stored object's bytes. Pins local arenas for
        the duration of the copy (get_buffer drops the pin before
        returning, so a concurrent spill/delete could reuse the extent
        mid-read); daemon-proxy stores already return a private copy."""
        get_pinned = getattr(store, "get_pinned", None)
        if get_pinned is None:
            frame = bytes(store.get_buffer(oid))
            _hotpath.count("copy.store.read_bytes", len(frame))
            return frame
        buf = get_pinned(oid)
        try:
            _hotpath.count("copy.store.read_bytes", buf.nbytes)
            return bytes(buf)
        finally:
            buf.release()
            del buf

    def _materialize_value(self, oid: ObjectID):
        entry = self._objects[oid]
        if entry.location[0] == "memory":
            frame = self.memory_store.get(oid)
            if frame is None:
                raise ObjectLostError(oid)
            return self.serializer.deserialize(frame)
        _, node_id, size = entry.location
        node = self.scheduler.get_node(node_id)
        if node is None:
            raise ObjectLostError(oid, f"node {node_id.hex()[:8]} holding object is gone")
        if hasattr(node.store, "get_pinned"):
            # Zero-copy: numpy values deserialize as read-only views into
            # the arena; the pin (released on GC) + deferred-free let them
            # safely outlive store eviction.
            return self.serializer.deserialize(node.store.get_pinned(oid))
        # Daemon-backed store: the network pull is already a private copy.
        return self.serializer.deserialize(node.store.get_buffer(oid))

    def _object_entry_payload(self, oid: ObjectID):
        """Entry for shipping to a worker: inline frame or shm pointer."""
        entry = self._objects.get(oid)
        if entry is None or entry.status != _ObjStatus.READY:
            if entry is not None and entry.status == _ObjStatus.FAILED:
                return ("error", entry.error)
            return None
        if entry.location[0] == "memory":
            return ("inline", self.memory_store.get(oid))
        _, node_id, size = entry.location
        return ("shm", (oid.binary(), size, node_id.hex()))

    # ------------------------------------------------------------------ wait
    def wait(self, refs: List[ObjectRef], num_returns: int = 1,
             timeout: Optional[float] = None, fetch_local: bool = True):
        if num_returns > len(refs):
            raise ValueError("num_returns exceeds number of refs")
        if self._submit_buf:
            self._flush_submissions()
        deadline = None if timeout is None else time.monotonic() + timeout
        done: set = set()

        def check() -> bool:
            for r in refs:
                e = self._objects.get(r.id)
                if e is not None and e.status in (_ObjStatus.READY,
                                                  _ObjStatus.FAILED):
                    done.add(r.id)
            return len(done) >= num_returns

        # Condvar wakeup on READY/FAILED transitions; the 1s cap is a
        # belt-and-braces re-check, not the latency path.
        with self._obj_cond:
            while not check():
                remaining = (None if deadline is None
                             else deadline - time.monotonic())
                if remaining is not None and remaining <= 0:
                    break
                self._obj_cond.wait(
                    1.0 if remaining is None else min(remaining, 1.0))
        ready = [r for r in refs if r.id in done][:num_returns]
        ready_ids = {r.id for r in ready}
        not_ready = [r for r in refs if r.id not in ready_ids]
        return ready, not_ready

    # ------------------------------------------------------ task submission
    def submit_spec(self, spec: TaskSpec) -> List[ObjectRef]:
        if spec.task_type == TaskType.ACTOR_CREATION_TASK:
            self._flush_submissions()
            return self._create_actor(spec)
        if spec.task_type == TaskType.ACTOR_TASK:
            # Actor pushes resolve args immediately: any buffered producer
            # must reach the scheduler first. (Only when something is
            # actually buffered — the unconditional flush cost a cv
            # round-trip on every call of the sync actor hot path.)
            if self._submit_buf:
                self._flush_submissions()
            return self._submit_actor_task(spec)
        return self._submit_normal_task(spec)

    def _task_finished(self, record: _TaskRecord, state: str) -> None:
        """Count a task reaching DONE/FAILED, node-tagged when placed.
        Tag keys are interned per (state, node) — this runs on the reply
        path of every sync call."""
        if self._ctr_finished is None:
            return
        node = record.node
        node_hex = None
        if node is not None:
            node_hex = getattr(node, "_telemetry_hex", None)
            if node_hex is None:
                node_hex = node.node_id.hex()[:8]
                node._telemetry_hex = node_hex
        key = self._finished_keys.get((state, node_hex))
        if key is None:
            pairs = [("state", state)]
            if node_hex is not None:
                pairs.append(("node", node_hex))
            key = tuple(sorted(pairs))
            self._finished_keys[(state, node_hex)] = key
        self._ctr_finished.inc_key(key)
        ts = record.state_ts
        if ts is not None:
            ts["finished" if state == "DONE" else "failed"] = \
                time.monotonic()
            from ..observability import flight

            spec = record.spec
            flight.task_finished(
                spec.task_id.hex(),
                spec.name or spec.method_name or "fn", ts, state)

    def _submit_normal_task(self, spec: TaskSpec) -> List[ObjectRef]:
        if self._ctr_submitted is not None:
            self._ctr_submitted.inc_key(self._key_task)
        record = _TaskRecord(spec, retries_left=spec.max_retries)
        if self._flight_on:
            record.state_ts = {"submitted": time.monotonic()}
        return_refs = [ObjectRef(oid) for oid in spec.return_ids()]
        with self._lock:
            self._tasks[spec.task_id] = record
            self._retain_lineage(spec)
            for oid in spec.return_ids():
                entry = self._objects.setdefault(oid, _ObjectEntry())
                entry.creating_task = spec.task_id
        self._increment_arg_pins(spec)
        # Buffered submission (reference: the submitter batches lease
        # requests per scheduling key): records enqueue into a small
        # driver-side buffer and enter the scheduler in BULK — one lock
        # round + one wake per batch instead of per task. Refs are valid
        # immediately (entries exist above); get/wait flush the buffer.
        with self._submit_cv:
            self._submit_buf.append(record)
            n = len(self._submit_buf)
            self._submit_cv.notify()
        if n >= 16:
            self._flush_submissions()
        return return_refs

    def _flush_submissions(self) -> None:
        """Move buffered records into the scheduler in one bulk step."""
        with self._submit_cv:
            records, self._submit_buf = self._submit_buf, []
        if not records:
            return
        leases = []
        qnow = time.monotonic() if self._flight_on else 0.0
        with self._lock:
            for record in records:
                if record.state_ts is not None:
                    record.state_ts["queued"] = qnow
                spec = record.spec
                lease = PendingLease(
                    spec,
                    on_granted=(lambda r: lambda node, worker:
                                self._dispatch(r, node, worker))(record),
                    on_unschedulable=(lambda r: lambda msg: self._fail_task(
                        r, TaskError(RuntimeError(msg),
                                     task_desc=r.spec.describe())))(record),
                )
                record.lease = lease
                pending_deps = 0
                for oid in spec.arg_refs:
                    entry = self._objects.setdefault(oid, _ObjectEntry())
                    if entry.status == _ObjStatus.PENDING:
                        entry.waiting_tasks.append(spec.task_id)
                        pending_deps += 1
                    elif entry.status == _ObjStatus.LOST:
                        entry.waiting_tasks.append(spec.task_id)
                        pending_deps += 1
                        self._recover_object(oid)
                record.deps_remaining = pending_deps
                lease.deps_ready = pending_deps == 0
                leases.append(lease)
        self.scheduler.submit_bulk(leases)

    def _submit_flush_loop(self) -> None:
        """Flushes the submission buffer shortly after it goes non-empty
        (bounded latency for drivers that submit and then go quiet)."""
        while not self._stopped.is_set():
            with self._submit_cv:
                while not self._submit_buf and not self._stopped.is_set():
                    self._submit_cv.wait()
                if self._stopped.is_set():
                    return
            time.sleep(0.001)  # let a burst accumulate
            self._flush_submissions()

    def _retain_lineage(self, spec: TaskSpec) -> None:
        size = len(spec.args_frame) + len(spec.function_blob or b"")
        if self._lineage_bytes + size > config().max_lineage_bytes:
            return  # over cap: objects from this task won't be reconstructible
        self._lineage[spec.task_id] = spec
        self._lineage_bytes += size

    def _schedule_task(self, record: _TaskRecord) -> None:
        spec = record.spec
        if self._flight_on:
            # Fresh stamps per attempt: a retry's queue/exec intervals
            # must not be measured against the failed attempt's clock.
            record.state_ts = {"submitted": time.monotonic(),
                               "queued": time.monotonic()}
        lease = PendingLease(
            spec,
            on_granted=lambda node, worker: self._dispatch(record, node, worker),
            on_unschedulable=lambda msg: self._fail_task(
                record, TaskError(RuntimeError(msg), task_desc=spec.describe())
            ),
        )
        record.lease = lease
        pending_deps = 0
        with self._lock:
            for oid in spec.arg_refs:
                entry = self._objects.setdefault(oid, _ObjectEntry())
                if entry.status == _ObjStatus.PENDING:
                    entry.waiting_tasks.append(spec.task_id)
                    pending_deps += 1
                elif entry.status == _ObjStatus.LOST:
                    entry.waiting_tasks.append(spec.task_id)
                    pending_deps += 1
                    self._recover_object(oid)
            record.deps_remaining = pending_deps
            lease.deps_ready = pending_deps == 0
        self.scheduler.submit(lease)

    def _dep_ready(self, task_id: TaskID) -> None:
        with self._lock:
            record = self._tasks.get(task_id)
            if record is None or record.lease is None:
                return
            record.deps_remaining -= 1
            if record.deps_remaining <= 0:
                record.lease.deps_ready = True
        self.scheduler.notify()

    def _dispatch(self, record: _TaskRecord, node: NodeManager,
                  worker: WorkerHandle) -> None:
        spec = record.spec
        if record.state_ts is not None:
            record.state_ts["scheduled"] = time.monotonic()
        resolved: Dict[int, Any] = {}
        failed_error = None
        lost_arg = None
        with self._lock:
            for i, oid in enumerate(spec.arg_refs):
                payload = self._object_entry_payload(oid)
                if payload is None:
                    # Arg vanished between deps-ready and dispatch (evicted
                    # or holder died). Mark it LOST so the retry's
                    # _schedule_task waits on it AND kicks lineage
                    # reconstruction, instead of failing the task outright.
                    entry = self._objects.setdefault(oid, _ObjectEntry())
                    if entry.status != _ObjStatus.FAILED:
                        entry.status = _ObjStatus.LOST
                        entry.location = None
                        lost_arg = oid
                    failed_error = ObjectLostError(oid, "arg unavailable at dispatch")
                    break
                if payload[0] == "error":
                    failed_error = payload[1]
                    break
                resolved[i] = payload
            record.node = node
            record.worker = worker
            record.state = "RUNNING"
            self._worker_tasks.setdefault(
                worker.worker_id.binary(), set()).add(spec.task_id)
        if failed_error is not None:
            self._fail_task(record, failed_error, retryable=lost_arg is not None)
            return
        ok = worker.send(("exec", spec.task_id.hex(), {
            "task_type": spec.task_type.value,
            "function_blob": spec.function_blob,
            "method_name": spec.method_name,
            "actor_id": spec.actor_id.hex() if spec.actor_id else None,
            "args_frame": spec.args_frame,
            "resolved_args": resolved,
            "num_returns": spec.num_returns,
            "max_concurrency": spec.max_concurrency,
            "concurrency_groups": spec.concurrency_groups,
            "name": spec.describe(),
            "runtime_env": spec.runtime_env,
            "trace_ctx": spec.trace_ctx,
        }))
        if record.state_ts is not None:
            record.state_ts["dispatched"] = time.monotonic()
        if not ok:
            self._handle_worker_death(worker)

    # ------------------------------------------------ completions & failures
    def _complete_task(self, record: _TaskRecord, results: List[tuple]) -> None:
        spec = record.spec
        self._task_finished(record, "DONE")
        with self._lock:
            record.state = "DONE"
            if record.worker is not None:
                assigned = self._worker_tasks.get(
                    record.worker.worker_id.binary())
                if assigned is not None:
                    assigned.discard(spec.task_id)
        for i, (kind, payload) in enumerate(results):
            oid = ObjectID.for_return(spec.task_id, i)
            if kind == "inline":
                self.memory_store.put(oid, payload)
                self._mark_ready(oid, ("memory",))
            else:  # shm, sealed by the worker on its node
                size = payload
                record.node.store.register_external(oid, size)
                self._mark_ready(oid, ("shm", record.node.node_id, size))
        self._release_after_task(record)
        self._decrement_arg_pins(spec)
        self.placement_group_manager.retry_pending()

    def _release_after_task(self, record: _TaskRecord) -> None:
        node, worker, spec = record.node, record.worker, record.spec
        if node is None or worker is None:
            return
        if spec.task_type == TaskType.ACTOR_TASK:
            return
        if spec.strategy.kind != "DEFAULT" or \
                spec.task_type != TaskType.NORMAL_TASK:
            # Non-pipelined strategies keep per-task lease semantics.
            node.pool.return_worker(worker)
            if not record.resources_released:
                self.scheduler.release(node, spec)
            return
        with self._lock:
            assigned = self._worker_tasks.get(worker.worker_id.binary())
            remaining = len(assigned) if assigned else 0
        if record.resources_released:
            # Blocked-worker path already gave the lease's resources back;
            # tell the scheduler so the final release is skipped.
            self.scheduler.release_lease_resources(node, worker, spec)
        # Worker-reuse fast path (OnWorkerIdle): top the still-leased
        # worker back up with same-key tasks straight from the completion
        # handler; returns the worker when idle and nothing is claimable.
        leases = self.scheduler.finish_on_worker(node, worker, spec,
                                                 remaining)
        for lease in leases:
            try:
                lease.on_granted(node, worker)
            except Exception as e:  # pragma: no cover — defensive
                lease.on_unschedulable(str(e))

    def _decrement_arg_pins(self, spec: TaskSpec) -> None:
        for oid in list(spec.arg_refs) + list(spec.borrowed_refs):
            self._ref_removed(oid)

    def _increment_arg_pins(self, spec: TaskSpec) -> None:
        for oid in list(spec.arg_refs) + list(spec.borrowed_refs):
            self._ref_added(oid)

    def _fail_task(self, record: _TaskRecord, error: Exception,
                   retryable: bool = True) -> None:
        spec = record.spec
        retry = retryable and record.retries_left > 0 and (
            isinstance(error, (WorkerCrashedError, ObjectLostError))
            or spec.retry_exceptions
        )
        with self._lock:
            if record.worker is not None:
                assigned = self._worker_tasks.get(
                    record.worker.worker_id.binary())
                if assigned is not None:
                    assigned.discard(spec.task_id)
        if record.node is not None:
            self._release_after_task(record)
        if retry:
            record.retries_left -= 1
            record.node = record.worker = None
            record.state = "PENDING"
            self._schedule_task(record)
            return
        record.state = "FAILED"
        self._task_finished(record, "FAILED")
        for oid in spec.return_ids():
            self._mark_failed(oid, error)
        self._decrement_arg_pins(spec)

    # ------------------------------------------------------------- recovery
    def _recover_object(self, oid: ObjectID) -> None:
        """Lineage reconstruction: resubmit the creating task.

        Reference: ObjectRecoveryManager — try another copy (none on a single
        host), then restore from spill (store handles transparently), then
        resubmit the producer from retained lineage, recursively recovering
        its lost args.
        """
        with self._lock:
            entry = self._objects.get(oid)
            if entry is None:
                return
            task_id = entry.creating_task or oid.task_id()
            spec = self._lineage.get(task_id)
            existing = self._tasks.get(task_id)
            if existing is not None and existing.state in ("PENDING", "RUNNING"):
                return  # already being recomputed
            if spec is None:
                self._mark_failed_locked = True
        if spec is None:
            self._mark_failed(
                oid, ObjectLostError(oid, "no lineage retained to reconstruct")
            )
            return
        record = _TaskRecord(spec, retries_left=spec.max_retries)
        with self._lock:
            self._tasks[task_id] = record
            self._increment_arg_pins(spec)
            for rid in spec.return_ids():
                e = self._objects.setdefault(rid, _ObjectEntry())
                e.status = _ObjStatus.PENDING
                e.creating_task = task_id
        self._schedule_task(record)

    # ----------------------------------------------- head failover recovery
    def _recover_control_plane(self) -> None:
        """Reload the persisted actor/job/PG tables after a head restart
        and reconcile them against this head's actually-alive cluster.

        Reference: the GCS fault-tolerance path — GcsActorManager::
        Initialize loads the actor table from storage and
        ReconstructActor re-runs creation for actors whose workers are
        gone. Here a replacement head started on the same
        ``control_store_persist_path``:

          1. replays the WAL (daemon-side) and scans the FSM tables,
          2. closes jobs the dead head left RUNNING,
          3. re-creates + re-schedules placement groups (same ids, new
             node assignments),
          4. for every non-DEAD actor whose worker no longer exists,
             re-runs ``max_restarts`` logic: restartable actors go
             RESTARTING and their creation is resubmitted (queued calls
             buffer and complete after the restart); exhausted ones go
             DEAD with a typed death cause. Named actors re-resolve via
             the rebuilt name table + the WAL-durable handle KV.
        """
        restore = getattr(self.gcs, "restore_tables", None)
        if restore is None or not getattr(
                self.gcs, "supports_persistent_tables", False):
            return
        t0 = time.perf_counter()
        try:
            tables = restore()
        except Exception:
            import logging

            logging.getLogger(__name__).warning(
                "control-plane table restore failed; starting empty",
                exc_info=True)
            return
        report = {"actors_restarted": 0, "actors_dead": 0,
                  "actors_seen": 0, "jobs_closed": 0, "pgs_restored": 0}
        for job in tables["jobs"]:
            if job.job_id == self.job_id:
                continue
            if job.status == "RUNNING":
                # The owning driver died with the old head.
                self.gcs.finish_job(job.job_id, "FAILED")
                report["jobs_closed"] += 1
        for desc in tables["pgs"]:
            try:
                if self.placement_group_manager.restore(desc) is not None:
                    report["pgs_restored"] += 1
            except Exception:
                import logging

                logging.getLogger(__name__).warning(
                    "failed to restore placement group", exc_info=True)
        for info in tables["actors"]:
            if info.state == ActorState.DEAD:
                # Tombstone: register a DEAD runtime record (no state
                # change to persist) so durable handles keep failing
                # TYPED with the stored death_cause on EVERY later
                # failover, not just the one that killed the actor.
                with self._lock:
                    self._actors.setdefault(
                        info.actor_id,
                        _ActorRecord(info.actor_id, None,  # type: ignore[arg-type]
                                     state=ActorState.DEAD,
                                     restarts_left=0))
                continue
            report["actors_seen"] += 1
            outcome = self._reconcile_recovered_actor(info)
            report["actors_" + outcome] += 1
        report["recovery_ms"] = round(
            (time.perf_counter() - t0) * 1000.0, 2)
        self.recovery_report = report
        if not any((report["actors_seen"], report["jobs_closed"],
                    report["pgs_restored"])):
            return  # fresh WAL: nothing recovered, keep quiet
        try:
            from ..observability.events import emit

            emit("HEAD_RECOVERY",
                 f"recovered control plane in {report['recovery_ms']}ms: "
                 f"{report['actors_restarted']} actors restarted, "
                 f"{report['actors_dead']} dead (restarts exhausted or "
                 f"unrecoverable), {report['jobs_closed']} jobs closed, "
                 f"{report['pgs_restored']} placement groups rescheduled")
        except Exception:
            pass
        if self._metrics is not None:
            try:
                from ..observability.metrics import Gauge, get_or_create

                get_or_create(
                    Gauge, "rt_head_recovery_ms",
                    "Control-plane reload+reconcile time of the last "
                    "head failover").set(report["recovery_ms"])
                get_or_create(
                    Gauge, "rt_head_recovered_actors",
                    "Actors restarted by the last head failover").set(
                    float(report["actors_restarted"]))
            except Exception:
                pass

    def _reconcile_recovered_actor(self, info: ActorInfo) -> str:
        """One persisted actor record → 'restarted' or 'dead'.

        The dead head's workers are gone (a surviving daemon reaps them
        before it rejoins), so every recovered actor lost its worker
        while the head was down — exactly the window ``max_restarts``
        must cover.
        """
        actor_id = info.actor_id
        if info.creation_spec_blob is None:
            return self._mark_recovered_dead(
                info, None,
                "head failover: no creation spec persisted")
        try:
            spec = serialization.loads(info.creation_spec_blob)
        except Exception:
            return self._mark_recovered_dead(
                info, None,
                "head failover: persisted creation spec unreadable")
        if spec.arg_refs or spec.borrowed_refs:
            # Creation args lived in the dead head's object plane and
            # have no lineage here; re-running would hang on deps.
            return self._mark_recovered_dead(
                info, spec,
                "head failover: creation arguments lost with the old "
                "head")
        if spec.strategy.kind == "NODE_AFFINITY" and not spec.strategy.soft:
            # Hard affinity names a node of the dead head; this head's
            # nodes have fresh ids, so the creation could never place —
            # fail typed instead of pending forever.
            return self._mark_recovered_dead(
                info, spec,
                "head failover: hard node affinity to a node of the "
                "dead head")
        if (spec.strategy.kind == "PLACEMENT_GROUP"
                and self.placement_group_manager.get(
                    spec.strategy.placement_group_id) is None):
            # The PG record didn't survive (dropped write / unreadable):
            # the creation would wait on a dangling bundle forever.
            return self._mark_recovered_dead(
                info, spec,
                "head failover: placement group not recovered")
        restarts_left = (-1 if spec.max_restarts < 0
                         else max(0, spec.max_restarts - info.num_restarts))
        if restarts_left == 0:
            return self._mark_recovered_dead(
                info, spec,
                "worker died during head failover "
                f"(max_restarts={spec.max_restarts} exhausted)")
        if restarts_left > 0:
            restarts_left -= 1  # this failover consumes one restart
        record = _ActorRecord(actor_id, spec, state=ActorState.RESTARTING,
                              restarts_left=restarts_left)
        with self._lock:
            self._actors[actor_id] = record
        # update_actor(RESTARTING) bumps num_restarts and persists, so
        # repeated failovers exhaust max_restarts exactly like repeated
        # worker deaths under one head.
        self.gcs.update_actor(actor_id, ActorState.RESTARTING)
        self._schedule_actor_creation(record)
        return "restarted"

    def _mark_recovered_dead(self, info: ActorInfo,
                             spec: Optional[TaskSpec],
                             cause: str) -> str:
        """Terminal reconcile outcome: record the death AND register a
        DEAD _ActorRecord, so a surviving handle's submit takes the
        normal dead-actor path (refs failed with a typed ActorDiedError
        carrying the cause) instead of raising 'unknown actor'."""
        record = _ActorRecord(info.actor_id, spec,  # type: ignore[arg-type]
                              state=ActorState.DEAD, restarts_left=0)
        with self._lock:
            self._actors[info.actor_id] = record
        self.gcs.update_actor(info.actor_id, ActorState.DEAD,
                              death_cause=cause)
        return "dead"

    # --------------------------------------------------------------- actors
    def _create_actor(self, spec: TaskSpec) -> List[ObjectRef]:
        if self._ctr_submitted is not None:
            self._ctr_submitted.inc_key(self._key_creation)
        actor_id = spec.actor_id
        record = _ActorRecord(
            actor_id, spec, restarts_left=spec.max_restarts,
        )
        with self._lock:
            self._actors[actor_id] = record
        # With a durable control store, the creation spec travels with
        # the actor record so a replacement head can re-run the creation
        # (reference: gcs_actor_manager ReconstructActor needs the
        # registered task spec). Skipped otherwise — serializing the
        # spec again per creation is pure overhead without a WAL.
        spec_blob = (serialization.dumps(spec)
                     if getattr(self.gcs, "supports_persistent_tables",
                                False) else None)
        self.gcs.register_actor(ActorInfo(
            actor_id, spec.name or None, max_restarts=spec.max_restarts,
            creation_spec_blob=spec_blob,
        ))
        self._increment_arg_pins(spec)
        self._schedule_actor_creation(record)
        return [ObjectRef(oid) for oid in spec.return_ids()]

    def _schedule_actor_creation(self, record: _ActorRecord) -> None:
        spec = record.creation_spec
        task_record = _TaskRecord(spec, retries_left=0)
        if self._flight_on:
            now = time.monotonic()
            task_record.state_ts = {"submitted": now, "queued": now}
        with self._lock:
            self._tasks[spec.task_id] = task_record

        def on_granted(node: NodeManager, worker: WorkerHandle):
            if not spec.shared_process:
                # (shared hosts were attached by get_shared_host)
                node.pool.dedicate(worker, record.actor_id)
            with self._lock:
                record.node = node
                record.worker = worker
            self._dispatch(task_record, node, worker)

        lease = PendingLease(
            spec, on_granted=on_granted,
            on_unschedulable=lambda msg: self._actor_creation_failed(
                record, ActorError(record.actor_id, msg)
            ),
        )
        task_record.lease = lease
        pending = 0
        with self._lock:
            for oid in spec.arg_refs:
                entry = self._objects.setdefault(oid, _ObjectEntry())
                if entry.status in (_ObjStatus.PENDING, _ObjStatus.LOST):
                    entry.waiting_tasks.append(spec.task_id)
                    pending += 1
                    if entry.status == _ObjStatus.LOST:
                        self._recover_object(oid)
            task_record.deps_remaining = pending
            lease.deps_ready = pending == 0
        self.scheduler.submit(lease)

    def _actor_creation_done(self, record: _ActorRecord) -> None:
        # Replay-then-flip: methods buffered while the actor was
        # PENDING must hit the worker pipe BEFORE any new submission.
        # Flipping ALIVE first (old behavior) let a concurrent
        # _submit_actor_task push straight to the pipe mid-replay —
        # a later call could overtake buffered ones (the
        # test_actor_method_ordering flake; seq numbers were right,
        # arrival order wasn't). So: drain pending in batches while the
        # state still buffers new calls, and flip ALIVE atomically only
        # once the buffer is observed empty.
        while True:
            with self._lock:
                pending = list(record.pending)
                record.pending = []
                if not pending:
                    record.state = ActorState.ALIVE
                    break
            for spec in pending:
                self._push_actor_task(record, spec)
        self.gcs.update_actor(record.actor_id, ActorState.ALIVE,
                              node_id=record.node.node_id,
                              worker_id=record.worker.worker_id)
        if record.termination_requested:
            # Deferred handle-GC termination: the queued methods above are
            # already in the worker's pipe, so drain_exit runs after them.
            self.terminate_actor(record.actor_id)

    def _actor_creation_failed(self, record: _ActorRecord, error: Exception) -> None:
        with self._lock:
            record.state = ActorState.DEAD
            pending = list(record.pending)
            record.pending = []
            in_flight = list(record.in_flight.values())
            record.in_flight = {}
            worker = record.worker
        if worker is not None:
            if self._is_shared_hosted(record, worker):
                worker.send(("destroy_actor", record.actor_id.hex()))
                if record.node is not None:
                    record.node.pool.detach_shared(worker,
                                                   record.actor_id)
            else:
                worker.kill()  # ctor failed: reap the dedicated worker
        self._release_actor_resources(record)
        self.gcs.update_actor(record.actor_id, ActorState.DEAD,
                              death_cause=str(error))
        for oid in record.creation_spec.return_ids():
            self._mark_failed(oid, error)
        for spec in pending + in_flight:
            for oid in spec.return_ids():
                self._mark_failed(oid, ActorDiedError(
                    record.actor_id, "actor creation failed",
                    death_cause=str(error)))

    def _submit_actor_task(self, spec: TaskSpec) -> List[ObjectRef]:
        # HOT PATH (one lock round, see _push_actor_task): a sync actor
        # call submits, pushes, and completes thousands of times per
        # second; the lock is an RLock, so the nested helpers
        # (_increment_arg_pins/_mark_failed) are re-entrant and free.
        if self._ctr_submitted is not None:
            self._ctr_submitted.inc_key(self._key_actor)
        with self._lock:
            record = self._actors.get(spec.actor_id)
            if record is None:
                raise ActorError(spec.actor_id, "unknown actor")
            record.seq += 1
            spec.actor_seq_no = record.seq
            refs = [ObjectRef(oid) for oid in spec.return_ids()]
            for oid in spec.return_ids():
                entry = self._objects.setdefault(oid, _ObjectEntry())
                entry.creating_task = spec.task_id
            if record.state == ActorState.DEAD:
                info = self.gcs.get_actor(spec.actor_id)
                err = ActorDiedError(
                    spec.actor_id, "Actor is dead",
                    death_cause=info.death_cause if info else None,
                )
                for oid in spec.return_ids():
                    self._mark_failed(oid, err)
                return refs
            if record.state in (ActorState.PENDING, ActorState.RESTARTING):
                self._increment_arg_pins(spec)
                record.pending.append(spec)
                return refs
            self._increment_arg_pins(spec)
        self._push_actor_task(record, spec)
        return refs

    def _push_actor_task(self, record: _ActorRecord, spec: TaskSpec) -> None:
        """Push one method call straight into the actor worker's pipe.

        Fast path: ONE runtime-lock round covering bookkeeping + arg
        resolution (was three), and a positional "aexec" frame instead
        of the generic exec dict — per-call pickling of 9 string keys
        and a dict shell was measurable at sync-call rates. The worker's
        reader submits aexec frames directly to the actor's executor
        (see worker_main._route_aexec)."""
        resolved: Optional[Dict[int, Any]] = None
        failed = None
        with self._lock:
            record.in_flight[spec.task_id.binary()] = spec
            worker = record.worker
            task_record = _TaskRecord(spec, retries_left=spec.max_retries,
                                      node=record.node, worker=worker,
                                      state="RUNNING")
            if self._flight_on:
                # Actor pushes skip the scheduler: submit == scheduled
                # (queue/sched stages are genuinely ~0 on this path).
                now = time.monotonic()
                task_record.state_ts = {"submitted": now, "queued": now,
                                        "scheduled": now}
            self._tasks[spec.task_id] = task_record
            self._worker_tasks.setdefault(
                worker.worker_id.binary(), set()).add(spec.task_id)
            if spec.arg_refs:
                resolved = {}
                for i, oid in enumerate(spec.arg_refs):
                    payload = self._object_entry_payload(oid)
                    if payload is None or payload[0] == "error":
                        failed = (payload[1] if payload else
                                  ObjectLostError(
                                      oid, "actor-task arg unavailable"))
                        break
                    resolved[i] = payload
        if failed is not None:
            with self._lock:
                record.in_flight.pop(spec.task_id.binary(), None)
            for oid in spec.return_ids():
                self._mark_failed(oid, failed)
            return
        ok = worker.send(("aexec", spec.task_id.hex(), spec.actor_id.hex(),
                          spec.method_name, spec.args_frame, resolved,
                          spec.num_returns, spec.trace_ctx))
        if task_record.state_ts is not None:
            task_record.state_ts["dispatched"] = time.monotonic()
        if not ok:
            self._handle_worker_death(worker)

    @staticmethod
    def _is_shared_hosted(record, worker) -> bool:
        """True when the actor is ACTUALLY multiplexed on a shared host
        (vs a shared_process actor that degraded to a dedicated worker
        on a daemon node, where the dedicated lifecycle paths apply)."""
        return (record.creation_spec.shared_process
                and record.actor_id in getattr(worker, "actor_ids", ()))

    def terminate_actor(self, actor_id: ActorID) -> None:
        """Graceful termination: drain queued methods, then exit the worker.

        Triggered when the owning handle goes out of scope (reference:
        actor handle refcount drop -> __ray_terminate__).
        """
        with self._lock:
            record = self._actors.get(actor_id)
            if record is None or record.state == ActorState.DEAD:
                return
            if record.state in (ActorState.PENDING, ActorState.RESTARTING):
                # Creation in flight: queued method calls must run first.
                # Termination resumes once the actor is ALIVE and drained.
                record.termination_requested = True
                return
            record.state = ActorState.DEAD
            record.restarts_left = 0
            pending = list(record.pending)
            record.pending = []
            worker = record.worker
        self.gcs.update_actor(actor_id, ActorState.DEAD,
                              death_cause="all handles out of scope")
        for spec in pending:
            for oid in spec.return_ids():
                self._mark_failed(oid, ActorDiedError(
                    actor_id, "actor terminated",
                    death_cause="all handles out of scope"))
        self._release_actor_resources(record)
        if worker is not None:
            if self._is_shared_hosted(record, worker):
                # The host outlives this actor: drop only the instance
                # (queued methods already in the pipe run first — the
                # worker processes its pipe FIFO).
                worker.send(("destroy_actor",
                             record.actor_id.hex()))
                node = record.node
                if node is not None:
                    node.pool.detach_shared(worker, record.actor_id)
            else:
                worker.send(("drain_exit",))

    def _release_actor_resources(self, record: _ActorRecord) -> None:
        """Return the actor's reserved resources once it is DEAD for good.

        Reference: raylet releases an actor worker's resources on death.
        """
        with self._lock:
            if record.resources_released or record.node is None:
                return
            record.resources_released = True
            node, spec = record.node, record.creation_spec
        if spec.strategy.kind != "PLACEMENT_GROUP":
            node.ledger.release(spec.resources)
        self.scheduler.notify()

    def kill_actor(self, actor_id: ActorID, no_restart: bool = True) -> None:
        with self._lock:
            record = self._actors.get(actor_id)
            if record is None:
                return
            if no_restart:
                record.restarts_left = 0
            worker = record.worker
        if worker is not None and self._is_shared_hosted(record, worker):
            # Never kill a shared host for one tenant: evict the
            # instance and run this actor's death path directly.
            worker.send(("destroy_actor", actor_id.hex()))
            node = record.node
            if node is not None:
                node.pool.detach_shared(worker, actor_id)
            self._handle_actor_death(record)
        elif worker is not None:
            # kill() marks the handle DEAD, which suppresses the pump
            # thread's death callback — run the FT path synchronously so
            # in-flight and subsequent calls fail deterministically.
            worker.kill()
            self._handle_worker_death(worker)
        else:
            self._handle_actor_death(record)

    def get_actor_record(self, actor_id: ActorID) -> Optional[_ActorRecord]:
        with self._lock:
            return self._actors.get(actor_id)

    # ---------------------------------------------------- worker messages
    def _handle_worker_message(self, worker: WorkerHandle, msg: tuple) -> None:
        # Instrumented like the reference's event loops
        # (asio/instrumented_io_context.h): per-kind latency/count
        # aggregates surface via the state API and `rt status -v`.
        with _event_stats.measure(f"runtime.worker_msg.{msg[0]}"):
            self._handle_worker_message_impl(worker, msg)

    def _handle_worker_message_impl(self, worker: WorkerHandle,
                                    msg: tuple) -> None:
        kind = msg[0]
        if kind == "register":
            return
        if kind == "telemetry":
            # Worker flusher payload (metric deltas + finished spans):
            # merge into the head registry/timeline. Same handler for
            # head-local workers and daemon-relayed ones — the payload
            # carries its own node/worker identity.
            from ..observability import telemetry as _telemetry

            _telemetry.absorb(msg[1])
            return
        if kind == "revoked":
            # Reply to the revoke we sent when this worker blocked:
            # these tasks were still queued (never started) in the
            # worker's pipe — reschedule them so they can't starve
            # behind the blocked head-of-line task.
            self._requeue_revoked(worker, msg[1])
            return
        if kind == "refadd":
            self._ref_added(ObjectID(msg[1]))
            return
        if kind == "refdel":
            self._ref_removed(ObjectID(msg[1]))
            return
        if kind == "done":
            _, task_id_hex, results = msg
            task_id = TaskID.from_hex(task_id_hex)
            with self._lock:
                record = self._tasks.get(task_id)
            if record is None:
                return
            if record.spec.task_type == TaskType.ACTOR_CREATION_TASK:
                actor = self._actors.get(record.spec.actor_id)
                with self._lock:
                    record.state = "DONE"
                self._task_finished(record, "DONE")
                if actor is not None:
                    self._actor_creation_done(actor)
                    if not actor.creation_pins_released:
                        actor.creation_pins_released = True
                        self._decrement_arg_pins(record.spec)
                self._mark_ready_creation_returns(record, results)
            elif record.spec.task_type == TaskType.ACTOR_TASK:
                actor = self._actors.get(record.spec.actor_id)
                if actor is not None:
                    with self._lock:
                        actor.in_flight.pop(task_id.binary(), None)
                self._complete_actor_task(record, results)
            else:
                self._complete_task(record, results)
            self.scheduler.notify()
        elif kind == "error":
            _, task_id_hex, err_blob, retryable = msg
            task_id = TaskID.from_hex(task_id_hex)
            error = serialization.loads(err_blob)
            with self._lock:
                record = self._tasks.get(task_id)
            if record is None:
                return
            if record.spec.task_type == TaskType.ACTOR_CREATION_TASK:
                self._task_finished(record, "FAILED")
                actor = self._actors.get(record.spec.actor_id)
                if actor is not None:
                    self._actor_creation_failed(actor, error)
            elif record.spec.task_type == TaskType.ACTOR_TASK:
                actor = self._actors.get(record.spec.actor_id)
                if actor is not None:
                    with self._lock:
                        actor.in_flight.pop(task_id.binary(), None)
                with self._lock:
                    if record.worker is not None:
                        assigned = self._worker_tasks.get(
                            record.worker.worker_id.binary())
                        if assigned is not None:
                            assigned.discard(task_id)
                record.state = "FAILED"
                self._task_finished(record, "FAILED")
                for oid in record.spec.return_ids():
                    self._mark_failed(oid, error)
            else:
                # App-level exception: only retried with retry_exceptions.
                self._fail_task(record, error,
                                retryable=record.spec.retry_exceptions)
            self.scheduler.notify()
        elif kind in ("get", "wait"):
            # Guard: a handler exception must become an error REPLY, not
            # kill this worker's reader loop (which would hang the worker).
            try:
                if kind == "get":
                    self._handle_get_async(worker, msg)
                else:
                    self._handle_wait_async(worker, msg)
            except Exception as e:  # noqa: BLE001
                try:
                    worker.send(("reply", msg[1], False, e))
                except Exception:
                    pass
        elif kind == "fetch_object":
            # Cross-host object pull: a blocking chunked transfer that must
            # NOT run on the node's single message-relay thread (it would
            # queue task completions behind a multi-second copy). Bounded
            # executor; fetches don't depend on each other, so the cap
            # cannot deadlock.
            self._fetch_pool().submit(self._handle_worker_rpc, worker, msg)
        elif kind in ("put", "submit", "kill_actor", "cancel", "get_actor",
                      "put_named_handle"):
            # Quick, non-blocking RPCs run inline on this worker's reader
            # thread (ordering preserved, no thread churn). Blocking
            # get/wait are fully ASYNC above — callbacks on object
            # completion, never a parked thread — so deep nested-task
            # fan-outs can't exhaust any handler pool (reference: the
            # event-loop design of the C++ core worker RPC handlers).
            self._handle_worker_rpc(worker, msg)

    def _mark_ready_creation_returns(self, record: _TaskRecord, results) -> None:
        for i, (kind, payload) in enumerate(results):
            oid = ObjectID.for_return(record.spec.task_id, i)
            if kind == "inline":
                self.memory_store.put(oid, payload)
                self._mark_ready(oid, ("memory",))

    def _complete_actor_task(self, record: _TaskRecord, results) -> None:
        spec = record.spec
        self._task_finished(record, "DONE")
        with self._lock:
            record.state = "DONE"
            if record.worker is not None:
                assigned = self._worker_tasks.get(
                    record.worker.worker_id.binary())
                if assigned is not None:
                    assigned.discard(spec.task_id)
        for i, (kind, payload) in enumerate(results):
            oid = ObjectID.for_return(spec.task_id, i)
            if kind == "inline":
                self.memory_store.put(oid, payload)
                self._mark_ready(oid, ("memory",))
            else:
                size = payload
                record.node.store.register_external(oid, size)
                self._mark_ready(oid, ("shm", record.node.node_id, size))
        self._decrement_arg_pins(spec)

    def _fetch_pool(self):
        pool = getattr(self, "_fetch_executor", None)
        if pool is None:
            from concurrent.futures import ThreadPoolExecutor

            pool = ThreadPoolExecutor(max_workers=8,
                                      thread_name_prefix="rt-fetch")
            self._fetch_executor = pool
        return pool

    def _handle_get_async(self, worker: WorkerHandle, msg: tuple) -> None:
        """Worker get RPC without a parked thread: entry-status watchers
        assemble the reply of shm-pointer/inline entries when the last
        object completes (no value materialization on the head — the
        worker resolves the pointers; timeout via Timer)."""
        _, req_id, id_bins, timeout = msg
        oids = [ObjectID(b) for b in id_bins]
        self._mark_worker_blocked(worker)
        state = {"sent": False}
        slock = threading.Lock()
        timer: List[Optional[threading.Timer]] = [None]
        registered: List[tuple] = []

        def all_done_locked() -> bool:
            for oid in oids:
                e = self._objects.get(oid)
                if e is None or e.status not in (_ObjStatus.READY,
                                                 _ObjStatus.FAILED):
                    return False
            return True

        def cleanup_locked() -> None:
            for oid, cb in registered:
                entry = self._objects.get(oid)
                if entry is not None:
                    try:
                        entry.watchers.remove(cb)
                    except ValueError:
                        pass

        def try_finish(timed_out: bool = False) -> None:
            with self._lock:
                if not timed_out and not all_done_locked():
                    return
            with slock:
                if state["sent"]:
                    return
                state["sent"] = True
            if timer[0] is not None:
                timer[0].cancel()
            with self._lock:
                cleanup_locked()
                entries = None
                if not timed_out:
                    entries = []
                    for oid in oids:
                        payload = self._object_entry_payload(oid)
                        entries.append(payload if payload is not None
                                       else ("error",
                                             ObjectLostError(oid)))
            self._mark_worker_unblocked(worker)
            try:
                if timed_out:
                    worker.send(("reply", req_id, False,
                                 GetTimeoutError("get() timed out")))
                else:
                    worker.send(("reply", req_id, True, entries))
            except Exception:
                pass

        if timeout is not None:
            timer[0] = threading.Timer(timeout, lambda: try_finish(True))
            timer[0].daemon = True
            timer[0].start()
        recover: List[ObjectID] = []
        with self._lock:
            for oid in oids:
                entry = self._objects.setdefault(oid, _ObjectEntry())
                if entry.status in (_ObjStatus.READY, _ObjStatus.FAILED):
                    continue
                if entry.status == _ObjStatus.LOST:
                    recover.append(oid)
                cb = lambda: try_finish(False)  # noqa: E731
                entry.watchers.append(cb)
                registered.append((oid, cb))
        for oid in recover:
            self._recover_object(oid)
        try_finish(False)

    def _handle_wait_async(self, worker: WorkerHandle, msg: tuple) -> None:
        """Worker wait RPC via status watchers — no value materialization,
        no parked thread."""
        _, req_id, id_bins, num_returns, timeout = msg
        oids = [ObjectID(b) for b in id_bins]
        if num_returns > len(oids):
            worker.send(("reply", req_id, False, ValueError(
                "num_returns exceeds number of refs")))
            return
        self._mark_worker_blocked(worker)
        state = {"sent": False}
        slock = threading.Lock()
        timer: List[Optional[threading.Timer]] = [None]
        registered: List[tuple] = []  # (oid, callback, created_entry)

        def done_ids():
            out = []
            for oid in oids:
                e = self._objects.get(oid)
                if e is not None and e.status in (_ObjStatus.READY,
                                                  _ObjStatus.FAILED):
                    out.append(oid)
            return out

        def try_finish(force: bool = False) -> None:
            with self._lock:
                done = done_ids()
                if len(done) < num_returns and not force:
                    return
            with slock:
                if state["sent"]:
                    return
                state["sent"] = True
            if timer[0] is not None:
                timer[0].cancel()
            # Drop our watcher closures (and any phantom PENDING entries
            # this wait itself created for never-seen ids) so early-satisfied
            # or timed-out waits don't leak per-call state.
            with self._lock:
                for oid, cb, created in registered:
                    entry = self._objects.get(oid)
                    if entry is None:
                        continue
                    try:
                        entry.watchers.remove(cb)
                    except ValueError:
                        pass
                    if (created and entry.status == _ObjStatus.PENDING
                            and not entry.watchers and not entry.futures
                            and not entry.waiting_tasks
                            and entry.creating_task is None):
                        del self._objects[oid]
            self._mark_worker_unblocked(worker)
            try:
                worker.send(("reply", req_id, True,
                             [oid.binary() for oid in done[:num_returns]]))
            except Exception:
                pass

        if timeout is not None:
            timer[0] = threading.Timer(timeout, lambda: try_finish(True))
            timer[0].daemon = True
            timer[0].start()
        with self._lock:
            done_now = set(done_ids())
            for oid in oids:
                if oid in done_now:
                    continue
                created = oid not in self._objects
                entry = self._objects.setdefault(oid, _ObjectEntry())
                cb = lambda: try_finish(False)  # noqa: E731
                entry.watchers.append(cb)
                registered.append((oid, cb, created))
        try_finish(False)

    def _handle_worker_rpc(self, worker: WorkerHandle, msg: tuple) -> None:
        with _event_stats.measure(f"runtime.worker_rpc.{msg[0]}"):
            self._handle_worker_rpc_impl(worker, msg)

    def _handle_worker_rpc_impl(self, worker: WorkerHandle,
                                msg: tuple) -> None:
        kind, req_id = msg[0], msg[1]
        try:
            if kind == "fetch_object":
                # Cross-host object pull FALLBACK: daemons normally pull
                # peer-to-peer (PullManager); reaching this head relay
                # means P2P failed (or the worker is head-local). Counted
                # so tests can assert the relay stays cold. Blocks (on
                # the bounded fetch pool) through lineage reconstruction
                # when the holder died mid-pull.
                self.relay_fetch_count = getattr(
                    self, "relay_fetch_count", 0) + 1
                frame = self._fetch_frame_blocking(ObjectID(msg[2]))
                worker.send(("reply", req_id, True, frame))
            elif kind == "put":
                _, _, oid_bin, entry = msg
                oid = ObjectID(oid_bin)
                if entry[0] == "inline":
                    self.memory_store.put(oid, entry[1])
                    self._mark_ready(oid, ("memory",))
                else:
                    size = entry[1]
                    node = self._node_of_worker(worker)
                    node.store.register_external(oid, size)
                    self._mark_ready(oid, ("shm", node.node_id, size))
                self._ref_added(oid)
                worker.send(("reply", req_id, True, oid_bin))
            elif kind == "submit":
                _, _, spec_blob = msg
                spec = serialization.loads(spec_blob)
                refs = self.submit_spec(spec)
                # Pin each return on the borrower's behalf BEFORE our local
                # temp refs are GC'd; the worker's refdel releases this.
                for r in refs:
                    self._ref_added(r.id)
                worker.send(("reply", req_id, True,
                             [r.id.binary() for r in refs]))
            elif kind == "kill_actor":
                _, _, actor_bin, no_restart = msg
                self.kill_actor(ActorID(actor_bin), no_restart)
                worker.send(("reply", req_id, True, None))
            elif kind == "cancel":
                _, _, oid_bin, force = msg
                self.cancel(ObjectRef(ObjectID(oid_bin), _register=False), force)
                worker.send(("reply", req_id, True, None))
            elif kind == "put_named_handle":
                _, _, actor_bin, blob = msg
                self.gcs.kv_put(b"actor_handle:" + actor_bin, blob,
                                "actors")
                worker.send(("reply", req_id, True, None))
            elif kind == "get_actor":
                _, _, name, namespace = msg
                info = self.gcs.get_named_actor(name, namespace)
                payload = None
                if info is not None:
                    blob = self.gcs.kv_get(
                        b"actor_handle:" + info.actor_id.binary(), "actors"
                    )
                    payload = blob
                worker.send(("reply", req_id, True, payload))
        except Exception as e:  # noqa: BLE001
            try:
                worker.send(("reply", req_id, False, e))
            except Exception:
                pass

    def _node_of_worker(self, worker: WorkerHandle) -> NodeManager:
        node = self.scheduler.get_node(worker.node_id)
        if node is None:
            raise ObjectLostError(None, "worker's node is gone")
        return node

    def _mark_worker_blocked(self, worker: WorkerHandle) -> None:
        """Release CPU + pool slot while a worker blocks in get/wait.

        Reference: core worker notifies the raylet it is blocked so the CPU
        is released and the pool can start another worker, avoiding deadlock
        when nested tasks wait on their children.
        """
        with self._lock:
            assigned = self._worker_tasks.get(worker.worker_id.binary())
            # Pipelined tasks share one same-key lease: any record stands
            # in for the lease's resource shape.
            record = None
            for task_id in assigned or ():
                r = self._tasks.get(task_id)
                if r is not None and r.state == "RUNNING":
                    record = r
                    break
            node = self.scheduler.get_node(worker.node_id)
            if record is not None and node is not None and not record.resources_released:
                for task_id in assigned or ():
                    r = self._tasks.get(task_id)
                    if r is not None:
                        r.resources_released = True
                node.pool.grow(1)
                self._blocked_workers[worker.worker_id.binary()] = node
            else:
                record = None
        if record is not None:
            if worker.actor_id is not None:
                # Dedicated actor worker: no pool lease — free the CPU the
                # blocked method logically holds so nested children can
                # schedule (old per-record semantics).
                if record.spec.strategy.kind != "PLACEMENT_GROUP":
                    node.ledger.release(record.spec.resources)
            else:
                # Release the lease's resources ONCE (flagged on the
                # handle so the completion path skips its final release).
                self.scheduler.release_lease_resources(node, worker,
                                                       record.spec)
                # Recall pipelined same-key tasks still queued in this
                # worker's pipe: the head-of-line task may block
                # indefinitely (e.g. on a signal or a borrowed ref), and
                # eagerly-pushed tasks would starve even with idle
                # workers. The worker replies "revoked" with the subset
                # it actually pulled back (never-started by definition),
                # which _requeue_revoked reschedules.
                with self._lock:
                    assigned = self._worker_tasks.get(
                        worker.worker_id.binary()) or set()
                    extra = [
                        t.hex() for t in assigned
                        if (r := self._tasks.get(t)) is not None
                        and r.spec.task_type == TaskType.NORMAL_TASK
                        and r.spec.strategy.kind == "DEFAULT"
                    ]
                if len(extra) > 1:
                    worker.send(("revoke", extra))
        self.scheduler.notify()

    def _requeue_revoked(self, worker: WorkerHandle, task_hexes) -> None:
        """Reschedule tasks the worker pulled back out of its pipe. The
        worker guarantees a revoked task never started; guard against
        stale replies (worker death already rescheduled the record)."""
        requeue = []
        with self._lock:
            assigned = self._worker_tasks.get(worker.worker_id.binary())
            for task_hex in task_hexes:
                task_id = TaskID.from_hex(task_hex)
                record = self._tasks.get(task_id)
                if (record is None or record.worker is not worker
                        or record.state != "RUNNING"):
                    continue
                if assigned is not None:
                    assigned.discard(task_id)
                record.node = record.worker = None
                record.state = "PENDING"
                # The shared lease's resources were released on block;
                # the fresh lease below does its own accounting.
                record.resources_released = False
                requeue.append(record)
        for record in requeue:
            self._schedule_task(record)
        if requeue:
            self.scheduler.notify()

    def _mark_worker_unblocked(self, worker: WorkerHandle) -> None:
        with self._lock:
            node = self._blocked_workers.pop(worker.worker_id.binary(), None)
            if node is not None:
                node.pool.size = max(1, node.pool.size - 1)

    # --------------------------------------------------- memory pressure
    def _on_memory_pressure(self, snapshot) -> None:
        """Worker-killing policy: above the usage threshold, kill the
        worker running the newest retriable normal task (reference:
        raylet worker killing policy — newest-first protects long-running
        work, retriable-first guarantees forward progress)."""
        victim = None
        with self._lock:
            for worker_bin in reversed(list(self._worker_tasks)):
                record = None
                for task_id in self._worker_tasks[worker_bin]:
                    r = self._tasks.get(task_id)
                    if r is not None and r.state == "RUNNING":
                        record = r
                        break
                if (record is not None
                        and record.worker is not None
                        and record.worker.actor_id is None
                        and not record.worker.actor_ids
                        and record.retries_left > 0):
                    victim = record
                    # Mark DEAD while still holding the lock: a worker that
                    # finishes the victim task in the kill window must not
                    # be re-leased to an innocent (maybe non-retriable)
                    # task — pop_idle skips DEAD handles.
                    from .worker_pool import WorkerHandle

                    victim.worker.state = WorkerHandle.DEAD
                    break
        if victim is None:
            return
        try:
            from ..observability.events import emit

            emit("MEMORY_PRESSURE",
                 f"killing task {victim.spec.describe()} at "
                 f"{snapshot.fraction:.0%} node memory")
        except Exception:
            pass
        worker = victim.worker
        worker.kill()
        # kill() marks the handle DEAD before the process exits, which
        # tells the pool's handler loop NOT to fire on_worker_death (so
        # intentional kills — rt.kill, shutdown — stay silent). This kill
        # wants the failure path: invoke it directly to fail-and-retry.
        self._handle_worker_death(worker)

    # ------------------------------------------------------- worker death
    def _handle_worker_death(self, worker: WorkerHandle) -> None:
        with self._lock:
            assigned = self._worker_tasks.pop(worker.worker_id.binary(),
                                              None) or set()
            records = [r for r in (self._tasks.get(t) for t in assigned)
                       if r is not None]
            actor_record = None
            if worker.actor_id is not None:
                actor_record = self._actors.get(worker.actor_id)
            # A dead SHARED host takes all its multiplexed actors down;
            # each one goes through the normal death/restart FSM (a
            # restart lands on a surviving or fresh shared host).
            shared_records = [r for r in (self._actors.get(a)
                                          for a in getattr(
                                              worker, "actor_ids", ()))
                              if r is not None]
        node = self.scheduler.get_node(worker.node_id)
        if node is not None and node.alive:
            worker.state = WorkerHandle.DEAD
        if shared_records:
            worker.actor_ids.clear()  # present: shared_records nonempty
            for rec in shared_records:
                self._handle_actor_death(rec)
            return
        if actor_record is not None:
            self._handle_actor_death(actor_record)
            return
        # Fail EVERY task assigned to the dead worker (1 running +
        # pipelined ones queued in its pipe).
        for record in records:
            if record.state == "RUNNING":
                self._fail_task(record, WorkerCrashedError(
                    f"worker executing {record.spec.describe()} died"))
        self.scheduler.notify()

    def _handle_actor_death(self, record: _ActorRecord) -> None:
        with self._lock:
            if record.state == ActorState.DEAD:
                return
            in_flight = list(record.in_flight.values())
            record.in_flight = {}
            can_restart = record.restarts_left != 0
            if can_restart:
                if record.restarts_left > 0:
                    record.restarts_left -= 1
                record.state = ActorState.RESTARTING
                # In-flight methods are failed (at-most-once default, like
                # the reference; max_task_retries replay is opt-in per task).
                for spec in in_flight:
                    if spec.max_retries > 0:
                        record.pending.insert(0, spec)
            else:
                record.state = ActorState.DEAD
        if record.state == ActorState.RESTARTING:
            self.gcs.update_actor(record.actor_id, ActorState.RESTARTING)
            for spec in in_flight:
                if spec.max_retries <= 0:
                    for oid in spec.return_ids():
                        self._mark_failed(oid, ActorDiedError(
                            record.actor_id, "actor died; method not retried"))
            self._schedule_actor_creation(record)
        else:
            max_restarts = record.creation_spec.max_restarts
            cause = ("worker died (max_restarts=%d exhausted)" % max_restarts
                     if max_restarts else "worker died")
            self.gcs.update_actor(record.actor_id, ActorState.DEAD,
                                  death_cause=cause)
            self._release_actor_resources(record)
            with self._lock:
                pending = list(record.pending)
                record.pending = []
            # Pending callers see a TYPED ActorDiedError carrying the
            # death cause, not a bare "actor died" (reference:
            # RayActorError + ActorDeathCause).
            for spec in in_flight + pending:
                for oid in spec.return_ids():
                    self._mark_failed(oid, ActorDiedError(
                        record.actor_id, death_cause=cause))
        self.scheduler.notify()

    # ------------------------------------------------------------ cancel
    def cancel(self, ref: ObjectRef, force: bool = False) -> None:
        task_id = ref.id.task_id()
        with self._lock:
            record = self._tasks.get(task_id)
        if record is None:
            return
        if record.state == "PENDING":
            record.state = "CANCELLED"
            if record.lease is not None:
                with self.scheduler._lock:
                    if record.lease in self.scheduler._queue:
                        self.scheduler._queue.remove(record.lease)
            for oid in record.spec.return_ids():
                self._mark_failed(oid, TaskCancelledError(
                    f"task {record.spec.describe()} cancelled"))
        elif record.state == "RUNNING" and force and record.worker is not None:
            record.worker.kill()

    # ------------------------------------------------------------- info
    def cluster_resources(self) -> Dict[str, float]:
        out: Dict[str, float] = {}
        for node in self.scheduler.nodes():
            for k, v in node.ledger.total.items():
                out[k] = out.get(k, 0.0) + v
        return out

    def available_resources(self) -> Dict[str, float]:
        out: Dict[str, float] = {}
        for node in self.scheduler.nodes():
            for k, v in node.ledger.available.items():
                out[k] = out.get(k, 0.0) + v
        return out

    def next_task_id(self) -> TaskID:
        return TaskID.for_task(self.job_id)

    def next_actor_id(self) -> ActorID:
        return ActorID.of(self.job_id)

    # ---------------------------------------------------------- shutdown
    def shutdown(self) -> None:
        self._stopped.set()
        with self._submit_cv:
            self._submit_cv.notify_all()
        self.gcs.finish_job(self.job_id)
        install_refcount_hooks()
        self._hb_stop.set()
        self.memory_monitor.stop()
        if self.log_monitor is not None:
            self.log_monitor.stop()
        if self._log_unsub is not None:
            self._log_unsub()
        self.scheduler.shutdown()
        self.gcs.shutdown()
        # Daemon-attach plane: close the listener (unblocks the accept
        # thread) and any registered-but-unclaimed daemon connections.
        listener = getattr(self, "_cluster_listener", None)
        if listener is not None:
            try:
                listener.close()
            except OSError:
                pass
            self._cluster_listener = None
            with self._daemon_cv:
                conns = list(self._daemon_conns.values())
                self._daemon_conns.clear()
            for conn in conns:
                conn.close()
        pool = getattr(self, "_fetch_executor", None)
        if pool is not None:
            pool.shutdown(wait=False)


_DEVICE_PROBE = (
    "import json, jax; d = jax.devices(); "
    "print(json.dumps({'platform': d[0].platform, "
    "'kind': d[0].device_kind, 'count': len(d)}))")


def probe_devices(timeout: float = 120.0) -> Dict[str, Any]:
    """What JAX finds on this host — ``{"platform", "kind", "count"}`` —
    asked of a short-lived child, never of this process. A chip belongs
    to one process at a time: a driver that initialised a backend would
    hold the chip its own workers need. The child has exited, and let
    the chip go, before this returns."""
    proc = subprocess.run([sys.executable, "-c", _DEVICE_PROBE],
                          capture_output=True, text=True, timeout=timeout)
    if proc.returncode != 0:
        raise RuntimeError(
            f"device probe failed (rc={proc.returncode}): "
            f"{proc.stderr.strip()[-800:]}")
    return json.loads(proc.stdout.strip().splitlines()[-1])


def _local_chip_count() -> int:
    """Accelerator chips on this host, for the node's ``TPU`` resource
    when ``resources`` does not give one."""
    if jax_pinned_to_cpu() or importlib.util.find_spec("jax") is None:
        return 0  # nothing to count, and no child to pay for
    found = probe_devices()
    return 0 if found["platform"] == "cpu" else int(found["count"])


# ---------------------------------------------------------------------------
# Module-level current-runtime dispatch (driver Runtime or worker adapter).
# ---------------------------------------------------------------------------

_runtime: Optional[Runtime] = None
_worker_runtime = None
_init_lock = threading.Lock()


def init(num_cpus: Optional[float] = None, num_nodes: int = 1,
         resources: Optional[Dict[str, float]] = None,
         object_store_memory: Optional[int] = None,
         ignore_reinit_error: bool = False,
         storage: Optional[str] = None,
         env: Optional[dict] = None, **kwargs) -> Runtime:
    global _runtime
    with _init_lock:
        if _runtime is not None:
            if ignore_reinit_error:
                return _runtime
            raise RuntimeError("runtime already initialized; "
                               "pass ignore_reinit_error=True to reuse")
        if storage is not None:
            from .storage import ENV_STORAGE_URI, _init_storage

            _init_storage(storage)
            env = dict(env or {})
            env.setdefault(ENV_STORAGE_URI, storage)  # workers inherit
        _runtime = Runtime(num_cpus=num_cpus, num_nodes=num_nodes,
                           resources=resources,
                           object_store_memory=object_store_memory, env=env)
        return _runtime


def shutdown() -> None:
    global _runtime
    with _init_lock:
        if _runtime is not None:
            _runtime.shutdown()
            _runtime = None
            from .storage import _init_storage

            _init_storage(None)  # don't leak storage into the next init


def is_initialized() -> bool:
    return _runtime is not None or _worker_runtime is not None


def get_runtime():
    """The runtime backing the public API in this process."""
    if _worker_runtime is not None:
        return _worker_runtime
    if _runtime is None:
        init()
    return _runtime


def get_head_runtime() -> Optional[Runtime]:
    return _runtime


def _set_worker_mode(worker_runtime) -> None:
    global _worker_runtime
    _worker_runtime = worker_runtime


def is_worker_process() -> bool:
    """True in a spawned task/actor worker, False in a driver."""
    return _worker_runtime is not None


def auto_init() -> None:
    if not is_initialized():
        init()
