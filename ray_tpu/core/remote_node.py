"""Driver-side proxy for a node daemon running in its own OS process.

Reference analog: the raylet client + node manager RPC surface
(``src/ray/raylet_client/raylet_client.h``, ``node_manager.proto``): the
driver keeps scheduling METADATA (a resource-ledger mirror and worker
lease states — valid because this runtime schedules from one place, like
the reference's GCS-side actor scheduling), while worker processes, the
shm arena, and the data plane live in the daemon
(``node_daemon.NodeDaemon``). Worker messages relay over one TCP
connection; object push/pull is chunked (DCN transfer path).

Duck-types the ``scheduler.NodeManager`` surface the driver uses
(``ledger``/``pool``/``store``/bundles), so the cluster scheduler treats
local and daemon-backed nodes uniformly.
"""

from __future__ import annotations

import itertools
import json
import os
import subprocess
import sys
import threading
from typing import Callable, Dict, List, Optional

from .ids import NodeID, ObjectID, PlacementGroupID, WorkerID
from .node_protocol import TELEMETRY_FRAME, ChunkAssembler, FrameConn
from .scheduler import NodeManager, ResourceLedger


class _Pending:
    __slots__ = ("event", "ok", "payload")

    def __init__(self):
        self.event = threading.Event()
        self.ok = False
        self.payload = None


class DaemonConn:
    """Request/reply + event dispatch over the daemon's FrameConn."""

    def __init__(self, conn: FrameConn, on_event: Callable,
                 on_disconnect: Callable):
        import queue

        self._conn = conn
        self._on_event = on_event
        self._on_disconnect = on_disconnect
        self._req_ids = itertools.count(1)
        self._pending: Dict[int, _Pending] = {}
        self._lost = False  # under _lock: the reader has seen the end
        self._assembler = ChunkAssembler()
        self._lock = threading.Lock()
        # Events (worker messages etc.) dispatch on a separate thread so a
        # handler may issue synchronous RPCs on THIS connection — the
        # reader must stay free to deliver their replies (FIFO preserved
        # per daemon).
        self._events: "queue.Queue" = queue.Queue()
        self._dispatcher = threading.Thread(
            target=self._dispatch_loop, daemon=True,
            name="rt-daemon-dispatch")
        self._dispatcher.start()
        self._reader = threading.Thread(target=self._read_loop, daemon=True,
                                        name="rt-daemon-conn")
        self._reader.start()

    def send(self, msg) -> bool:
        return self._conn.send(msg)

    def request(self, build_msg: Callable[[int], list],
                timeout: float = 60.0):
        """``build_msg(req_id)`` returns the frames to send."""
        req_id = next(self._req_ids)
        p = _Pending()
        with self._lock:
            # No reader is left to answer, and the first send to a peer
            # that closed still succeeds: it would wait out its timeout.
            if self._lost:
                raise ConnectionError("node daemon connection lost")
            self._pending[req_id] = p
        for frame in build_msg(req_id):
            if not self._conn.send(frame):
                with self._lock:
                    self._pending.pop(req_id, None)
                raise ConnectionError("node daemon connection lost")
        if not p.event.wait(timeout):
            with self._lock:
                self._pending.pop(req_id, None)
            raise TimeoutError("node daemon RPC timed out")
        if not p.ok:
            raise p.payload if isinstance(p.payload, Exception) else \
                RuntimeError(str(p.payload))
        return p.payload

    def _resolve(self, req_id: int, ok: bool, payload) -> None:
        with self._lock:
            p = self._pending.pop(req_id, None)
        if p is not None:
            p.ok = ok
            p.payload = payload
            p.event.set()

    def _read_loop(self) -> None:
        try:
            while True:
                msg = self._conn.recv()
                kind = msg[0]
                if kind == "reply":
                    _, req_id, ok, payload = msg
                    self._resolve(req_id, ok, payload)
                elif kind == "chunk":
                    _, req_id, seq, total, data = msg
                    full = self._assembler.add(req_id, seq, total, data)
                    if full is not None:
                        self._resolve(req_id, True, full)
                else:
                    self._events.put(msg)
        except (EOFError, OSError):
            # EOF on graceful close; OSError/ConnectionReset when the
            # daemon is SIGKILLed (chaos) — both mean the host is gone.
            pass
        # Fail outstanding RPCs, then run the node-death path (after any
        # queued events drain, so a final "done" isn't lost behind death).
        with self._lock:
            self._lost = True
            pending = list(self._pending.values())
            self._pending.clear()
        for p in pending:
            p.ok = False
            p.payload = ConnectionError("node daemon connection lost")
            p.event.set()
        self._events.put(("__disconnect__",))

    def _dispatch_loop(self) -> None:
        while True:
            msg = self._events.get()
            if msg[0] == "__disconnect__":
                try:
                    self._on_disconnect()
                except Exception:
                    pass
                return
            try:
                self._on_event(msg)
            except Exception:
                pass

    def close(self) -> None:
        self._conn.close()


class RemoteWorkerHandle:
    """Driver-side handle to a worker living under a node daemon."""

    IDLE = "IDLE"
    LEASED = "LEASED"
    DEDICATED = "DEDICATED"
    DEAD = "DEAD"

    def __init__(self, worker_id: WorkerID, node_id: NodeID,
                 conn: DaemonConn):
        self.worker_id = worker_id
        self.node_id = node_id
        self.conn = conn
        self.state = RemoteWorkerHandle.IDLE
        self.actor_id = None
        self.current_tasks: set = set()
        self.lease_expiry: float = 0.0
        self._registered = threading.Event()

    def send(self, msg) -> bool:
        if self.state == RemoteWorkerHandle.DEAD:
            return False
        return self.conn.send(("to_worker", self.worker_id.binary(), msg))

    def alive(self) -> bool:
        return self.state != RemoteWorkerHandle.DEAD

    def kill(self) -> None:
        self.state = RemoteWorkerHandle.DEAD
        self.conn.send(("kill_worker", self.worker_id.binary()))


class RemoteWorkerPool:
    """Worker-lease mirror; spawn/kill are RPCs to the daemon.

    NON-BLOCKING by design: ``try_pop_idle``/``start_dedicated`` are
    called by the scheduler loop under its lock, and worker_started
    events are delivered by this connection's dispatcher thread which
    may itself be blocked on that lock (e.g. a task-done handler calling
    scheduler.notify). So spawn requests are fire-and-forget: the lease
    stays queued and the scheduler retries when the registration event
    notifies it (``on_change``).
    """

    def __init__(self, node_id: NodeID, size: int, conn: DaemonConn,
                 on_change: Callable[[], None]):
        self.node_id = node_id
        self.size = size
        self._conn = conn
        self._on_change = on_change
        self._workers: Dict[WorkerID, RemoteWorkerHandle] = {}
        self._lock = threading.RLock()
        self._spawn_tokens = itertools.count(1)
        # token -> actor_id (None for plain pool spawns), FIFO by send order
        self._inflight_spawns: Dict[int, object] = {}
        # actor_key -> registered handle waiting to be claimed
        self._ready_dedicated: Dict[bytes, RemoteWorkerHandle] = {}

    # called from the conn dispatcher on daemon events
    def _on_worker_started(self, wid_bin: bytes,
                           token: int) -> RemoteWorkerHandle:
        handle = RemoteWorkerHandle(WorkerID(wid_bin), self.node_id,
                                    self._conn)
        with self._lock:
            self._workers[handle.worker_id] = handle
            actor_id = self._inflight_spawns.pop(token, None)
            if actor_id is not None:
                handle.state = RemoteWorkerHandle.DEDICATED
                handle.actor_id = actor_id
                self._ready_dedicated[actor_id.binary()] = handle
        self._on_change()
        return handle

    def _request_spawn(self, actor_id=None) -> None:
        token = next(self._spawn_tokens)
        with self._lock:
            self._inflight_spawns[token] = actor_id
        if not self._conn.send(("spawn_worker", token)):
            with self._lock:
                self._inflight_spawns.pop(token, None)

    def _claim_idle_locked(self, new_state: str, actor_id=None):
        """Under self._lock: claim one registered idle worker into new_state."""
        for w in self._workers.values():
            if (w.state == RemoteWorkerHandle.IDLE and w.alive()
                    and w._registered.is_set()):
                w.state = new_state
                if actor_id is not None:
                    w.actor_id = actor_id
                return w
        return None

    def try_pop_idle(self) -> Optional[RemoteWorkerHandle]:
        with self._lock:
            w = self._claim_idle_locked(RemoteWorkerHandle.LEASED)
            if w is not None:
                return w
            plain_inflight = sum(
                1 for a in self._inflight_spawns.values() if a is None)
            if len(self._alive()) + plain_inflight >= self.size:
                return None
        self._request_spawn()
        return None  # lease retries when the worker registers

    def start_dedicated(self, actor_id) -> Optional[RemoteWorkerHandle]:
        """Claim a prestarted idle worker for the actor when available
        (reference: ``worker_pool.h:104`` PopWorker for actor-creation
        tasks), refilling the pool with a fire-and-forget spawn. Otherwise
        the first call requests a dedicated spawn and returns None; the
        scheduler re-runs the lease when the worker registers and the
        second call claims it."""
        with self._lock:
            handle = self._ready_dedicated.get(actor_id.binary())
            if handle is not None and handle._registered.is_set():
                del self._ready_dedicated[actor_id.binary()]
                return handle
            if handle is not None or any(
                    a is not None and a.binary() == actor_id.binary()
                    for a in self._inflight_spawns.values()):
                return None  # spawn (or registration) still in flight
            w = self._claim_idle_locked(RemoteWorkerHandle.DEDICATED, actor_id)
        if w is not None:
            self._request_spawn()  # refill the pool (outside the lock)
            return w
        self._request_spawn(actor_id)
        return None

    def get_shared_host(self, actor_id):
        """Daemon pools have no multiplexed hosts (the worker pool lives
        in another OS process): shared-process actors degrade to
        dedicated workers on remote nodes. The runtime's lifecycle
        branches key on ACTUAL hosting (worker.actor_ids membership),
        so the dedicated paths apply naturally."""
        return self.start_dedicated(actor_id)

    def detach_shared(self, worker, actor_id) -> None:
        pass

    def return_worker(self, worker: RemoteWorkerHandle) -> None:
        with self._lock:
            if worker.state == RemoteWorkerHandle.LEASED:
                worker.state = RemoteWorkerHandle.IDLE

    def dedicate(self, worker: RemoteWorkerHandle, actor_id) -> None:
        with self._lock:
            worker.state = RemoteWorkerHandle.DEDICATED
            worker.actor_id = actor_id

    def grow(self, n: int = 1) -> None:
        with self._lock:
            self.size += n
        for _ in range(n):
            self._request_spawn()

    def _alive(self) -> List[RemoteWorkerHandle]:
        return [w for w in self._workers.values()
                if w.alive() and w.state != RemoteWorkerHandle.DEDICATED]

    def num_idle(self) -> int:
        with self._lock:
            return sum(1 for w in self._workers.values()
                       if w.state == RemoteWorkerHandle.IDLE and w.alive())

    def get(self, worker_id: WorkerID) -> Optional[RemoteWorkerHandle]:
        with self._lock:
            return self._workers.get(worker_id)

    def all_workers(self) -> List[RemoteWorkerHandle]:
        with self._lock:
            return list(self._workers.values())

    def shutdown(self) -> None:
        for w in self.all_workers():
            w.state = RemoteWorkerHandle.DEAD


class RemoteStoreClient:
    """Chunked push/pull to the daemon's shm arena over the connection."""

    def __init__(self, conn: DaemonConn):
        self._conn = conn

    def put_bytes(self, object_id: ObjectID, frame: bytes) -> None:
        from .node_protocol import chunk_frames

        def build(req_id):
            yield ("store_put_begin", req_id, object_id.binary())
            yield from chunk_frames("store_put_chunk", req_id, frame)

        self._conn.request(build)

    def get_buffer(self, object_id: ObjectID) -> memoryview:
        payload = self._conn.request(
            lambda req_id: [("store_get", req_id, object_id.binary())])
        return memoryview(payload)

    def register_external(self, object_id: ObjectID, size: int) -> None:
        self._conn.request(
            lambda req_id: [("store_register", req_id,
                             object_id.binary(), size)])

    def delete(self, object_id: ObjectID) -> None:
        self._conn.send(("store_delete", object_id.binary()))

    def stats(self) -> dict:
        return self._conn.request(
            lambda req_id: [("store_stats", req_id)])

    def destroy(self) -> None:
        pass  # daemon tears its own store down on shutdown


class RemoteNode:
    """NodeManager stand-in whose data/worker plane is a daemon process."""

    is_remote = True

    def event_stats(self) -> list:
        """The daemon process's per-handler event-loop stats
        (reference: each raylet's instrumented_io_context is
        per-process; the dashboard aggregates across nodes)."""
        return self.conn.request(
            lambda req_id: [("event_stats", req_id)], timeout=5.0)

    def __init__(self, node_id: NodeID, resources: Dict[str, float],
                 message_handler: Callable, on_worker_death: Callable,
                 on_node_death: Callable,
                 driver_addr: str, accept_conn: Callable,
                 object_store_memory: Optional[int] = None,
                 env: Optional[dict] = None, labels: Optional[dict] = None,
                 on_change: Optional[Callable[[], None]] = None,
                 on_locate: Optional[Callable] = None):
        from .config import config

        self.node_id = node_id
        self.ledger = ResourceLedger(dict(resources))
        self.labels = labels or {}
        self.pg_bundles: Dict = {}
        self.alive = True
        self._message_handler = message_handler
        self._on_worker_death = on_worker_death
        self._on_node_death = on_node_death
        self._on_change = on_change or (lambda: None)
        self._on_locate = on_locate

        num_workers = config().num_workers_per_node or max(
            2, int(resources.get("CPU", 2)))
        env_json = json.dumps(dict(env or {}))
        repo_root = os.path.dirname(os.path.dirname(
            os.path.dirname(os.path.abspath(__file__))))
        proc_env = dict(os.environ)
        proc_env["PYTHONPATH"] = repo_root + os.pathsep + proc_env.get(
            "PYTHONPATH", "")
        self.process = subprocess.Popen(
            [sys.executable, "-m", "ray_tpu.core.node_daemon",
             "--driver", driver_addr,
             "--node-id", node_id.hex(),
             "--store-memory", str(object_store_memory or 0),
             "--num-workers", str(num_workers),
             "--env-json", env_json,
             # Head-failover survival (0 = die with the head, default);
             # the daemon re-registers with the node's REAL resource
             # shape, which head-spawned daemons only know driver-side.
             "--rejoin-attempts", str(config().daemon_rejoin_attempts),
             "--rejoin-resources-json", json.dumps(resources)],
            cwd=repo_root, env=proc_env,
        )
        raw_conn, reg_info = accept_conn(node_id)  # blocks until registered
        self.object_addr = (reg_info or {}).get("object_addr")
        self.conn = DaemonConn(raw_conn, self._on_event, self._disconnected)
        self.pool = RemoteWorkerPool(node_id, num_workers, self.conn,
                                     self._on_change)
        self.store = RemoteStoreClient(self.conn)
        self._down = False

    @classmethod
    def adopt(cls, node_id: NodeID, resources: Dict[str, float],
              message_handler: Callable, on_worker_death: Callable,
              on_node_death: Callable, raw_conn, num_workers: int,
              labels: Optional[dict] = None,
              on_change: Optional[Callable[[], None]] = None,
              object_addr: Optional[str] = None,
              on_locate: Optional[Callable] = None) -> "RemoteNode":
        """Attach to a daemon that STARTED ITSELF (``rt start
        --address=...``) and registered over the cluster listener — no
        process spawn; the daemon's lifetime belongs to its own shell/
        systemd (reference: raylets started by ``ray start`` joining the
        GCS, scripts.py:532)."""
        self = cls.__new__(cls)
        self.node_id = node_id
        self.ledger = ResourceLedger(dict(resources))
        self.labels = labels or {}
        self.pg_bundles = {}
        self.alive = True
        self._message_handler = message_handler
        self._on_worker_death = on_worker_death
        self._on_node_death = on_node_death
        self._on_change = on_change or (lambda: None)
        self._on_locate = on_locate
        self.object_addr = object_addr
        self.process = None
        self.conn = DaemonConn(raw_conn, self._on_event, self._disconnected)
        self.pool = RemoteWorkerPool(node_id, num_workers, self.conn,
                                     self._on_change)
        self.store = RemoteStoreClient(self.conn)
        self._down = False
        return self

    # -- daemon events -----------------------------------------------------
    def _on_event(self, msg: tuple) -> None:
        kind = msg[0]
        if kind == "locate_object":
            if self._on_locate is not None:
                self._on_locate(self, msg[1], msg[2])
            return
        if kind == TELEMETRY_FRAME:
            # The daemon process's own metric deltas + spans (workers
            # under it relay theirs via "from_worker" like any message).
            from ..observability import telemetry as _telemetry

            _telemetry.absorb(msg[1])
            return
        if kind == "worker_started":
            self.pool._on_worker_started(msg[1], msg[2] if len(msg) > 2
                                         else 0)
        elif kind == "worker_dead":
            handle = self.pool.get(WorkerID(msg[1]))
            if handle is not None and handle.state != RemoteWorkerHandle.DEAD:
                handle.state = RemoteWorkerHandle.DEAD
                self._on_worker_death(handle)
        elif kind == "from_worker":
            _, wid_bin, payload = msg
            handle = self.pool.get(WorkerID(wid_bin))
            if handle is None:
                return
            if payload and payload[0] == "register":
                handle._registered.set()
                # a lease may be parked waiting for this registration
                self._on_change()
            self._message_handler(handle, payload)

    def _disconnected(self) -> None:
        if self._down:
            return
        self._down = True
        self.alive = False
        self._on_node_death(self.node_id)

    # -- NodeManager surface ------------------------------------------------
    def start(self) -> None:
        for _ in range(min(self.pool.size, 2)):
            self.pool._request_spawn()

    # PG bundle logic is pure ledger math — share one implementation.
    reserve_bundle = NodeManager.reserve_bundle
    return_bundle = NodeManager.return_bundle

    def shutdown(self) -> None:
        self._down = True
        self.alive = False
        try:
            self.conn.send(("shutdown",))
        except Exception:
            pass
        self.conn.close()
        if self.process is None:
            return  # adopted daemon: its own shell owns the process
        try:
            self.process.terminate()
            self.process.wait(timeout=3)
        except Exception:
            try:
                self.process.kill()
            except Exception:
                pass
