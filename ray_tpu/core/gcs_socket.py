"""Socket client + launcher for the native control-store daemon.

Reference analog: ``src/ray/gcs/gcs_client/`` (GcsClient over gRPC) talking
to the ``gcs_server`` process. Here the daemon is the C++ binary built from
``ray_tpu/_native/control_store.cc``; this module spawns it, speaks its
length-prefixed binary protocol, and exposes the same surface as the
in-process :class:`~ray_tpu.core.gcs.GlobalControlStore` KV/node/pubsub
methods so either backend can serve :class:`~ray_tpu.core.gcs.GcsClient`
callers.

Payloads the daemon treats as opaque bytes are pickled Python objects on
this side (like the reference KV storing serialized protobufs).
"""

from __future__ import annotations

import os
import socket
import struct
import subprocess
import threading
import time
from typing import Any, Callable, Dict, List, Optional, Tuple

from ..observability import event_stats as _event_stats

_HERE = os.path.dirname(os.path.abspath(__file__))
_NATIVE_DIR = os.path.join(os.path.dirname(_HERE), "_native")
_BINARY = os.path.join(_NATIVE_DIR, "build", "control_store")

# Protocol constants — keep in sync with control_store.cc.
OP_PING = 1
OP_KV_PUT = 2
OP_KV_GET = 3
OP_KV_DEL = 4
OP_KV_KEYS = 5
OP_NODE_REGISTER = 10
OP_NODE_HEARTBEAT = 11
OP_NODE_LIST = 12
OP_NODE_MARK_DEAD = 13
OP_PUBLISH = 20
OP_SUBSCRIBE = 21
OP_HEALTH_START = 30
OP_STATS = 31
OP_TABLE_PUT = 40
OP_TABLE_DEL = 41
OP_TABLE_SCAN = 42
OP_SHUTDOWN = 99
OP_PUSH = 0xFE

ST_OK = 0
ST_ERR = 1
ST_NIL = 2

_OP_NAMES = {v: k[3:].lower() for k, v in list(globals().items())
             if k.startswith("OP_")}


class ControlStoreError(Exception):
    pass


class ControlStoreConnectionError(ControlStoreError):
    """Transport-level failure (daemon gone / connection dropped) —
    distinct from protocol ST_ERR replies so the client retry loop never
    re-runs a call the daemon explicitly rejected."""


def _pack_bytes(b: bytes) -> bytes:
    return struct.pack("<I", len(b)) + b


class _FrameReader:
    def __init__(self, data: bytes):
        self._d = data
        self._pos = 0

    def u8(self) -> int:
        v = self._d[self._pos]
        self._pos += 1
        return v

    def u32(self) -> int:
        (v,) = struct.unpack_from("<I", self._d, self._pos)
        self._pos += 4
        return v

    def f64(self) -> float:
        (v,) = struct.unpack_from("<d", self._d, self._pos)
        self._pos += 8
        return v

    def bytes_(self) -> bytes:
        n = self.u32()
        v = self._d[self._pos:self._pos + n]
        self._pos += n
        return v


def _recv_exact(sock: socket.socket, n: int) -> bytes:
    buf = b""
    while len(buf) < n:
        chunk = sock.recv(n - len(buf))
        if not chunk:
            raise ControlStoreConnectionError("connection closed")
        buf += chunk
    return buf


def _recv_frame(sock: socket.socket) -> bytes:
    (n,) = struct.unpack("<I", _recv_exact(sock, 4))
    return _recv_exact(sock, n)


class ControlStoreClient:
    """Request/response client (one TCP conn, lock-serialized).

    Subscriptions use a second dedicated connection with a reader thread
    (:meth:`subscribe`), since push frames interleave with responses.

    Transport failures reconnect transparently with bounded exponential
    backoff (``gcs_client_retry_attempts`` × ``gcs_client_retry_base_ms``)
    — a control-store daemon restarted on the same address (head
    failover, daemon crash) heals instead of failing the first call after
    the restart. Caveat: a retried mutation may apply twice if the first
    attempt committed before the connection died; every RETRIED op is
    either idempotent or (``kv_put overwrite=False``) first-wins, so a
    double-apply cannot change the stored state under the
    single-writer-per-key discipline the runtime follows (a retried
    overwrite CAN clobber an interleaved write to the same key from
    another client; no such contended keys exist today). Delivery ops
    are NOT retried (``publish`` would fan out twice) and neither are
    timeouts (a slow daemon may still execute the first attempt).
    """

    def __init__(self, address: Tuple[str, int], timeout: float = 10.0):
        self.address = address
        self._timeout = timeout
        self._sock = socket.create_connection(address, timeout=timeout)
        self._sock.setsockopt(socket.IPPROTO_TCP, socket.TCP_NODELAY, 1)
        self._lock = threading.Lock()
        self._closed = False
        self._sub_client: Optional["_Subscriber"] = None

    # -- wire -------------------------------------------------------------
    def _reconnect_locked(self) -> None:
        try:
            self._sock.close()
        except OSError:
            pass
        self._sock = socket.create_connection(self.address,
                                              timeout=self._timeout)
        self._sock.setsockopt(socket.IPPROTO_TCP, socket.TCP_NODELAY, 1)

    def _roundtrip_locked(self, frame: bytes, retryable: bool) -> bytes:
        from .config import config

        attempts = (max(1, int(config().gcs_client_retry_attempts))
                    if retryable else 1)
        delay = max(0.001, config().gcs_client_retry_base_ms / 1000.0)
        for attempt in range(attempts):
            try:
                self._sock.sendall(struct.pack("<I", len(frame)) + frame)
                return _recv_frame(self._sock)
            except socket.timeout:
                # A SLOW daemon is not a dead one: the request may still
                # execute, so a retry would double-apply (e.g. a publish
                # delivering twice). Surface the timeout — but close the
                # socket first: the late reply is still in flight, and
                # the next call on this connection would read it as its
                # own response (off-by-one framing forever after).
                try:
                    self._sock.close()
                except OSError:
                    pass
                raise
            except (ControlStoreConnectionError, OSError):
                if self._closed or attempt == attempts - 1:
                    raise
                time.sleep(delay)
                delay = min(delay * 2, 1.0)
                try:
                    self._reconnect_locked()
                except OSError:
                    continue  # daemon not back yet; next attempt re-dials
        raise ControlStoreConnectionError("unreachable")  # pragma: no cover

    def _call(self, op: int, body: bytes = b"",
              retryable: bool = True) -> _FrameReader:
        frame = bytes([op]) + body
        t0 = time.perf_counter()
        with self._lock:
            reply = self._roundtrip_locked(frame, retryable)
        _event_stats.record(f"control_store.{_OP_NAMES.get(op, op)}",
                            time.perf_counter() - t0)
        r = _FrameReader(reply)
        status = r.u8()
        if status == ST_ERR:
            raise ControlStoreError(r.bytes_().decode("utf-8", "replace"))
        r.status = status  # type: ignore[attr-defined]
        return r

    # -- KV ---------------------------------------------------------------
    def kv_put(self, key: bytes, value: bytes, namespace: str = "default",
               overwrite: bool = True) -> bool:
        r = self._call(OP_KV_PUT, _pack_bytes(namespace.encode()) +
                       _pack_bytes(key) + _pack_bytes(value) +
                       bytes([1 if overwrite else 0]))
        return r.u8() == 1

    def kv_get(self, key: bytes, namespace: str = "default"
               ) -> Optional[bytes]:
        r = self._call(OP_KV_GET, _pack_bytes(namespace.encode()) +
                       _pack_bytes(key))
        if r.status == ST_NIL:  # type: ignore[attr-defined]
            return None
        return r.bytes_()

    def kv_del(self, key: bytes, namespace: str = "default") -> bool:
        r = self._call(OP_KV_DEL, _pack_bytes(namespace.encode()) +
                       _pack_bytes(key))
        return r.u8() == 1

    def kv_keys(self, prefix: bytes = b"", namespace: str = "default"
                ) -> List[bytes]:
        r = self._call(OP_KV_KEYS, _pack_bytes(namespace.encode()) +
                       _pack_bytes(prefix))
        return [r.bytes_() for _ in range(r.u32())]

    # -- node table -------------------------------------------------------
    def register_node(self, node_id: bytes, info: bytes = b"") -> None:
        self._call(OP_NODE_REGISTER, _pack_bytes(node_id) +
                   _pack_bytes(info))

    def heartbeat(self, node_id: bytes) -> None:
        self._call(OP_NODE_HEARTBEAT, _pack_bytes(node_id))

    def list_nodes(self) -> List[Dict[str, Any]]:
        r = self._call(OP_NODE_LIST)
        out = []
        for _ in range(r.u32()):
            node_id = r.bytes_()
            alive = r.u8() == 1
            age = r.f64()
            info = r.bytes_()
            out.append({"node_id": node_id, "alive": alive,
                        "heartbeat_age_s": age, "info": info})
        return out

    def mark_node_dead(self, node_id: bytes) -> bool:
        r = self._call(OP_NODE_MARK_DEAD, _pack_bytes(node_id))
        return r.u8() == 1

    # -- control-plane tables (reference: gcs_table_storage.h) ------------
    def table_put(self, table: str, key: bytes, value: bytes,
                  retryable: bool = True) -> None:
        # retryable=False for callers holding hot locks (the GCS
        # write-through): one failed write degrades durability and is
        # logged; burning the full backoff budget under the lock would
        # stall every control-plane mutation behind it.
        self._call(OP_TABLE_PUT, _pack_bytes(table.encode()) +
                   _pack_bytes(key) + _pack_bytes(value),
                   retryable=retryable)

    def table_del(self, table: str, key: bytes,
                  retryable: bool = True) -> bool:
        r = self._call(OP_TABLE_DEL, _pack_bytes(table.encode()) +
                       _pack_bytes(key), retryable=retryable)
        return r.u8() == 1

    def table_scan(self, table: str) -> List[Tuple[bytes, bytes]]:
        """Full dump of one table: [(key, value), ...] — the head
        recovery path reloads each FSM table in one round trip."""
        r = self._call(OP_TABLE_SCAN, _pack_bytes(table.encode()))
        return [(r.bytes_(), r.bytes_()) for _ in range(r.u32())]

    # -- pubsub -----------------------------------------------------------
    def publish(self, channel: str, payload: bytes) -> int:
        # NOT retryable: the daemon may have fanned the message out
        # before the connection died — a re-send would deliver twice.
        # Callers (_NativePubsub.publish) degrade to local fan-out.
        r = self._call(OP_PUBLISH, _pack_bytes(channel.encode()) +
                       _pack_bytes(payload), retryable=False)
        return r.u32()

    def subscribe(self, channel: str,
                  callback: Callable[[bytes], None]) -> Callable[[], None]:
        """Push-based subscription on a dedicated connection."""
        if self._sub_client is None:
            self._sub_client = _Subscriber(self.address)
        return self._sub_client.subscribe(channel, callback)

    # -- control ----------------------------------------------------------
    def start_health_check(self, period_s: float, timeout_beats: int) -> None:
        self._call(OP_HEALTH_START, struct.pack("<d", period_s) +
                   struct.pack("<I", timeout_beats))

    def stats(self) -> Dict[str, int]:
        r = self._call(OP_STATS)
        return {"nodes": r.u32(), "kv_entries": r.u32(),
                "subscriber_channels": r.u32()}

    def ping(self) -> bool:
        self._call(OP_PING)
        return True

    def shutdown_server(self) -> None:
        try:
            self._call(OP_SHUTDOWN)
        except ControlStoreError:
            pass

    def close(self) -> None:
        self._closed = True
        if self._sub_client is not None:
            self._sub_client.close()
            self._sub_client = None
        try:
            self._sock.close()
        except OSError:
            pass


class _Subscriber:
    """Dedicated subscription connection + reader thread.

    On connection loss the reader re-dials (same bounded backoff as the
    request client) and re-issues every channel subscription — a store
    restarted on the same address keeps pushing; only frames published
    during the gap are lost (callers with stronger needs already pair
    pushes with a poll fallback, see gcs.start_health_check)."""

    def __init__(self, address: Tuple[str, int]):
        import queue

        self.address = address
        self._sock = socket.create_connection(address, timeout=10.0)
        # Connect timeout only: push channels are idle for arbitrarily
        # long, and a recv timeout would read as connection loss.
        self._sock.settimeout(None)
        self._sock.setsockopt(socket.IPPROTO_TCP, socket.TCP_NODELAY, 1)
        self._lock = threading.Lock()
        # Serializes SUBSCRIBE sends against the reconnect handshake:
        # a subscribe racing the socket swap would write into a dying
        # socket or lose its ack to the resubscribe loop's inline reads.
        self._conn_lock = threading.Lock()
        self._callbacks: Dict[str, List[Callable[[bytes], None]]] = {}
        self._closed = False
        self._thread: Optional[threading.Thread] = None
        self._acks: "queue.Queue[int]" = queue.Queue()

    def subscribe(self, channel: str,
                  callback: Callable[[bytes], None]) -> Callable[[], None]:
        import queue

        with self._lock:
            first_for_channel = channel not in self._callbacks
            self._callbacks.setdefault(channel, []).append(callback)
        if first_for_channel:
            frame = (bytes([OP_SUBSCRIBE]) +
                     _pack_bytes(channel.encode()))
            with self._conn_lock:  # excludes a mid-flight socket swap
                self._sock.sendall(struct.pack("<I", len(frame)) + frame)
                start_thread = self._thread is None
                if start_thread:
                    # Wait for the daemon's ack before returning — a
                    # publish issued right after subscribe() must observe
                    # the subscription (read inline before the reader
                    # thread exists, via the ack queue afterwards).
                    reply = _recv_frame(self._sock)
                    if reply[0] != ST_OK:
                        raise ControlStoreError("subscribe failed")
                    self._thread = threading.Thread(
                        target=self._read_loop, daemon=True,
                        name="control-store-sub")
                    self._thread.start()
            if not start_thread:
                try:
                    status = self._acks.get(timeout=10.0)
                except queue.Empty:
                    raise ControlStoreError("subscribe ack timeout")
                if status != ST_OK:
                    raise ControlStoreError("subscribe failed")

        def unsubscribe():
            with self._lock:
                try:
                    self._callbacks.get(channel, []).remove(callback)
                except ValueError:
                    pass

        return unsubscribe

    def _read_loop(self) -> None:
        while not self._closed:
            try:
                frame = _recv_frame(self._sock)
            except (ControlStoreError, OSError):
                # Re-dial until the store is back (a replacement store
                # can take seconds: WAL flock wait + replay). One warn
                # per outage; the thread never gives up while the client
                # is open — a permanently-dead reader would silently
                # disable every future push.
                pushes = None
                warned = False
                while pushes is None and not self._closed:
                    pushes = self._reconnect_resubscribe()
                    if pushes is None and not self._closed:
                        if not warned:
                            import logging

                            logging.getLogger(__name__).warning(
                                "control-store subscription connection "
                                "lost; retrying until the store returns")
                            warned = True
                        time.sleep(2.0)
                if pushes is None:
                    return  # closed
                # Dispatch pushes that interleaved with the handshake
                # OUTSIDE _conn_lock (a callback may itself subscribe).
                for push in pushes:
                    self._dispatch(push)
                continue
            self._dispatch(frame)

    def _dispatch(self, frame: bytes) -> None:
        r = _FrameReader(frame)
        kind = r.u8()
        if kind != OP_PUSH:
            self._acks.put(kind)  # ack for a later SUBSCRIBE
            return
        channel = r.bytes_().decode()
        payload = r.bytes_()
        with self._lock:
            cbs = list(self._callbacks.get(channel, ()))
        for cb in cbs:
            try:
                cb(payload)
            except Exception:
                pass  # wrapper callbacks (gcs layer) log + count already

    def _reconnect_resubscribe(self) -> Optional[List[bytes]]:
        """Re-dial the store and re-issue every channel subscription.
        Runs on the reader thread under ``_conn_lock`` (excluding
        concurrent subscribes from the swapping socket). Returns push
        frames that interleaved with the handshake acks — the caller
        dispatches them after the lock drops — or None when the retry
        budget is exhausted.

        Known limit: a subscribe() parked on the ack queue when the
        connection died never gets its ack (this loop re-subscribes the
        channel and consumes the ST_OK inline) — it raises "subscribe
        ack timeout" after 10s even though the subscription IS live on
        the healed connection; re-subscribing then is safe."""
        from .config import config

        attempts = max(1, int(config().gcs_client_retry_attempts))
        delay = max(0.001, config().gcs_client_retry_base_ms / 1000.0)
        with self._conn_lock:
            for _ in range(attempts):
                if self._closed:
                    return None
                time.sleep(delay)
                delay = min(delay * 2, 1.0)
                try:
                    sock = socket.create_connection(self.address,
                                                    timeout=10.0)
                except OSError:
                    continue
                sock.settimeout(None)  # push channels idle indefinitely
                sock.setsockopt(socket.IPPROTO_TCP, socket.TCP_NODELAY, 1)
                old, self._sock = self._sock, sock
                try:
                    old.close()
                except OSError:
                    pass
                with self._lock:
                    channels = list(self._callbacks)
                pushes: List[bytes] = []
                try:
                    for channel in channels:
                        frame = (bytes([OP_SUBSCRIBE]) +
                                 _pack_bytes(channel.encode()))
                        sock.sendall(struct.pack("<I", len(frame)) + frame)
                        # Consume frames until this channel's ack; pushes
                        # for channels re-subscribed just above may
                        # interleave.
                        while True:
                            reply = _recv_frame(sock)
                            if reply[0] == OP_PUSH:
                                pushes.append(reply)
                                continue
                            if reply[0] != ST_OK:
                                raise ControlStoreError(
                                    "resubscribe failed")
                            break
                except (ControlStoreError, OSError):
                    continue  # store flapped again: next attempt
                return pushes
            return None

    def close(self) -> None:
        self._closed = True
        try:
            self._sock.close()
        except OSError:
            pass


def build_native() -> bool:
    """Build the daemon binary unless it is current; True when available."""
    from .._native import ensure_built

    try:
        ensure_built("control_store", "control_store.cc")
    except (OSError, subprocess.SubprocessError):
        return False
    return True


class ControlStoreProcess:
    """Owns a spawned daemon (start, port handshake, stop)."""

    def __init__(self, port: int = 0, host: str = "127.0.0.1",
                 persist_path: Optional[str] = None):
        if not build_native():
            raise ControlStoreError(
                "control_store binary unavailable (g++/make missing?)")
        cmd = [_BINARY, "--port", str(port), "--host", host,
               # Spawned daemons die with the head (daemon-side ppid
               # watch): a SIGKILLed head must not leave an orphan
               # appending to a WAL its replacement is about to replay
               # and reopen.
               "--die-with-parent"]
        if persist_path:
            # Durable mutation log (reference: Redis-backed GCS tables) —
            # a restarted daemon replays KV + node state from it.
            cmd += ["--persist", persist_path]
        self._proc = subprocess.Popen(
            cmd, stdout=subprocess.PIPE, stderr=subprocess.DEVNULL,
            text=True,
        )
        line = self._proc.stdout.readline()
        if not line.startswith("CONTROL_STORE_PORT "):
            self._proc.kill()
            raise ControlStoreError(f"bad startup handshake: {line!r}")
        self.port = int(line.split()[1])
        self.host = host

    @property
    def address(self) -> Tuple[str, int]:
        return (self.host, self.port)

    def client(self) -> ControlStoreClient:
        return ControlStoreClient(self.address)

    def stop(self, timeout: float = 5.0) -> None:
        if self._proc.poll() is None:
            try:
                ControlStoreClient(self.address).shutdown_server()
            except Exception:
                pass
            try:
                self._proc.wait(timeout=timeout)
            except subprocess.TimeoutExpired:
                self._proc.kill()
                self._proc.wait(timeout=timeout)

    def __del__(self):
        try:
            if self._proc.poll() is None:
                self._proc.kill()
        except Exception:
            pass
