"""Shared-memory object store (plasma-equivalent) + in-process memory store.

Reference analog:
  - ``src/ray/object_manager/plasma/store.h`` — per-node shared-memory store of
    immutable sealed objects, mmap'd zero-copy reads, eviction + spilling.
  - ``src/ray/core_worker/store_provider/memory_store`` — in-process store for
    small/inlined values.

Design: one POSIX shm segment per object (``multiprocessing.shared_memory``),
named ``rt_<object-hex>``. The creating process writes the flattened
``SerializedObject`` frame then "seals" by publishing metadata (size, node) to
the store directory. Readers attach by name and deserialize with zero-copy
views into the segment. Capacity accounting + LRU-ish spill-to-disk when over
the high-water mark (reference: ``LocalObjectManager`` spilling, raylet).

The C++ arena store (``ray_tpu/_native/``) supersedes the per-object-segment
allocator when built; this module is the always-available fallback and the
metadata/ownership layer either way.
"""

from __future__ import annotations

import os
import threading
import time
from dataclasses import dataclass, field
from multiprocessing import shared_memory
from typing import Dict, List, Optional, Set, Tuple

from .config import config
from .exceptions import ObjectLostError, ObjectStoreFullError
from .ids import NodeID, ObjectID
from .serialization import SerializedObject
from ..observability import hotpath as _hotpath

_SEG_PREFIX = "rt_"


def _segment_name(object_id: ObjectID) -> str:
    return _SEG_PREFIX + object_id.hex()


def arena_name_for(node_id_hex: str) -> str:
    return f"/rt_arena_{node_id_hex[:16]}"


def _try_native():
    try:
        from .. import _native

        if _native.available():
            return _native
    except Exception:
        pass
    return None


@dataclass
class ObjectMeta:
    object_id: ObjectID
    size: int
    node_id: NodeID
    sealed: bool = True
    spilled_path: Optional[str] = None
    pinned: int = 0
    last_access: float = field(default_factory=time.monotonic)
    backend: str = "arena"  # arena | segment


class SharedMemoryStore:
    """Node-local store of sealed immutable objects in POSIX shared memory.

    One instance per (simulated) node lives in the node-manager process; worker
    processes use :class:`ShmClient` to create/attach segments directly — the
    store only tracks metadata, capacity, and spilling, like the plasma store
    does for its clients.
    """

    def __init__(self, node_id: NodeID, capacity: Optional[int] = None,
                 spill_dir: Optional[str] = None):
        self.node_id = node_id
        self.capacity = capacity or config().object_store_memory
        self.used = 0
        self._meta: Dict[ObjectID, ObjectMeta] = {}
        self._segments: Dict[ObjectID, shared_memory.SharedMemory] = {}
        self._lock = threading.RLock()
        self._spill_dir = spill_dir or os.path.join(
            os.environ.get("TMPDIR", "/tmp"), f"rt_spill_{node_id.hex()[:8]}"
        )
        # Native arena backend (C++ plasma-equivalent); per-object python
        # shm segments remain the fallback and the spill format.
        self._arena = None
        native = _try_native()
        if native is not None and os.environ.get("RT_DISABLE_NATIVE_STORE") != "1":
            try:
                self._arena = native.NativeStore.create(
                    arena_name_for(node_id.hex()), self.capacity
                )
            except Exception:
                self._arena = None

    @property
    def backend(self) -> str:
        """Which store new objects land in: ``"arena"`` (the native
        store) or ``"segment"`` (the Python one it falls back to when
        the native build is unavailable) — ``ObjectMeta.backend``'s
        vocabulary, so a failed native build can be seen."""
        return "arena" if self._arena is not None else "segment"

    # -- create/seal ---------------------------------------------------------
    def put_serialized(self, object_id: ObjectID, obj: SerializedObject) -> ObjectMeta:
        """Zero-copy put: write the frame (header + inband + out-of-band
        buffers) straight into the arena extent — no intermediate flat
        bytes object (reference: plasma Create/Seal + pickle5 out-of-band
        path in ``python/ray/_private/serialization.py``)."""
        size = obj.frame_bytes()
        with self._lock:
            if object_id in self._meta:
                return self._meta[object_id]
            self._ensure_capacity(size)
            backend = "segment"
            if self._arena is not None:
                self._arena_create_write_seal(object_id, obj, size)
                backend = "arena"
            else:
                seg = shared_memory.SharedMemory(
                    create=True, size=max(size, 1),
                    name=_segment_name(object_id)
                )
                obj.write_into(memoryview(seg.buf)[:size])
                self._segments[object_id] = seg
            meta = ObjectMeta(object_id, size, self.node_id, backend=backend)
            self._meta[object_id] = meta
            self.used += size
            return meta

    def _arena_create_write_seal(self, object_id: ObjectID,
                                 obj: SerializedObject, size: int) -> None:
        """One-call reserve → C-side copy → seal (``put_frame``),
        spilling + retrying on a full arena exactly like the copying
        path. Layout parity with write_into is pinned by tests."""
        from .._native import NativeStoreFull, NativeStoreUnsealed

        key = object_id.binary()

        def attempt() -> bool:
            try:
                try:
                    self._arena.put_frame(key, obj.inband, obj.buffers)
                except NativeStoreUnsealed:
                    # A prior writer died between create and seal; the
                    # owner serializes same-key writes, so reclaim it.
                    self._arena.abort(key)
                    self._arena.put_frame(key, obj.inband, obj.buffers)
            except NativeStoreFull:
                return False
            # Same byte unit as write_into's own count: payload bytes
            # (inband + buffers), not the padded frame size.
            _hotpath.count("copy.serialize.write_into", obj.total_bytes())
            return True

        if attempt():
            return
        for meta in sorted(
                (m for m in self._meta.values()
                 if m.pinned == 0 and m.spilled_path is None
                 and m.backend == "arena" and m.object_id != object_id),
                key=lambda m: m.last_access):
            self._spill(meta)
            if attempt():
                return
        raise ObjectStoreFullError(
            f"arena full putting {size} bytes "
            f"(used {self._used_now()}/{self.capacity})")

    def put_bytes(self, object_id: ObjectID, frame: bytes) -> ObjectMeta:
        size = len(frame)
        with self._lock:
            if object_id in self._meta:
                return self._meta[object_id]
            self._ensure_capacity(size)
            backend = "segment"
            if self._arena is not None:
                self._arena_put_retrying(object_id, frame)
                backend = "arena"
            else:
                seg = shared_memory.SharedMemory(
                    create=True, size=max(size, 1),
                    name=_segment_name(object_id)
                )
                seg.buf[:size] = frame
                self._segments[object_id] = seg
            meta = ObjectMeta(object_id, size, self.node_id, backend=backend)
            self._meta[object_id] = meta
            self.used += size
            return meta

    def register_external(self, object_id: ObjectID, size: int) -> ObjectMeta:
        """Account for an object sealed directly by a worker."""
        with self._lock:
            if object_id in self._meta:
                return self._meta[object_id]
            backend = "segment"
            if self._arena is not None and self._arena.contains(
                    object_id.binary()):
                backend = "arena"
            else:
                try:
                    seg = shared_memory.SharedMemory(
                        name=_segment_name(object_id))
                except FileNotFoundError:
                    raise ObjectLostError(
                        object_id, "worker-sealed object vanished")
                self._segments[object_id] = seg
            meta = ObjectMeta(object_id, size, self.node_id, backend=backend)
            self._meta[object_id] = meta
            self.used += size
            return meta

    # -- read ----------------------------------------------------------------
    def contains(self, object_id: ObjectID) -> bool:
        with self._lock:
            return object_id in self._meta

    def get_buffer(self, object_id: ObjectID) -> memoryview:
        with self._lock:
            meta = self._meta.get(object_id)
            if meta is None:
                raise ObjectLostError(object_id)
            meta.last_access = time.monotonic()
            if meta.spilled_path is not None:
                frame = self._restore(meta)
                if frame is not None:
                    # Old extent still pinned by a stale reader; serve the
                    # spill-file bytes directly (file remains on disk).
                    return memoryview(frame)
            if meta.backend == "arena" and self._arena is not None:
                view = self._arena.get(object_id.binary())
                if view is None:
                    raise ObjectLostError(object_id)
                # Unpin immediately: lifetime is governed by our metadata
                # (delete only runs once refcounts drop, i.e. no readers).
                self._arena.release(object_id.binary())
                return view
            seg = self._segments[object_id]
            return memoryview(seg.buf)[: meta.size]

    def get_pinned(self, object_id: ObjectID) -> memoryview:
        """Zero-copy read for value materialization: a read-only view
        whose arena pin is released when the last derived view (numpy
        arrays deserialized out of band) is garbage-collected. Values
        may safely outlive the object's deletion — deferred-free keeps
        the extent until the last pin drops (plasma client semantics).
        Falls back to spill-file bytes / segment views where pinning
        does not apply."""
        with self._lock:
            meta = self._meta.get(object_id)
            if meta is None:
                raise ObjectLostError(object_id)
            meta.last_access = time.monotonic()
            if meta.spilled_path is not None:
                frame = self._restore(meta)
                if frame is not None:
                    return memoryview(frame)
            if meta.backend == "arena" and self._arena is not None:
                view = self._arena.get_pinned(object_id.binary())
                if view is None:
                    raise ObjectLostError(object_id)
                return view
            seg = self._segments[object_id]
            # read-only: sealed objects are immutable; a writable view
            # would let deserialized numpy values mutate the store.
            return memoryview(seg.buf).toreadonly()[: meta.size]

    def meta(self, object_id: ObjectID) -> Optional[ObjectMeta]:
        with self._lock:
            return self._meta.get(object_id)

    def pin(self, object_id: ObjectID) -> None:
        with self._lock:
            if object_id in self._meta:
                self._meta[object_id].pinned += 1

    def unpin(self, object_id: ObjectID) -> None:
        with self._lock:
            if object_id in self._meta:
                self._meta[object_id].pinned = max(0, self._meta[object_id].pinned - 1)

    # -- delete / spill ------------------------------------------------------
    def delete(self, object_id: ObjectID) -> None:
        with self._lock:
            meta = self._meta.pop(object_id, None)
            if meta is None:
                return
            if meta.backend == "arena" and self._arena is not None:
                if meta.spilled_path is None and self._arena.delete(
                        object_id.binary()):
                    self.used -= meta.size
            else:
                seg = self._segments.pop(object_id, None)
                if seg is not None:
                    try:
                        seg.close()
                        seg.unlink()
                    except FileNotFoundError:
                        pass
                    except BufferError:
                        # A zero-copy view is still exported; unlink the
                        # name but keep the mapping alive for the reader.
                        try:
                            seg.unlink()
                        except FileNotFoundError:
                            pass
                    self.used -= meta.size
            if meta.spilled_path and os.path.exists(meta.spilled_path):
                os.unlink(meta.spilled_path)

    def _used_now(self) -> int:
        """Live occupancy. For the arena backend ask the allocator itself:
        it is the truth for deferred frees (delete-while-pinned) and
        absorbed-sliver padding that logical accounting can't see."""
        if self._arena is not None:
            try:
                return self._arena.stats()["used_bytes"]
            except Exception:
                pass
        return self.used

    def _ensure_capacity(self, need: int) -> None:
        if need > self.capacity:
            raise ObjectStoreFullError(
                f"object of {need} bytes exceeds store capacity {self.capacity}"
            )
        threshold = config().object_spilling_threshold
        # Logical accounting first: one arena.stats() round-trip per put
        # was measurable on the 10MB hot path (the first header access
        # after dirtying a large extent pays a fixed surcharge). The
        # logical figure can only UNDER-count vs the allocator's truth
        # (deferred frees, absorbed slivers), and the under-count is
        # safe: a genuinely full arena still raises NativeStoreFull,
        # which the put paths catch by spilling and retrying.
        if self.used + need <= self.capacity * threshold:
            return
        if self._used_now() + need <= self.capacity * threshold:
            return
        # Spill least-recently-accessed unpinned objects until there is room
        # (reference: LocalObjectManager::SpillObjects, fused to min size).
        candidates = sorted(
            (m for m in self._meta.values()
             if m.pinned == 0 and m.spilled_path is None),
            key=lambda m: m.last_access,
        )
        for meta in candidates:
            if self._used_now() + need <= self.capacity * threshold:
                break
            self._spill(meta)
        if self._used_now() + need > self.capacity:
            raise ObjectStoreFullError(
                f"need {need} bytes; used {self._used_now()}/"
                f"{self.capacity} after spilling"
            )

    def _spill(self, meta: ObjectMeta) -> None:
        os.makedirs(self._spill_dir, exist_ok=True)
        path = os.path.join(self._spill_dir, meta.object_id.hex())
        if meta.backend == "arena" and self._arena is not None:
            view = self._arena.get(meta.object_id.binary())
            if view is None:
                return
            with open(path, "wb") as f:
                f.write(bytes(view))
            self._arena.release(meta.object_id.binary())
            self._arena.delete(meta.object_id.binary())
        else:
            seg = self._segments.pop(meta.object_id)
            with open(path, "wb") as f:
                f.write(bytes(memoryview(seg.buf)[: meta.size]))
            seg.close()
            seg.unlink()
        meta.spilled_path = path
        self.used -= meta.size

    def _arena_put_retrying(self, object_id: ObjectID, frame: bytes) -> None:
        """Arena put that spills harder and retries once when the arena is
        fuller than logical accounting suggested (deferred frees,
        fragmentation), rather than leaking NativeStoreFull to callers."""
        from .._native import NativeStoreFull

        try:
            self._arena.put(object_id.binary(), frame)
            return
        except NativeStoreFull:
            pass
        for meta in sorted(
                (m for m in self._meta.values()
                 if m.pinned == 0 and m.spilled_path is None
                 and m.backend == "arena"
                 and m.object_id != object_id),
                key=lambda m: m.last_access):
            self._spill(meta)
            try:
                self._arena.put(object_id.binary(), frame)
                return
            except NativeStoreFull:
                continue
        raise ObjectStoreFullError(
            f"arena full putting {len(frame)} bytes "
            f"(used {self._used_now()}/{self.capacity})")

    def _restore(self, meta: ObjectMeta) -> bytes | None:
        """Bring a spilled object back. Returns the raw frame when the
        object could NOT be re-admitted to shared memory (its key is
        pending-delete: a stale reader still pins the old extent) — the
        caller serves those bytes directly and the spill file stays as
        the durable copy."""
        from .._native import NativeStorePendingDelete

        path = meta.spilled_path
        assert path is not None
        with open(path, "rb") as f:
            frame = f.read()
        self._ensure_capacity(len(frame))
        if meta.backend == "arena" and self._arena is not None:
            try:
                self._arena.put(meta.object_id.binary(), frame)
            except NativeStorePendingDelete:
                return frame
        else:
            seg = shared_memory.SharedMemory(
                create=True, size=max(len(frame), 1),
                name=_segment_name(meta.object_id),
            )
            seg.buf[: len(frame)] = frame
            self._segments[meta.object_id] = seg
        self.used += meta.size
        meta.spilled_path = None
        os.unlink(path)
        return None

    def destroy(self) -> None:
        """Tear down all segments (node death / shutdown)."""
        with self._lock:
            for oid in list(self._meta):
                self.delete(oid)
            if self._arena is not None:
                try:
                    self._arena.close(unlink=True)
                except Exception:
                    pass
                self._arena = None

    def stats(self) -> dict:
        with self._lock:
            spilled = sum(1 for m in self._meta.values() if m.spilled_path)
            return {
                "num_objects": len(self._meta),
                "used_bytes": self.used,
                "capacity_bytes": self.capacity,
                "num_spilled": spilled,
            }


class ShmClient:
    """Worker-side client: create/attach segments without store round-trips.

    Mirrors the plasma client: ``create`` + write + ``seal`` (here: notify the
    owner over the worker pipe), and attach-by-name for reads. Keeps attached
    segments open so zero-copy views stay valid for the process lifetime.
    """

    def __init__(self, node_id_hex: Optional[str] = None):
        self._attached: Dict[str, shared_memory.SharedMemory] = {}
        self._lock = threading.Lock()
        self._arena = None
        self._arenas: Dict[str, object] = {}  # other nodes' arenas by hex
        self._node_id_hex = node_id_hex
        self._native = None
        if os.environ.get("RT_DISABLE_NATIVE_STORE") != "1":
            self._native = _try_native()
        if node_id_hex and self._native is not None:
            try:
                self._arena = self._native.NativeStore.attach(
                    arena_name_for(node_id_hex)
                )
                self._arenas[node_id_hex] = self._arena
            except Exception:
                self._arena = None

    def create_and_seal(self, object_id: ObjectID, frame: bytes) -> int:
        if self._arena is not None:
            try:
                self._arena.put(object_id.binary(), frame)
                return len(frame)
            except Exception:
                pass  # arena full/unavailable: fall back to a segment
        seg = shared_memory.SharedMemory(
            create=True, size=max(len(frame), 1), name=_segment_name(object_id)
        )
        seg.buf[: len(frame)] = frame
        with self._lock:
            self._attached[_segment_name(object_id)] = seg
        return len(frame)

    def create_and_seal_serialized(self, object_id: ObjectID,
                                   obj: SerializedObject) -> int:
        """Zero-copy seal: write header/inband/out-of-band buffers straight
        into the arena extent (plasma Create/Seal), no flat intermediate."""
        from .._native import NativeStoreExists, NativeStoreUnsealed

        size = obj.frame_bytes()
        if self._arena is not None:
            key = object_id.binary()
            done = False
            try:
                try:
                    self._arena.put_frame(key, obj.inband, obj.buffers)
                    done = True
                except NativeStoreUnsealed:
                    # Prior writer died mid-create; reclaim and retry.
                    self._arena.abort(key)
                    self._arena.put_frame(key, obj.inband, obj.buffers)
                    done = True
            except NativeStoreExists:
                return size  # idempotent re-put
            except Exception:
                done = False  # full/unavailable: fall back below
            if done:
                # Payload bytes, matching write_into's unit.
                _hotpath.count("copy.serialize.write_into",
                               obj.total_bytes())
                return size
        seg = shared_memory.SharedMemory(
            create=True, size=max(size, 1), name=_segment_name(object_id)
        )
        obj.write_into(memoryview(seg.buf)[:size])
        with self._lock:
            self._attached[_segment_name(object_id)] = seg
        return size

    def _arena_for(self, node_hex: Optional[str]):
        if self._native is None:
            return None
        if node_hex is None:
            return self._arena
        arena = self._arenas.get(node_hex)
        if arena is None:
            try:
                arena = self._native.NativeStore.attach(
                    arena_name_for(node_hex))
            except Exception:
                arena = False  # negative-cache
            self._arenas[node_hex] = arena
        return arena or None

    def read(self, object_id: ObjectID, size: int,
             node_hex: Optional[str] = None) -> memoryview:
        # Test hook: pretend cross-node arenas are unattachable (as on a
        # real multi-host cluster) to force the network transfer path.
        if (os.environ.get("RT_FORCE_OBJECT_TRANSFER") == "1"
                and node_hex is not None
                and self._node_id_hex is not None
                and node_hex != self._node_id_hex):
            raise LookupError(
                f"arena {node_hex[:8]} is on another host")
        for arena in (self._arena_for(node_hex), self._arena):
            if arena is not None:
                view = arena.get_pinned(object_id.binary())
                if view is not None:
                    # Pin released when the last derived view (numpy in
                    # user code) is collected; deferred-free protects the
                    # extent meanwhile.
                    return view
        name = _segment_name(object_id)
        with self._lock:
            seg = self._attached.get(name)
            if seg is None:
                seg = shared_memory.SharedMemory(name=name)
                self._attached[name] = seg
        return memoryview(seg.buf)[:size]

    def close(self) -> None:
        with self._lock:
            for seg in self._attached.values():
                try:
                    seg.close()
                except Exception:
                    pass
            self._attached.clear()
        if self._arena is not None:
            try:
                self._arena.close(unlink=False)
            except Exception:
                pass
            self._arena = None


class MemoryStore:
    """In-process store for inlined small objects (memory_store/)."""

    def __init__(self):
        self._values: Dict[ObjectID, Tuple[bytes, tuple]] = {}
        self._used_bytes = 0
        self._lock = threading.Lock()

    def put(self, object_id: ObjectID, frame: bytes) -> None:
        with self._lock:
            prev = self._values.get(object_id)
            if prev is not None:
                self._used_bytes -= len(prev[0])
            self._values[object_id] = (frame, ())
            self._used_bytes += len(frame)

    def get(self, object_id: ObjectID) -> Optional[bytes]:
        with self._lock:
            entry = self._values.get(object_id)
            return entry[0] if entry else None

    def contains(self, object_id: ObjectID) -> bool:
        with self._lock:
            return object_id in self._values

    def delete(self, object_id: ObjectID) -> None:
        with self._lock:
            entry = self._values.pop(object_id, None)
            if entry is not None:
                self._used_bytes -= len(entry[0])

    def size(self) -> int:
        with self._lock:
            return len(self._values)

    def stats(self) -> dict:
        """Same shape as SharedMemoryStore.stats (telemetry gauge feed)."""
        with self._lock:
            return {"num_objects": len(self._values),
                    "used_bytes": self._used_bytes}
