"""ctypes bindings for the native shared-memory arena store.

The C++ store (``shm_store.cc``) is the plasma-equivalent data plane; this
module builds it on first use (g++, cached in ``build/``) and exposes
:class:`NativeStore`. Callers fall back to the pure-Python per-object
segment store when the toolchain is unavailable.
"""

from __future__ import annotations

import ctypes
import fcntl
import hashlib
import os
import subprocess
import threading
import weakref
from typing import Optional

import numpy as _np

_HERE = os.path.dirname(os.path.abspath(__file__))
_BUILD = os.path.join(_HERE, "build")
_lib = None
_lib_lock = threading.Lock()


def ensure_built(target: str, source: str) -> str:
    """Path of ``build/<target>``, built now unless it was made from
    exactly these bytes of ``source`` and the Makefile. The test is the
    sources' content, never a modification time: ``build/`` is not in
    git, so a copy of the tree or another session can leave a binary
    there that looks newer than sources it was not built from. Raises
    ``OSError`` / ``subprocess.SubprocessError`` when it cannot build."""
    artifact = os.path.join(_BUILD, target)
    stamp = artifact + ".sha256"
    h = hashlib.sha256()
    for name in (source, "Makefile"):
        with open(os.path.join(_HERE, name), "rb") as f:
            h.update(f.read())
    digest = h.hexdigest()

    def fresh() -> bool:
        try:
            with open(stamp) as f:
                return f.read() == digest and os.path.exists(artifact)
        except OSError:
            return False

    if fresh():
        return artifact
    os.makedirs(_BUILD, exist_ok=True)
    with open(os.path.join(_BUILD, ".lock"), "w") as lock:
        # One builder per tree; the others wait here and find it fresh.
        fcntl.flock(lock, fcntl.LOCK_EX)
        if not fresh():
            # -B: make itself goes by modification times.
            subprocess.run(
                ["make", "-C", _HERE, "-B", f"build/{target}"],
                check=True, capture_output=True, timeout=180)
            tmp = f"{stamp}.{os.getpid()}"
            with open(tmp, "w") as f:
                f.write(digest)
            os.replace(tmp, stamp)
    return artifact


def _load_lib():
    global _lib
    with _lib_lock:
        if _lib is not None:
            return _lib
        try:
            path = ensure_built("libshmstore.so", "shm_store.cc")
        except (OSError, subprocess.SubprocessError) as e:
            raise RuntimeError(f"native store build failed: {e}") from e
        lib = ctypes.CDLL(path)
        lib.rt_store_create.restype = ctypes.c_void_p
        lib.rt_store_create.argtypes = [ctypes.c_char_p, ctypes.c_uint64]
        lib.rt_store_attach.restype = ctypes.c_void_p
        lib.rt_store_attach.argtypes = [ctypes.c_char_p]
        lib.rt_store_put.restype = ctypes.c_int
        lib.rt_store_put.argtypes = [
            ctypes.c_void_p, ctypes.c_char_p, ctypes.c_char_p,
            ctypes.c_uint64,
        ]
        lib.rt_store_create_object.restype = ctypes.c_void_p
        lib.rt_store_create_object.argtypes = [
            ctypes.c_void_p, ctypes.c_char_p, ctypes.c_uint64,
            ctypes.POINTER(ctypes.c_int32),
        ]
        lib.rt_store_seal.restype = ctypes.c_int
        lib.rt_store_seal.argtypes = [ctypes.c_void_p, ctypes.c_char_p]
        lib.rt_store_put_frame.restype = ctypes.c_int
        lib.rt_store_put_frame.argtypes = [
            ctypes.c_void_p, ctypes.c_char_p,
            ctypes.c_char_p, ctypes.c_uint64,
            ctypes.POINTER(ctypes.c_void_p),
            ctypes.POINTER(ctypes.c_uint64), ctypes.c_uint32,
        ]
        lib.rt_store_abort.restype = ctypes.c_int
        lib.rt_store_abort.argtypes = [ctypes.c_void_p, ctypes.c_char_p]
        lib.rt_store_get.restype = ctypes.POINTER(ctypes.c_ubyte)
        lib.rt_store_get.argtypes = [
            ctypes.c_void_p, ctypes.c_char_p,
            ctypes.POINTER(ctypes.c_uint64),
        ]
        lib.rt_store_release.restype = ctypes.c_int
        lib.rt_store_release.argtypes = [ctypes.c_void_p, ctypes.c_char_p]
        lib.rt_store_contains.restype = ctypes.c_int
        lib.rt_store_contains.argtypes = [ctypes.c_void_p, ctypes.c_char_p]
        lib.rt_store_delete.restype = ctypes.c_int
        lib.rt_store_delete.argtypes = [ctypes.c_void_p, ctypes.c_char_p]
        lib.rt_store_stats.argtypes = [
            ctypes.c_void_p, ctypes.POINTER(ctypes.c_uint64),
            ctypes.POINTER(ctypes.c_uint64), ctypes.POINTER(ctypes.c_uint64),
        ]
        lib.rt_store_close.argtypes = [ctypes.c_void_p, ctypes.c_int]
        _lib = lib
        return lib


def available() -> bool:
    try:
        _load_lib()
        return True
    except Exception:
        return False


class NativeStoreError(Exception):
    pass


class NativeStoreFull(NativeStoreError):
    pass


class NativeStorePendingDelete(NativeStoreError):
    """Key was deleted while readers still pin the old extent; a new put
    for the same key must wait until the last reader releases."""


class NativeStoreExists(NativeStoreError):
    """Object already SEALED under this key — puts are idempotent, so
    callers usually treat this as success."""


class NativeStoreUnsealed(NativeStoreError):
    """An unsealed reservation exists for this key (a prior writer died
    between create and seal). The owner serializes same-key writes, so
    it may abort() the wedged reservation and retry."""


def _pinned_view(store: "NativeStore", key: bytes, ptr: int,
                 size: int) -> memoryview:
    """Read-only view over a pinned arena extent whose pin is released
    when the LAST derived view is garbage-collected.

    The ctypes array is the buffer exporter: every derived slice —
    including numpy arrays rebuilt from out-of-band pickle buffers —
    keeps it alive through the buffer protocol, and ``weakref.finalize``
    fires the release exactly once when the exporter is collected.
    (A ``__buffer__``-based exporter class would need PEP 688, py3.12+;
    the finalize pin works on every supported interpreter.) Deferred-free
    in the store (``SLOT_PENDING_DELETE``) guarantees the extent is not
    reused while pinned, so zero-copy values safely outlive deletion."""
    arr = (ctypes.c_ubyte * max(size, 1)).from_address(ptr)
    key = bytes(key)
    lib, handle = store._lib, store._handle

    def _release():
        if not store._closed:
            try:
                lib.rt_store_release(handle, key)
            except Exception:
                pass

    weakref.finalize(arr, _release)
    # ctypes exports format "<B"; cast to "B" so consumers (pickle
    # buffer loads, numpy frombuffer) accept it.
    return memoryview(arr).cast("B").toreadonly()[:size]


class NativeStore:
    """One arena per node; create in the node manager, attach in workers."""

    def __init__(self, handle, name: str, owner: bool):
        self._lib = _load_lib()
        self._handle = ctypes.c_void_p(handle)
        self.name = name
        self._owner = owner
        self._closed = False

    @classmethod
    def create(cls, name: str, capacity: int) -> "NativeStore":
        lib = _load_lib()
        handle = lib.rt_store_create(name.encode(), capacity)
        if not handle:
            raise NativeStoreError(f"failed to create shm arena {name!r}")
        return cls(handle, name, owner=True)

    @classmethod
    def attach(cls, name: str) -> "NativeStore":
        lib = _load_lib()
        handle = lib.rt_store_attach(name.encode())
        if not handle:
            raise NativeStoreError(f"failed to attach shm arena {name!r}")
        return cls(handle, name, owner=False)

    def put(self, key: bytes, data: bytes) -> None:
        rc = self._lib.rt_store_put(self._handle, key, data, len(data))
        if rc == -1:
            return  # already sealed: idempotent put
        if rc == -2:
            raise NativeStoreFull("arena full")
        if rc == -3:
            raise NativeStoreError("object table full")
        if rc == -5:
            raise NativeStorePendingDelete(key.hex())
        if rc != 0:
            raise NativeStoreError(f"put failed rc={rc}")

    def get(self, key: bytes) -> Optional[memoryview]:
        """Zero-copy view into the arena; release() when done with it."""
        size = ctypes.c_uint64()
        ptr = self._lib.rt_store_get(self._handle, key, ctypes.byref(size))
        if not ptr:
            return None
        return memoryview(
            ctypes.cast(
                ptr, ctypes.POINTER(ctypes.c_ubyte * size.value)
            ).contents
        )

    def get_pinned(self, key: bytes) -> Optional[memoryview]:
        """Zero-copy READ-ONLY view whose pin is released automatically
        when the last derived view (e.g. a numpy array deserialized out
        of band) is garbage-collected — plasma-client buffer semantics.
        """
        size = ctypes.c_uint64()
        ptr = self._lib.rt_store_get(self._handle, key, ctypes.byref(size))
        if not ptr:
            return None
        addr = ctypes.cast(ptr, ctypes.c_void_p).value
        return _pinned_view(self, key, addr, size.value)

    def create_object(self, key: bytes, size: int) -> memoryview:
        """Reserve an extent and return a WRITABLE view into the arena;
        call seal() after filling it (abort() on failure). This is the
        zero-copy write path (reference: plasma Create/Seal)."""
        err = ctypes.c_int32()
        ptr = self._lib.rt_store_create_object(
            self._handle, key, size, ctypes.byref(err))
        if not ptr:
            if err.value == -2:
                raise NativeStoreFull("arena full")
            if err.value == -3:
                raise NativeStoreError("object table full")
            if err.value == -5:
                raise NativeStorePendingDelete(key.hex())
            if err.value == -1:
                raise NativeStoreExists(key.hex())
            if err.value == -6:
                raise NativeStoreUnsealed(key.hex())
            raise NativeStoreError(f"create_object failed err={err.value}")
        arr = (ctypes.c_ubyte * max(size, 1)).from_address(ptr)
        return memoryview(arr).cast("B")[:size]

    def seal(self, key: bytes) -> None:
        rc = self._lib.rt_store_seal(self._handle, key)
        if rc != 0:
            raise NativeStoreError(f"seal failed rc={rc}")

    def put_frame(self, key: bytes, inband: bytes, buffers) -> None:
        """One-call owner put of a serialized frame (reserve → C-side
        copy with the lock released → seal); layout identical to
        ``serialization.SerializedObject.write_into`` (the C side owns
        the only other copy of the offset math — callers wanting the
        frame size use ``SerializedObject.frame_bytes()``). ``buffers``
        is a sequence of PickleBuffers. Raises the same exceptions as
        create_object."""
        n = len(buffers)
        ptrs = (ctypes.c_void_p * n)()
        lens = (ctypes.c_uint64 * n)()
        raws = []  # keep buffer views alive across the C call
        for i, b in enumerate(buffers):
            raw = b.raw()
            # np.frombuffer yields a pointer for read-only exporters
            # too (ctypes.from_buffer insists on writable).
            arr = _np.frombuffer(raw, dtype=_np.uint8)
            raws.append((raw, arr))
            ptrs[i] = arr.ctypes.data
            lens[i] = raw.nbytes
        rc = self._lib.rt_store_put_frame(
            self._handle, key, inband, len(inband), ptrs, lens, n)
        if rc == 0:
            return
        if rc == -1:
            raise NativeStoreExists(key.hex())
        if rc == -2:
            raise NativeStoreFull("arena full")
        if rc == -3:
            raise NativeStoreError("object table full")
        if rc == -5:
            raise NativeStorePendingDelete(key.hex())
        if rc == -6:
            raise NativeStoreUnsealed(key.hex())
        raise NativeStoreError(f"put_frame failed rc={rc}")

    def abort(self, key: bytes) -> None:
        self._lib.rt_store_abort(self._handle, key)

    def release(self, key: bytes) -> None:
        self._lib.rt_store_release(self._handle, key)

    def contains(self, key: bytes) -> bool:
        return bool(self._lib.rt_store_contains(self._handle, key))

    def delete(self, key: bytes) -> bool:
        """True when the object existed. The extent free may be deferred
        until the last pinned reader releases (rc 1); either way the key
        stops being gettable immediately."""
        return self._lib.rt_store_delete(self._handle, key) >= 0

    def stats(self) -> dict:
        cap = ctypes.c_uint64()
        used = ctypes.c_uint64()
        n = ctypes.c_uint64()
        self._lib.rt_store_stats(self._handle, ctypes.byref(cap),
                                 ctypes.byref(used), ctypes.byref(n))
        return {"capacity_bytes": cap.value, "used_bytes": used.value,
                "num_objects": n.value}

    def close(self, unlink: Optional[bool] = None) -> None:
        if self._closed:
            return
        self._closed = True
        self._lib.rt_store_close(
            self._handle, int(self._owner if unlink is None else unlink)
        )

    def __del__(self):
        try:
            self.close(unlink=False)
        except Exception:
            pass
