"""Robust shm mutex: a worker killed inside the arena's critical section
must not wedge the node.

The arena is guarded by a PTHREAD_MUTEX_ROBUST process-shared mutex; a
client that dies holding it hands EOWNERDEAD to the next locker, which
repairs the allocator (rebuilds the free list from the object table,
tombstones torn slots) before marking the mutex consistent. Reference
concern: plasma's server-mediated design never exposes clients to each
other's locks (``plasma/store.h:55``); the direct-mapped arena must earn
that same safety.
"""

import ctypes
import multiprocessing
import os

import pytest

from ray_tpu._native import NativeStore, _load_lib

_MP = multiprocessing.get_context("spawn")


def _die_holding_lock(name: str) -> None:
    from ray_tpu._native import NativeStore, _load_lib

    store = NativeStore.attach(name)
    lib = _load_lib()
    lib.rt_store_test_lock_hold.argtypes = [ctypes.c_void_p]
    lib.rt_store_test_lock_hold.restype = ctypes.c_int32
    assert lib.rt_store_test_lock_hold(store._handle) == 0
    os._exit(0)  # exit while holding the mutex


def _die_mid_alloc(name: str) -> None:
    from ray_tpu._native import NativeStore, _load_lib

    store = NativeStore.attach(name)
    lib = _load_lib()
    lib.rt_store_test_die_mid_alloc.argtypes = [
        ctypes.c_void_p, ctypes.c_char_p]
    lib.rt_store_test_die_mid_alloc.restype = ctypes.c_int32
    assert lib.rt_store_test_die_mid_alloc(
        store._handle, b"tornslot" + bytes(12)) == 0
    os._exit(0)


def _put_loop_victim(name: str, barrier) -> None:
    """Hammer puts until killed (the chaos scenario from VERDICT r3)."""
    from ray_tpu._native import NativeStore

    store = NativeStore.attach(name)
    barrier.wait(30)
    i = 0
    while True:
        key = b"victim" + i.to_bytes(14, "little")
        try:
            store.put(key, b"v" * 4096)
            store.delete(key)
        except Exception:
            pass
        i += 1


@pytest.fixture
def arena():
    name = f"/rt_test_robust_{os.getpid()}"
    store = NativeStore.create(name, 16 * 1024 * 1024)
    yield name, store
    store.close(unlink=True)


def test_dead_lock_holder_does_not_wedge(arena):
    name, store = arena
    store.put(b"live-object" + bytes(9), b"x" * 1000)

    p = _MP.Process(target=_die_holding_lock, args=(name,))
    p.start()
    p.join(30)
    assert p.exitcode == 0

    # Next operation takes EOWNERDEAD, repairs, and proceeds.
    store.put(b"after-death" + bytes(9), b"y" * 2000)
    got = store.get(b"live-object" + bytes(9))
    assert got is not None and bytes(got) == b"x" * 1000
    store.release(b"live-object" + bytes(9))
    got = store.get(b"after-death" + bytes(9))
    assert got is not None and bytes(got) == b"y" * 2000
    store.release(b"after-death" + bytes(9))


def test_death_mid_alloc_repairs_allocator(arena):
    name, store = arena
    store.put(b"survivor-obj" + bytes(8), b"s" * 5000)
    used_before = store.stats()["used_bytes"]

    p = _MP.Process(target=_die_mid_alloc, args=(name,))
    p.start()
    p.join(30)
    assert p.exitcode == 0

    # Repair must tombstone the torn slot, rebuild the free list (the
    # test hook dangled free_head), and keep the survivor readable.
    stats = store.stats()
    assert stats["num_objects"] == 1
    assert stats["used_bytes"] == used_before
    got = store.get(b"survivor-obj" + bytes(8))
    assert got is not None and bytes(got) == b"s" * 5000
    store.release(b"survivor-obj" + bytes(8))
    # Allocator is healthy: a put close to remaining capacity succeeds.
    store.put(b"big-after-fix" + bytes(7), b"z" * (8 * 1024 * 1024))
    store.delete(b"big-after-fix" + bytes(7))


def test_sigkill_during_put_loop(arena):
    """End-to-end chaos: SIGKILL a worker mid-put-loop; the node's other
    clients keep making progress."""
    name, store = arena
    barrier = _MP.Barrier(2)
    p = _MP.Process(target=_put_loop_victim, args=(name, barrier))
    p.start()
    barrier.wait(30)
    import time

    for round_i in range(3):
        time.sleep(0.05)
        if round_i == 1:
            p.kill()  # SIGKILL mid-loop (possibly mid-critical-section)
            p.join(30)
        key = f"progress-{round_i}".encode().ljust(20, b"\0")
        store.put(key, b"p" * 10000)
        got = store.get(key)
        assert got is not None and bytes(got) == b"p" * 10000
        store.release(key)
    assert not p.is_alive()
