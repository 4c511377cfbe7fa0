"""Daemon-backed nodes: separate OS processes over loopback TCP.

VERDICT round-1 item 1 criteria: two daemons as real processes (no shared
Python state), tasks/actors/objects/PGs/chaos across them, and a
large object produced on host A gettable from host B via the network
transfer path (forced with RT_FORCE_OBJECT_TRANSFER).
"""

import time

import numpy as np
import pytest

import ray_tpu as rt
from ray_tpu.cluster_utils import Cluster, NodeKiller


@pytest.fixture
def daemon_cluster():
    cluster = Cluster(head_node_args={
        "num_cpus": 2,
        "env": {"RT_FORCE_OBJECT_TRANSFER": "1"},
    })
    ids = [
        cluster.add_node(num_cpus=2, resources={"zone_a": 1.0}, remote=True),
        cluster.add_node(num_cpus=2, resources={"zone_b": 1.0}, remote=True),
    ]
    cluster.wait_for_nodes()
    yield cluster, ids
    cluster.shutdown()


def test_daemons_are_separate_processes(daemon_cluster):
    cluster, (n1, n2) = daemon_cluster
    import os

    node1 = cluster.runtime.scheduler.get_node(n1)
    node2 = cluster.runtime.scheduler.get_node(n2)
    assert node1.is_remote and node2.is_remote
    pids = {node1.process.pid, node2.process.pid}
    assert os.getpid() not in pids and len(pids) == 2
    for pid in pids:
        os.kill(pid, 0)  # raises if not actually running


def test_tasks_actors_across_daemons(daemon_cluster):
    cluster, _ = daemon_cluster

    @rt.remote(resources={"zone_a": 0.1})
    def square(x):
        return x * x

    assert rt.get([square.remote(i) for i in range(8)]) == [
        i * i for i in range(8)]

    @rt.remote(resources={"zone_b": 0.1})
    class Counter:
        def __init__(self):
            self.x = 0

        def add(self, k):
            self.x += k
            return self.x

    c = Counter.remote()
    assert rt.get([c.add.remote(2) for _ in range(5)])[-1] == 10


def test_cross_daemon_object_transfer(daemon_cluster):
    """>max_direct_call object produced on daemon A, consumed on daemon B.
    RT_FORCE_OBJECT_TRANSFER makes workers treat other nodes' arenas as
    unattachable (real multi-host), forcing the chunked TCP pull."""
    cluster, _ = daemon_cluster

    @rt.remote(resources={"zone_a": 0.1})
    def produce(n):
        return np.arange(n, dtype=np.int32)

    @rt.remote(resources={"zone_b": 0.1})
    def consume(arr):
        return int(arr.sum())

    n = 3 * 1024 * 1024 // 4  # ~3MB, multiple chunks at play driver-side
    ref = produce.remote(n)
    assert rt.get(consume.remote(ref)) == n * (n - 1) // 2
    # the driver itself can pull it too (head-side network path)
    assert len(rt.get(ref)) == n


def test_placement_group_across_daemons(daemon_cluster):
    cluster, _ = daemon_cluster
    pg = rt.placement_group([{"CPU": 1}, {"CPU": 1}], strategy="STRICT_SPREAD")
    assert pg.wait(timeout_seconds=30)
    nodes = {nid.hex() for nid in pg.bundle_nodes}
    assert len(nodes) == 2
    rt.remove_placement_group(pg)


@pytest.mark.chaos
def test_daemon_chaos_sigkill_retries():
    """SIGKILL one daemon mid-workload: driver sees EOF, fails the node,
    and retries/reconstructs so the workload still completes."""
    cluster = Cluster(head_node_args={"num_cpus": 2})
    try:
        cluster.add_node(num_cpus=2, remote=True)
        cluster.add_node(num_cpus=2, remote=True)
        cluster.wait_for_nodes()

        @rt.remote(max_retries=4)
        def slow(i):
            time.sleep(0.3)
            return i

        refs = [slow.remote(i) for i in range(16)]
        killer = NodeKiller(cluster, max_kills=1)
        time.sleep(0.5)
        killed = killer.kill_one()
        assert killed is not None
        results = rt.get(refs, timeout=120)
        assert sorted(results) == list(range(16))
    finally:
        cluster.shutdown()


def test_cross_daemon_transfer_is_peer_to_peer(daemon_cluster):
    """Worker-to-worker object pulls must go daemon->daemon through the
    holder's ObjectServer (PullManager), NOT relay through the head —
    the head relay counter stays cold (reference: pull_manager.h:47,
    push_manager.h:29 — raylets transfer directly)."""
    cluster, _ = daemon_cluster
    rtime = cluster.runtime
    base = getattr(rtime, "relay_fetch_count", 0)

    @rt.remote(resources={"zone_a": 0.1})
    def produce(n):
        return np.arange(n, dtype=np.int32)

    @rt.remote(resources={"zone_b": 0.1})
    def consume(arr):
        return int(arr.sum())

    n = 2 * 1024 * 1024 // 4
    total = 0
    refs = [produce.remote(n) for _ in range(3)]
    total = rt.get([consume.remote(r) for r in refs], timeout=120)
    assert total == [n * (n - 1) // 2] * 3
    assert getattr(rtime, "relay_fetch_count", 0) == base, (
        "cross-daemon pull used the head relay instead of P2P")


def test_holder_daemon_killed_mid_pull_recovers_via_lineage():
    """SIGKILL the daemon HOLDING an object while a consumer on another
    daemon pulls it: the pull fails, the object is LOST, and lineage
    reconstruction re-runs the producer so the consumer still finishes."""
    import os
    import signal

    cluster = Cluster(head_node_args={
        "num_cpus": 2,
        "env": {"RT_FORCE_OBJECT_TRANSFER": "1"},
    })
    try:
        holder = cluster.add_node(num_cpus=2, resources={"hold": 1.0},
                                  remote=True)
        cluster.add_node(num_cpus=2, resources={"use": 1.0}, remote=True)
        cluster.wait_for_nodes()

        @rt.remote(resources={"hold": 0.1}, max_retries=4)
        def produce(n):
            return np.ones(n, dtype=np.int64)

        @rt.remote(resources={"use": 0.1}, max_retries=4)
        def consume(arr):
            return int(arr.sum())

        n = 4 * 1024 * 1024 // 8
        ref = produce.remote(n)
        rt.wait([ref], num_returns=1, timeout=60)  # sealed on holder
        out_ref = consume.remote(ref)
        # Kill the holder while the consumer's pull is (likely) in flight.
        holder_node = cluster.runtime.scheduler.get_node(holder)
        os.kill(holder_node.process.pid, signal.SIGKILL)
        # A replacement host joins (elastic recovery); lineage re-runs
        # produce there and the consumer's pull completes.
        cluster.add_node(num_cpus=2, resources={"hold": 1.0}, remote=True)
        assert rt.get(out_ref, timeout=180) == n
    finally:
        cluster.shutdown()


def test_a_holder_whose_connection_is_lost_is_a_lost_copy(rt_init,
                                                          monkeypatch):
    """The head's relay of a cross-host pull reads the holder's store
    through its daemon; when that daemon was SIGKILLed and the head has
    not yet processed the disconnect, the read raises ``ConnectionError``:
    a lost copy, to be recomputed from lineage like any other, not the
    consumer task's error (what failed
    ``test_holder_daemon_killed_mid_pull_recovers_via_lineage`` by turns)."""
    from ray_tpu.core.api import get_runtime

    @rt_init.remote(max_retries=2)
    def produce(n):
        return np.ones(n, dtype=np.int64)

    ref = produce.remote(1 << 18)
    rt.wait([ref], timeout=60)
    runtime = get_runtime()
    real, calls = runtime._store_read_bytes, []

    def dead_once(store, oid):
        calls.append(oid)
        if len(calls) == 1:
            raise ConnectionError("node daemon connection lost")
        return real(store, oid)

    monkeypatch.setattr(runtime, "_store_read_bytes", dead_once)
    frame = runtime._fetch_frame_blocking(ref.id, timeout=60)
    assert len(calls) == 2
    assert int(runtime.serializer.deserialize(frame).sum()) == 1 << 18


def test_a_request_after_the_daemon_died_fails_at_once():
    """A request sent after the daemon's death was seen (its pending
    requests failed, the node not yet removed) is refused at once: the
    first ``sendall`` to a peer that closed succeeds, so the request was
    registered with no reader left to answer it and waited out its 60 s
    (the consumer's ``TimeoutError: node daemon RPC timed out``)."""
    import socket
    import threading

    from ray_tpu.core.node_protocol import FrameConn
    from ray_tpu.core.remote_node import DaemonConn

    with socket.create_server(("127.0.0.1", 0)) as server:  # TCP, as daemons
        ours = socket.create_connection(server.getsockname())
        theirs, _ = server.accept()
    gone = threading.Event()
    conn = DaemonConn(FrameConn(ours), lambda msg: None, gone.set)
    theirs.close()  # the daemon, SIGKILLed
    assert gone.wait(10)
    t0 = time.monotonic()
    with pytest.raises(ConnectionError):
        conn.request(lambda req_id: [("store_get", req_id, b"x")], timeout=5)
    assert time.monotonic() - t0 < 4
