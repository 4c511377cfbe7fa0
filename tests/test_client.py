"""Ray-Client-equivalent tests: remote driver over the socket proxy.

Reference coverage analog: python/ray/tests/test_client.py — tasks,
actors, put/get/wait, ref passing, error propagation through the proxy.
"""

import pytest


@pytest.fixture(scope="module")
def client(rt_shared_module):
    from ray_tpu.client import ClientServer, connect

    server = ClientServer()
    server.start()
    session = connect(server.address)
    yield session
    session.close()
    server.stop()


@pytest.fixture(scope="module")
def rt_shared_module():
    import ray_tpu as rt

    rt.init(num_cpus=4, ignore_reinit_error=True)
    yield rt
    rt.shutdown()


def test_put_get_roundtrip(client):
    ref = client.put({"a": [1, 2, 3]})
    assert client.get(ref) == {"a": [1, 2, 3]}


def test_remote_function(client):
    @client.remote
    def add(a, b):
        return a + b

    assert client.get(add.remote(2, 40)) == 42


def test_ref_passing_between_tasks(client):
    @client.remote
    def double(x):
        return x * 2

    r1 = double.remote(21)
    r2 = double.remote(r1)  # client ref as arg resolves server-side
    assert client.get(r2) == 84


def test_wait(client, tmp_path):
    import time

    @client.remote
    def fast():
        return 1

    @client.remote
    def slow(release):
        # Held by a file the test owns, not by a sleep raced against
        # wait()'s timeout: pending until the test has seen wait() return.
        deadline = time.monotonic() + 60
        while not release.exists() and time.monotonic() < deadline:
            time.sleep(0.02)
        return 2

    release = tmp_path / "release"
    # ``fast`` goes first: submitted behind ``slow`` it can be pipelined
    # onto slow's worker and wait for it (why ``sleep(5)`` against
    # ``timeout=4`` failed by turns).
    fast_ref = fast.remote()
    refs = [slow.remote(release), fast_ref]
    ready, pending = client.wait(refs, num_returns=1, timeout=20)
    release.touch()
    assert ready == [refs[1]] and pending == [refs[0]]
    assert client.get(ready[0], timeout=20) == 1
    assert client.get(pending[0], timeout=20) == 2


def test_actor_lifecycle(client):
    @client.remote
    class Counter:
        def __init__(self, start):
            self.n = start

        def incr(self, by=1):
            self.n += by
            return self.n

    c = Counter.remote(10)
    assert client.get(c.incr.remote()) == 11
    assert client.get(c.incr.remote(by=5)) == 16
    client.kill(c)


def test_error_propagates(client):
    @client.remote
    def boom():
        raise ValueError("kapow")

    ref = boom.remote()
    with pytest.raises(Exception, match="kapow"):
        client.get(ref)


def test_cluster_info(client):
    info = client.cluster_info()
    assert info["nodes"] >= 1
    assert info["resources"].get("CPU", 0) > 0


def test_two_sessions_isolated(client, rt_shared_module):
    from ray_tpu.client import ClientServer, connect

    server2 = ClientServer()
    server2.start()
    s2 = connect(server2.address)
    try:
        ref = s2.put("second-session")
        assert s2.get(ref) == "second-session"
        # The first session can't see the second's refs.
        from ray_tpu.client.client import ClientObjectRef

        foreign = ClientObjectRef(ref.hex(), client)
        with pytest.raises(Exception):
            client.get(foreign, timeout=2)
    finally:
        s2.close()
        server2.stop()


def test_remote_with_options(client):
    @client.remote(num_cpus=1, max_retries=2)
    def opt_task():
        return "opted"

    assert client.get(opt_task.remote()) == "opted"
