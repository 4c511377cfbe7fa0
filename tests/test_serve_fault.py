"""Serve fault-tolerance tests (ISSUE 18): replica SIGKILL mid-request
(transparent safe retry), streaming death past the first chunk (typed
fail-fast), hung-replica health detection + replacement, cluster-wide
admission shedding (typed 503), end-to-end deadlines (typed 504), and
the phantom-queue-depth regression on replica eviction."""

import http.client
import inspect
import json
import os
import select
import signal
import socket
import time
import urllib.error
import urllib.request

import pytest


@pytest.fixture()
def serve_instance(rt_shared):
    from ray_tpu import serve

    serve.start(http_port=18311)
    yield serve
    serve.shutdown()


def test_replica_death_mid_request_is_retried(serve_instance):
    """SIGKILL the replica while a request is in flight on it: the
    router re-dispatches to the surviving replica and the ORIGINAL ref
    resolves — the caller never sees the death."""
    serve = serve_instance
    from ray_tpu.core import get

    @serve.deployment(name="retryme", num_replicas=2,
                      health_check_period_s=0.2,
                      health_check_timeout_s=1.0,
                      health_check_failure_threshold=2)
    def who(_=None):
        import os as _os
        import time as _time

        _time.sleep(0.4)
        return _os.getpid()

    handle = serve.run(who.bind())
    # Sticky routing: the warm call's pid is the replica the next
    # request will land on while its load is within the slack.
    victim_pid = get(handle.remote(), timeout=30)
    ref = handle.remote()
    time.sleep(0.15)  # in flight on the victim (handler sleeps 0.4s)
    os.kill(victim_pid, signal.SIGKILL)
    got = get(ref, timeout=30)
    assert isinstance(got, int)
    assert got != victim_pid  # served by the survivor, original ref


def test_stream_death_after_first_chunk_is_typed_not_retried(
        serve_instance):
    """Replica death AFTER the stream started: delivered chunks cannot
    be replayed safely, so the consumer gets the typed
    StreamInterruptedError instead of a silent retry or a hang."""
    serve = serve_instance
    from ray_tpu.core.exceptions import StreamInterruptedError

    @serve.deployment(name="streamer", num_replicas=1)
    def streamer(n=20):
        import os as _os
        import time as _time

        count = int(n) if not isinstance(n, dict) else 20

        def gen():
            yield _os.getpid()
            for i in range(count):
                _time.sleep(0.1)
                yield i

        return gen()

    handle = serve.run(streamer.bind())
    it = iter(handle.stream(20))
    pid = next(it)
    assert isinstance(pid, int)
    os.kill(pid, signal.SIGKILL)
    with pytest.raises(StreamInterruptedError):
        for _ in it:
            pass


@pytest.mark.slow
def test_hung_replica_detected_and_replaced(serve_instance):
    """A replica whose event loop is wedged (not dead — probes just
    never answer) is detected by the controller's health sweep, killed,
    and replaced via reconciliation. idempotent=False: the wedged
    request fails fast with the actor-death error, no retry."""
    serve = serve_instance
    from ray_tpu.core import get
    from ray_tpu.core.exceptions import (ActorError, TaskError,
                                         WorkerCrashedError)

    @serve.deployment(name="hangy", num_replicas=1, idempotent=False,
                      health_check_period_s=0.2,
                      health_check_timeout_s=0.5,
                      health_check_failure_threshold=2)
    async def hangy(payload=None):
        import os as _os
        import time as _time

        if payload == "hang":
            _time.sleep(6.0)  # BLOCKS the loop: hung, not merely busy
        return _os.getpid()

    handle = serve.run(hangy.bind())
    pid0 = get(handle.remote(), timeout=30)
    time.sleep(0.8)  # a few healthy probe rounds end the warmup grace
    ref = handle.remote("hang")
    with pytest.raises((ActorError, WorkerCrashedError, TaskError)):
        get(ref, timeout=30)
    deadline = time.monotonic() + 30
    new_pid = None
    while time.monotonic() < deadline:
        try:
            new_pid = get(handle.remote(), timeout=10)
            if new_pid != pid0:
                break
        except Exception:  # noqa: BLE001 — replacement window
            pass
        time.sleep(0.2)
    assert new_pid is not None and new_pid != pid0


def test_max_pending_sheds_typed_503(serve_instance, tmp_path):
    """A non-LLM deployment with max_pending sheds a burst as typed
    503s (body carries the overloaded flag) while admitted requests
    still complete — cluster-wide admission, not an engine special."""
    serve = serve_instance

    # The handler holds the replica until the test opens the gate, and
    # queue_timeout_s is long, so only max_pending can shed. What fills the
    # queue: the proxy's router takes DeploymentHandle's default of
    # requests in flight to a replica (not the deployment's
    # max_concurrent_queries), so that many plus twelve are sent before the
    # gate opens: beyond those in flight, one batch waits for a slot,
    # max_pending=2 queue behind it and the rest are shed, however slowly
    # a loaded box sends them (sent in one instant, the coalescer sheds
    # more: its queue is at the bound before its drainer runs).
    gate = tmp_path / "gate"
    gate.touch()

    @serve.deployment(name="busy", num_replicas=1,
                      max_concurrent_queries=1, max_pending=2,
                      queue_timeout_s=60)
    def busy(_=None):
        import time as _time

        deadline = _time.monotonic() + 60
        while not gate.exists() and _time.monotonic() < deadline:
            _time.sleep(0.01)
        return {"ok": True}

    serve.run(busy.bind())
    # One sequential warm request: proves the deployment serves 200s
    # and primes the proxy router's deployment cfg.
    with urllib.request.urlopen("http://127.0.0.1:18311/busy",
                                timeout=30) as resp:
        assert resp.status == 200
    gate.unlink()
    in_flight = inspect.signature(serve.api.DeploymentHandle).parameters[
        "max_concurrent_queries"].default
    socks = []
    try:
        for _ in range(in_flight + 12):
            sock = socket.create_connection(("127.0.0.1", 18311), timeout=90)
            sock.sendall(b"GET /busy HTTP/1.1\r\nHost: test\r\n\r\n")
            socks.append(sock)
        # No handler returns before the gate opens: the first answer is a
        # shed one (or, with nothing shed, none comes and the test fails).
        select.select(socks, [], [], 30)
        gate.touch()
        results = []
        for sock in socks:
            resp = http.client.HTTPResponse(sock)
            resp.begin()
            results.append((resp.status, resp.read()))
    finally:
        for sock in socks:
            sock.close()
    statuses = [s for s, _ in results]
    assert set(statuses) <= {200, 503}, statuses
    assert statuses.count(503) >= 1 and statuses.count(200) >= 1, statuses
    for status, body in results:
        payload = json.loads(body)
        if status == 503:
            assert payload.get("overloaded") is True
            assert "overloaded" in payload["error"].lower()
        else:
            assert payload == {"ok": True}


def test_overloaded_error_is_one_shared_type():
    """The LLM engine's shed error IS core.exceptions.OverloadedError —
    one class, isinstance-matched by the proxy, no string matching."""
    from ray_tpu.core.exceptions import OverloadedError as core_exc
    from ray_tpu.llm.paged import OverloadedError as paged_exc

    assert paged_exc is core_exc


def test_request_deadline_typed_and_timely(serve_instance):
    """request_deadline_s bounds the request end-to-end: the handle
    path raises the typed DeadlineExceededError and HTTP returns 504 —
    both long before the handler's 60 s sleep would finish (the bound of
    20 s is far above what a loaded host adds to a 0.6 s deadline, and
    far below the handler: it says which of the two ended the request,
    whatever the box's load)."""
    serve = serve_instance
    from ray_tpu.core import get
    from ray_tpu.core.exceptions import DeadlineExceededError, TaskError

    @serve.deployment(name="slowpoke", num_replicas=1,
                      request_deadline_s=0.6)
    async def slowpoke(_=None):
        import asyncio as _asyncio

        await _asyncio.sleep(60.0)
        return {"ok": True}

    handle = serve.run(slowpoke.bind())
    t0 = time.monotonic()
    with pytest.raises((DeadlineExceededError, TaskError)) as ei:
        get(handle.remote(), timeout=30)
    assert time.monotonic() - t0 < 20  # the 0.6 s deadline, not the 60 s
    root = ei.value
    while isinstance(root, TaskError) and root.cause is not None:
        root = root.cause
    assert isinstance(root, DeadlineExceededError)

    t0 = time.monotonic()
    with pytest.raises(urllib.error.HTTPError) as hei:
        urllib.request.urlopen("http://127.0.0.1:18311/slowpoke",
                               timeout=30)
    assert hei.value.code == 504
    body = json.loads(hei.value.read())
    assert body.get("deadline_exceeded") is True
    assert time.monotonic() - t0 < 20

    # Per-request deadline via header beats the deployment default.
    req = urllib.request.Request("http://127.0.0.1:18311/slowpoke",
                                 headers={"x-serve-deadline-s": "0.15"})
    t0 = time.monotonic()
    with pytest.raises(urllib.error.HTTPError) as hei:
        urllib.request.urlopen(req, timeout=30)
    assert hei.value.code == 504
    assert time.monotonic() - t0 < 20


def test_evicted_replica_releases_queue_depth(serve_instance):
    """Phantom-queue-depth regression: a replica leaving the set while
    charged with in-flight requests must give its residual back to the
    router and deployment-wide totals; a late release must not
    double-subtract."""
    serve = serve_instance
    from ray_tpu.core import get
    from ray_tpu.serve import _internal

    @serve.deployment(name="qd", num_replicas=1)
    def qd(_=None):
        return 1

    handle = serve.run(qd.bind())
    assert get(handle.remote(), timeout=30) == 1
    router = handle._router
    with router._slot_free:
        picked = router._pick_slot_locked()
        assert picked is not None
        _, key = picked
    assert router.stats()["queue_depth"] == 1
    with _internal._qd_lock:
        assert _internal._qd_totals.get("qd", 0) == 1
    with router._slot_free:
        router._set_replicas_locked([])  # eviction while charged
    assert router.stats()["queue_depth"] == 0
    with _internal._qd_lock:
        assert _internal._qd_totals.get("qd", 0) == 0
    router._release(key)  # late completion: must no-op, not go negative
    assert router.stats()["queue_depth"] == 0
    with _internal._qd_lock:
        assert _internal._qd_totals.get("qd", 0) == 0
