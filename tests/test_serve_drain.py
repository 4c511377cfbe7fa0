"""Graceful drain + sticky-session routing tests (ISSUE 19): a drained
replica finishes its in-flight requests AND handed-off streams before
dying (zero drops, zero drain-caused errors), sessions stay pinned to
one replica and re-pin deterministically when it leaves the set."""

import threading
import time

import pytest

pytestmark = pytest.mark.chaos


@pytest.fixture()
def serve_instance(rt_shared):
    from ray_tpu import serve

    serve.start(http_port=18641)
    yield serve
    serve.shutdown()


def _replica_hexes(name):
    from ray_tpu.core import get
    from ray_tpu.serve.api import _controller

    return [r._actor_id.hex()
            for r in get(_controller().get_replicas.remote(name),
                         timeout=10)]


def test_sticky_session_routing_and_repin(serve_instance):
    """Requests tagged with one session id land on ONE replica; when
    that replica is drained the session re-pins (rendezvous hash) to a
    survivor and keeps being served."""
    serve = serve_instance
    from ray_tpu.core import get

    @serve.deployment(name="pinme", num_replicas=2,
                      health_check_period_s=0.25)
    def pinme(_=None):
        import os as _os

        return _os.getpid()

    handle = serve.run(pinme.bind())
    sess = handle.session("alpha")
    pids = {get(sess.remote(), timeout=30) for _ in range(6)}
    assert len(pids) == 1  # pinned
    pinned_key = handle._router.session_replica("alpha")
    assert pinned_key is not None

    rep = serve.drain("pinme", replica=pinned_key, timeout_s=20.0)
    assert rep.get("error") is None, rep
    # Session must re-pin to a live replica and keep serving.
    pid2 = get(sess.remote(), timeout=30)
    assert pid2 not in pids
    assert handle._router.session_replica("alpha") != pinned_key


def test_drain_completes_streams_not_severed(serve_instance):
    """Regression (satellite): drain must NOT sever in-progress
    streams. A stream being consumed while its replica drains completes
    normally — no StreamInterruptedError — and a replacement replica
    appears."""
    serve = serve_instance

    @serve.deployment(name="drainstream", num_replicas=1,
                      health_check_period_s=0.25)
    def streamer(n=12):
        import os as _os
        import time as _time

        count = int(n) if not isinstance(n, dict) else 12

        def gen():
            yield _os.getpid()
            for i in range(count):
                _time.sleep(0.08)
                yield i

        return gen()

    handle = serve.run(streamer.bind())
    before = set(_replica_hexes("drainstream"))
    assert len(before) == 1
    it = iter(handle.stream(12))
    pid = next(it)  # stream is live on the (sole) replica
    assert isinstance(pid, int)

    drain_result = {}

    def do_drain():
        drain_result.update(
            serve.drain("drainstream", timeout_s=30.0))

    t = threading.Thread(target=do_drain, daemon=True)
    t.start()
    got = list(it)  # must complete, not raise StreamInterruptedError
    t.join(timeout=60)
    assert got == list(range(12))
    assert drain_result.get("error") is None, drain_result
    assert drain_result.get("timed_out") is False, drain_result
    # Reconciliation replaced the drained replica.
    deadline = time.monotonic() + 30
    while time.monotonic() < deadline:
        after = set(_replica_hexes("drainstream"))
        if after and not (after & before):
            break
        time.sleep(0.2)
    assert after and not (after & before)


def test_drain_zero_dropped_requests(serve_instance):
    """Requests in flight on the draining replica (and requests racing
    the drain) all complete — no drops, no drain-caused errors."""
    serve = serve_instance
    from ray_tpu.core import get

    @serve.deployment(name="drainbusy", num_replicas=2,
                      health_check_period_s=0.25)
    def busy(_=None):
        import time as _time

        _time.sleep(0.15)
        return 1

    handle = serve.run(busy.bind())
    assert get(handle.remote(), timeout=30) == 1
    results, errors = [], []
    lock = threading.Lock()

    def call():
        try:
            r = get(handle.remote(), timeout=60)
            with lock:
                results.append(r)
        except Exception as e:  # noqa: BLE001 — counted, not raised
            with lock:
                errors.append(e)

    threads = [threading.Thread(target=call, daemon=True) for _ in range(10)]
    for th in threads:
        th.start()
    time.sleep(0.05)  # let a few land in flight
    rep = serve.drain("drainbusy", timeout_s=30.0)
    for th in threads:
        th.join(timeout=90)
    assert rep.get("error") is None, rep
    assert errors == [], errors
    assert results == [1] * 10
