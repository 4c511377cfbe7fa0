"""Actor fault-tolerance tests (fresh runtime per test).

Mirrors reference coverage in ``python/ray/tests/test_actor_failures.py``.
"""

import time

import pytest


def test_kill_actor(rt_init):
    rt = rt_init

    @rt.remote
    class Victim:
        def ping(self):
            return "pong"

    v = Victim.remote()
    assert rt.get(v.ping.remote(), timeout=60) == "pong"
    rt.kill(v)
    with pytest.raises(rt.ActorError):
        rt.get(v.ping.remote(), timeout=15)


def test_actor_restart(rt_init):
    rt = rt_init

    @rt.remote(max_restarts=1)
    class Phoenix:
        def __init__(self):
            self.n = 0

        def incr(self):
            self.n += 1
            return self.n

        def die(self):
            import os

            os._exit(1)

    p = Phoenix.remote()
    assert rt.get(p.incr.remote(), timeout=60) == 1
    p.die.remote()
    # After restart state is fresh (recovered via user checkpointing if
    # needed, like the reference).
    deadline = time.time() + 30
    while time.time() < deadline:
        try:
            result = rt.get(p.incr.remote(), timeout=10)
            break
        except rt.ActorError:
            time.sleep(0.2)
    else:
        pytest.fail("actor did not restart")
    assert result == 1


def test_actor_no_restart_fails_calls(rt_init):
    rt = rt_init

    @rt.remote
    class Mortal:
        def die(self):
            import os

            os._exit(1)

        def ping(self):
            return "pong"

    m = Mortal.remote()
    assert rt.get(m.ping.remote(), timeout=60) == "pong"
    m.die.remote()
    with pytest.raises(rt.ActorError):
        rt.get(m.ping.remote(), timeout=15)

