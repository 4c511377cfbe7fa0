"""Head failover: the control plane survives a SIGKILLed head.

Reference coverage analog: GCS fault-tolerance tests — the gcs_server
restarts, reloads its tables from storage (``gcs_table_storage.h``), and
``GcsActorManager::ReconstructActor`` re-runs creation for actors whose
workers died while the head was down.

Here each "head" is a driver subprocess running the native control store
on a shared WAL (``control_store_persist_path``). Killing it with
SIGKILL is a real head-host crash: no teardown, workers orphaned, WAL
possibly torn mid-append. The replacement head must re-resolve named
actors, restart them under ``max_restarts``, and complete queued calls.
"""

import os
import signal
import time

import pytest

from ray_tpu.core.gcs_socket import build_native

pytestmark = [
    pytest.mark.chaos,
    pytest.mark.skipif(
        not build_native(), reason="native toolchain unavailable"),
]


# Driver script for the basic failover cycle: creates a named actor and
# a placement group on first run; on every later run resolves the actor
# by name, submits a call (queued while the actor restarts), and reports
# the recovery outcome.
_SRC_BASIC = r"""
import time
import ray_tpu as rt
from ray_tpu.core import runtime as _rtm

rt.init(num_cpus=2)


@rt.remote
class Counter:
    def __init__(self):
        self.n = 0

    def bump(self):
        self.n += 1
        return self.n


try:
    h = rt.get_actor("survivor")
    created = 0
except ValueError:
    h = Counter.options(name="survivor", max_restarts=5).remote()
    rt.placement_group([{"CPU": 1.0}], strategy="PACK", name="pg0")
    created = 1
ref = h.bump.remote()  # queued: the recovered actor is still restarting
v = rt.get(ref, timeout=120)
rep = _rtm.get_head_runtime().recovery_report or {}
print("HEADKILLER_READY value=%d created=%d restarted=%d dead=%d pgs=%d "
      "actor=%s" % (v, created, rep.get("actors_restarted", 0),
                    rep.get("actors_dead", 0), rep.get("pgs_restored", 0),
                    h._actor_id.hex()), flush=True)
while True:
    rt.get(h.bump.remote())
    time.sleep(0.005)
"""


# Driver script for restart exhaustion across failovers: max_restarts=1
# buys exactly ONE head failover; the second replacement head must mark
# the actor DEAD with a typed, explanatory death cause and drop its name.
_SRC_EXHAUST = r"""
import time
import ray_tpu as rt
from ray_tpu.core import runtime as _rtm
from ray_tpu.core.gcs import ActorState

rt.init(num_cpus=2)


@rt.remote
class C:
    def ping(self):
        return "pong"


head = _rtm.get_head_runtime()
try:
    h = rt.get_actor("exhaust_me")
    rt.get(h.ping.remote(), timeout=60)
    print("HEADKILLER_READY value=1 created=0 outcome=alive", flush=True)
    while True:
        rt.get(h.ping.remote())
        time.sleep(0.005)
except ValueError:
    infos = [i for i in head.gcs.actors.values() if i.name == "exhaust_me"]
    if infos:
        info = infos[0]
        dead = int(info.state == ActorState.DEAD)
        cause_ok = int(bool(info.death_cause
                            and "max_restarts" in info.death_cause))
        # A surviving handle (the WAL-durable KV blob) must fail TYPED —
        # refs resolve to ActorDiedError with the cause, not a raise of
        # 'unknown actor' at submit time.
        from ray_tpu.core import serialization as _ser
        typed = 0
        blob = head.gcs.kv_get(b"actor_handle:" + info.actor_id.binary(),
                               "actors")
        if blob is not None:
            h2 = _ser.loads(blob)
            try:
                rt.get(h2.ping.remote(), timeout=30)
            except rt.ActorDiedError as e:
                typed = int(bool(getattr(e, "death_cause", None)
                                 and "max_restarts" in e.death_cause))
            except Exception:
                typed = 0
        print("HEADKILLER_READY value=0 created=0 outcome=dead dead=%d "
              "cause_ok=%d typed=%d" % (dead, cause_ok, typed), flush=True)
        time.sleep(3600)
    else:
        h = C.options(name="exhaust_me", max_restarts=1).remote()
        rt.get(h.ping.remote(), timeout=60)
        print("HEADKILLER_READY value=1 created=1 outcome=created",
              flush=True)
        while True:
            rt.get(h.ping.remote())
            time.sleep(0.005)
"""


def test_head_failover_named_actor_and_queued_call(tmp_path):
    """SIGKILL the head mid-workload; the replacement head (same WAL)
    re-resolves the named actor, restarts it, completes the queued call,
    and reschedules the persisted placement group."""
    from ray_tpu.cluster_utils import HeadKiller

    killer = HeadKiller(str(tmp_path / "gcs.wal"), kill_after_s=0.3,
                        head_src=_SRC_BASIC)
    first = killer.run_cycle()  # creates, then is SIGKILLed mid-workload
    assert first["created"] == 1
    assert first["value"] == 1

    second = killer.run_cycle()  # replacement head on the same WAL
    assert second["created"] == 0, "named actor must re-resolve"
    assert second["actor"] == first["actor"], \
        "recovery must preserve the actor identity"
    assert second["restarted"] == 1, second
    # State is rebuilt by re-running the creation (standard max_restarts
    # semantics): the counter starts fresh and the queued call completes.
    assert second["value"] == 1
    assert second["pgs"] == 1, "persisted placement group must reschedule"
    assert len(killer.killed) == 2


def test_head_failover_chaos_loop(tmp_path):
    """Chaos loop: kill the head every cycle; every replacement recovers
    the SAME actor with sane recovery latency samples."""
    from ray_tpu.cluster_utils import HeadKiller

    killer = HeadKiller(str(tmp_path / "gcs.wal"), kill_after_s=0.2)
    samples = killer.run(cycles=3)
    recoveries = [s for s in samples if not s["created"]]
    assert len(recoveries) == 2
    actor_ids = {s["actor"] for s in samples}
    assert len(actor_ids) == 1, "one identity across every failover"
    for s in recoveries:
        assert s["restarted"] == 1, s
        assert s["recover_ms"] > 0
        assert s["total_ms"] >= s["recover_ms"]


def test_head_failover_restart_exhaustion_typed_death(tmp_path):
    """max_restarts=1 buys exactly one failover; the second replacement
    head marks the actor DEAD with an explanatory death cause and the
    name stops resolving."""
    from ray_tpu.cluster_utils import HeadKiller

    killer = HeadKiller(str(tmp_path / "gcs.wal"), kill_after_s=0.2,
                        head_src=_SRC_EXHAUST)
    first = killer.run_cycle()
    assert first["outcome"] == "created"
    second = killer.run_cycle()  # consumes the single allowed restart
    assert second["outcome"] == "alive"
    third = killer.run_cycle()
    assert third["outcome"] == "dead", third
    assert third["dead"] == 1
    assert third["cause_ok"] == 1, \
        "death_cause must name the exhausted max_restarts"
    assert third["typed"] == 1, \
        "a surviving handle must fail with a typed ActorDiedError"
    # The tombstone must keep working across FURTHER failovers: the
    # restored DEAD record still routes handle submits to the typed
    # dead-actor path with the persisted cause.
    fourth = killer.run_cycle()
    assert fourth["outcome"] == "dead", fourth
    assert fourth["typed"] == 1, \
        "typed death_cause must survive repeated failovers"


def test_actor_died_error_carries_death_cause(rt_init):
    """Satellite: pending callers of a dead actor get a TYPED
    ActorDiedError whose death_cause explains the death (not a generic
    failure)."""
    import ray_tpu as rt

    @rt.remote(max_restarts=0)
    class B:
        def pid(self):
            return os.getpid()

        def slow(self):
            time.sleep(30)
            return 1

    b = B.remote()
    pid = rt.get(b.pid.remote())
    ref = b.slow.remote()  # in-flight when the worker dies
    time.sleep(0.3)
    os.kill(pid, signal.SIGKILL)
    with pytest.raises(rt.ActorDiedError) as ei:
        rt.get(ref, timeout=30)
    assert ei.value.death_cause == "worker died"
    # Subsequent submissions surface the recorded cause too.
    with pytest.raises(rt.ActorDiedError) as ei2:
        rt.get(b.pid.remote(), timeout=30)
    assert ei2.value.death_cause and "worker died" in ei2.value.death_cause


def test_max_restarts_exhaustion_death_cause(rt_init):
    """Satellite: exhausting max_restarts names the budget in the death
    cause surfaced to callers."""
    import ray_tpu as rt

    @rt.remote(max_restarts=1, max_task_retries=1)
    class B:
        def pid(self):
            return os.getpid()

        def slow(self):
            time.sleep(30)
            return 1

    b = B.remote()
    pid1 = rt.get(b.pid.remote())
    os.kill(pid1, signal.SIGKILL)
    # Wait for the restart to complete (calls retry/buffer meanwhile).
    deadline = time.monotonic() + 60
    pid2 = pid1
    while pid2 == pid1 and time.monotonic() < deadline:
        pid2 = rt.get(b.pid.remote(), timeout=60)
    assert pid2 != pid1
    ref = b.slow.remote()
    time.sleep(0.3)
    os.kill(pid2, signal.SIGKILL)  # second death: budget exhausted
    with pytest.raises(rt.ActorDiedError) as ei:
        rt.get(ref, timeout=30)
    assert ei.value.death_cause == "worker died (max_restarts=1 exhausted)"


def test_pubsub_callback_errors_logged_and_counted(caplog):
    """Satellite: a raising subscriber callback is no longer swallowed —
    it logs at warning and bumps rt_pubsub_callback_errors."""
    import logging

    from ray_tpu.core.gcs import Pubsub
    from ray_tpu.observability.metrics import registry

    ps = Pubsub()
    ps.subscribe("CHAOS", lambda msg: 1 / 0)
    before = 0.0
    ctr = registry.get("rt_pubsub_callback_errors")
    if ctr is not None:
        before = sum(ctr.collect()[1].values())
    with caplog.at_level(logging.WARNING, logger="ray_tpu.core.gcs"):
        ps.publish("CHAOS", "boom")
    assert any("pubsub subscriber callback failed" in r.message
               for r in caplog.records)
    ctr = registry.get("rt_pubsub_callback_errors")
    assert ctr is not None
    assert sum(ctr.collect()[1].values()) == before + 1


@pytest.mark.slow
def test_daemon_rejoins_replacement_head(tmp_path):
    """A node daemon that outlives its head re-dials the fixed cluster
    port and is adopted by the replacement head as fresh capacity."""
    import socket
    import subprocess
    import sys

    s = socket.socket()
    s.bind(("127.0.0.1", 0))
    port = s.getsockname()[1]
    s.close()

    src_a = (
        "import time\n"
        "import ray_tpu as rt\n"
        "rt.init(num_cpus=2)\n"
        "print('HEAD_A_READY', flush=True)\n"
        "while True:\n"
        "    time.sleep(0.5)\n"
    )
    src_b = (
        "import time\n"
        "import ray_tpu as rt\n"
        "from ray_tpu.core import runtime as _rtm\n"
        "rt.init(num_cpus=2)\n"
        "head = _rtm.get_head_runtime()\n"
        "deadline = time.time() + 30\n"
        "n = 1\n"
        "while time.time() < deadline:\n"
        "    n = len(head.scheduler.nodes())\n"
        "    if n >= 2:\n"
        "        break\n"
        "    time.sleep(0.2)\n"
        "print('HEAD_B_NODES %d' % n, flush=True)\n"
        "rt.shutdown()\n"  # daemons get a clean stop (no rejoin loop)
    )
    repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    env = dict(os.environ)
    env.update({
        "RT_NATIVE_CONTROL_STORE": "1",
        "RT_CONTROL_STORE_PERSIST_PATH": str(tmp_path / "gcs.wal"),
        "RT_NODE_DAEMONS": "1",
        "RT_DAEMON_REJOIN_ATTEMPTS": "60",
        "RT_CLUSTER_LISTENER_PORT": str(port),
        "RT_OBJECT_STORE_MEMORY": str(64 * 1024 * 1024),
        "JAX_PLATFORMS": "cpu",
        "PYTHONUNBUFFERED": "1",
        "PYTHONPATH": repo + os.pathsep + env.get("PYTHONPATH", ""),
    })
    a = subprocess.Popen([sys.executable, "-c", src_a], env=env,
                         stdout=subprocess.PIPE,
                         stderr=subprocess.DEVNULL, text=True)
    try:
        for line in a.stdout:
            if line.startswith("HEAD_A_READY"):
                break
        time.sleep(0.5)  # let the daemon settle
    finally:
        a.send_signal(signal.SIGKILL)
        a.wait()
        a.stdout.close()
    out = subprocess.run([sys.executable, "-c", src_b], env=env,
                         capture_output=True, text=True, timeout=120)
    line = next((ln for ln in out.stdout.splitlines()
                 if ln.startswith("HEAD_B_NODES")), None)
    assert line is not None, out.stdout[-500:]
    assert int(line.split()[1]) >= 2, \
        f"surviving daemon did not rejoin: {line}"
