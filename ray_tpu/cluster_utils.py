"""Multi-node-on-one-host test cluster.

Reference analog: ``python/ray/cluster_utils.py:99`` — the central fixture
for testing scheduling, spillback, fault tolerance, and node failure without
real machines: multiple node managers (each with its own worker pool, store,
and resource ledger) share one control store in the head process.
``add_node(**resources)`` / ``remove_node(node)`` drive membership.
"""

from __future__ import annotations

import os
import sys
from typing import Dict, Optional

from .core import runtime as runtime_mod
from .core.ids import NodeID


def chaos_seed(seed: Optional[int] = None) -> int:
    """Resolve a chaos harness's RNG seed: an explicit ``seed`` wins,
    else ``RT_CHAOS_SEED`` from the environment, else 0. Every killer
    logs the resolved value at start so a failing chaos run can be
    replayed bit-for-bit (same seed -> same victim sequence)."""
    if seed is not None:
        return int(seed)
    return int(os.environ.get("RT_CHAOS_SEED", "0") or 0)


def _log_seed(harness: str, seed: int) -> None:
    print("[rt-chaos] %s seed=%d (explicit seed arg or RT_CHAOS_SEED "
          "env replays this run)" % (harness, seed), file=sys.stderr,
          flush=True)


class Cluster:
    def __init__(self, initialize_head: bool = True,
                 head_node_args: Optional[dict] = None):
        self.head_node_id: Optional[NodeID] = None
        self._nodes: list = []
        if initialize_head:
            args = dict(head_node_args or {})
            num_cpus = args.pop("num_cpus", 2)
            self.runtime = runtime_mod.init(num_cpus=num_cpus, **args)
            self.head_node_id = self.runtime.scheduler.nodes()[0].node_id
            self._nodes.append(self.head_node_id)
        else:
            self.runtime = None

    def add_node(self, num_cpus: float = 2, num_tpus: float = 0,
                 resources: Optional[Dict[str, float]] = None,
                 object_store_memory: Optional[int] = None,
                 topology: Optional[dict] = None,
                 labels: Optional[dict] = None,
                 remote: Optional[bool] = None) -> NodeID:
        """``remote=True`` runs the node as a separate OS-process daemon
        (its own worker pool + shm store, attached over TCP) — the
        multi-host path; default in-process node managers simulate
        multi-node cheaply (reference: Cluster.add_node raylets)."""
        node_resources = {"CPU": float(num_cpus)}
        if num_tpus:
            node_resources["TPU"] = float(num_tpus)
        node_resources.update(resources or {})
        node_id = self.runtime.add_node(
            node_resources, object_store_memory=object_store_memory,
            labels=labels, topology=topology, remote=remote,
        )
        self._nodes.append(node_id)
        return node_id

    def remove_node(self, node_id: NodeID) -> None:
        """Simulated node failure: workers killed, store destroyed."""
        self.runtime.remove_node(node_id)
        if node_id in self._nodes:
            self._nodes.remove(node_id)

    def wait_for_nodes(self, timeout: float = 30.0) -> None:
        """Block until every node's worker pool has a registered worker.

        Reference analog: ``Cluster.wait_for_nodes`` — tests that need
        deterministic placement call this after ``add_node``.
        """
        import time

        deadline = time.monotonic() + timeout
        while time.monotonic() < deadline:
            pools_ready = all(
                any(w._registered.is_set() for w in n.pool.all_workers())
                for n in self.runtime.scheduler.nodes()
            )
            if pools_ready:
                return
            time.sleep(0.02)
        raise TimeoutError("worker pools did not become ready")

    def shutdown(self) -> None:
        runtime_mod.shutdown()


class NodeKiller:
    """Chaos fault injector: kills random non-head nodes on a timer.

    Reference analog: ``_private/test_utils.get_and_run_node_killer``'s
    ``NodeKillerActor`` (:1116) driving chaos release tests
    (``release/nightly_tests/chaos_test/``) — workloads must survive
    repeated node loss through lineage reconstruction and retries.
    """

    def __init__(self, cluster: Cluster, kill_interval_s: float = 1.0,
                 max_kills: Optional[int] = None,
                 seed: Optional[int] = None):
        import random
        import threading

        self.cluster = cluster
        self.kill_interval_s = kill_interval_s
        self.max_kills = max_kills
        self.killed: list = []
        self.seed = chaos_seed(seed)
        _log_seed("NodeKiller", self.seed)
        self._rng = random.Random(self.seed)
        self._stop = threading.Event()
        self._thread: Optional[threading.Thread] = None

    def _victims(self) -> list:
        return [nid for nid in self.cluster._nodes
                if nid != self.cluster.head_node_id]

    def kill_one(self) -> Optional[NodeID]:
        """Kill one random non-head node now; returns its id (or None).

        Daemon-backed nodes are SIGKILLed (a real host crash: the driver
        notices via connection EOF, no cooperative teardown); in-process
        nodes go through the simulated removal path.
        """
        victims = self._victims()
        if not victims:
            return None
        node_id = self._rng.choice(victims)
        node = self.cluster.runtime.scheduler.get_node(node_id)
        if node is not None and getattr(node, "is_remote", False):
            try:
                node.process.kill()
            except Exception:
                self.cluster.remove_node(node_id)
            if node_id in self.cluster._nodes:
                self.cluster._nodes.remove(node_id)
        else:
            self.cluster.remove_node(node_id)
        self.killed.append(node_id)
        return node_id

    def run(self) -> None:
        import threading

        def loop():
            while not self._stop.wait(self.kill_interval_s):
                if (self.max_kills is not None
                        and len(self.killed) >= self.max_kills):
                    return
                self.kill_one()

        self._thread = threading.Thread(target=loop, daemon=True,
                                        name="rt-node-killer")
        self._thread.start()

    def stop(self) -> None:
        self._stop.set()
        if self._thread is not None:
            self._thread.join(timeout=2)
            self._thread = None


class ReplicaKiller:
    """Chaos fault injector for the SERVE plane: SIGKILLs a random
    replica worker of one deployment on a timer (sibling of
    :class:`NodeKiller` / :class:`HeadKiller`).

    A replica dies like a real worker crash — no cooperative teardown,
    the head notices via pipe EOF, the controller's health sweep /
    death path evicts it, and target-count reconciliation replaces it.
    Used by ``bench_serve_chaos`` and the fault-tolerance tests to
    prove requests in flight on the victim are retried (or fail with a
    typed error), never hung.
    """

    def __init__(self, deployment: str, kill_interval_s: float = 1.0,
                 max_kills: Optional[int] = None,
                 seed: Optional[int] = None):
        import random
        import threading

        self.deployment = deployment
        self.kill_interval_s = kill_interval_s
        self.max_kills = max_kills
        self.killed: list = []  # (actor_id, pid) per kill
        self.seed = chaos_seed(seed)
        _log_seed("ReplicaKiller", self.seed)
        self._rng = random.Random(self.seed)
        self._stop = threading.Event()
        self._thread: Optional[threading.Thread] = None

    def _replicas(self) -> list:
        from .serve import api as serve_api

        ctrl = serve_api._controller()
        if ctrl is None:
            return []
        rt = runtime_mod.get_head_runtime()
        return rt.get(ctrl.get_replicas.remote(self.deployment),
                      timeout=10)

    def replica_pids(self) -> Dict[bytes, int]:
        """actor_id bytes -> worker pid for the deployment's live
        replicas (skips replicas whose worker is gone already)."""
        rt = runtime_mod.get_head_runtime()
        out: Dict[bytes, int] = {}
        for r in self._replicas():
            rec = rt.get_actor_record(r._actor_id)
            worker = getattr(rec, "worker", None)
            proc = getattr(worker, "process", None)
            pid = getattr(proc, "pid", None)
            if pid is not None:
                out[r._actor_id.binary()] = pid
        return out

    def kill_one(self) -> Optional[bytes]:
        """SIGKILL one random replica worker now; returns the victim's
        actor_id bytes (or None if no killable replica exists)."""
        import os
        import signal

        pids = self.replica_pids()
        if not pids:
            return None
        victim = self._rng.choice(sorted(pids))
        pid = pids[victim]
        try:
            os.kill(pid, signal.SIGKILL)
        except ProcessLookupError:
            return None
        self.killed.append((victim, pid))
        return victim

    def run(self) -> None:
        import threading

        def loop():
            while not self._stop.wait(self.kill_interval_s):
                if (self.max_kills is not None
                        and len(self.killed) >= self.max_kills):
                    return
                try:
                    self.kill_one()
                except Exception:
                    pass  # serve shutting down mid-chaos is fine

        self._thread = threading.Thread(target=loop, daemon=True,
                                        name="rt-replica-killer")
        self._thread.start()

    def stop(self) -> None:
        self._stop.set()
        if self._thread is not None:
            self._thread.join(timeout=2)
            self._thread = None


# Driver script run by each HeadKiller head process. Cycle 1 creates the
# named chaos actor; every later cycle is a RECOVERY: the replacement
# head replays the WAL during init, the actor re-resolves by name, and
# the first call (queued while the actor restarts) completes. Prints one
# parseable READY line, then keeps the actor-call workload running until
# the killer SIGKILLs the process mid-workload.
_HEADKILLER_DRIVER_SRC = r"""
import time
_t0 = time.perf_counter()
import ray_tpu as rt
from ray_tpu.core import runtime as _rtm

rt.init(num_cpus=2)
_init_ms = (time.perf_counter() - _t0) * 1000.0


@rt.remote
class _ChaosCounter:
    def __init__(self):
        self.n = 0

    def bump(self):
        self.n += 1
        return self.n


_t1 = time.perf_counter()
try:
    h = rt.get_actor("chaos_counter")
    created = 0
except ValueError:
    h = _ChaosCounter.options(name="chaos_counter",
                              max_restarts=100000).remote()
    created = 1
v = rt.get(h.bump.remote(), timeout=120)
_recover_ms = (time.perf_counter() - _t1) * 1000.0
_rep = getattr(_rtm.get_head_runtime(), "recovery_report", None) or {}
print("HEADKILLER_READY value=%d created=%d init_ms=%.1f "
      "recover_ms=%.1f restarted=%d actor=%s"
      % (v, created, _init_ms, _recover_ms,
         _rep.get("actors_restarted", 0), h._actor_id.hex()), flush=True)
while True:
    rt.get(h.bump.remote())
    time.sleep(0.005)
"""


class HeadKiller:
    """Chaos fault injector for the HEAD: the NodeKiller counterpart for
    the control plane's single point of failure.

    Each cycle runs a driver/head process (with the native control store
    on a shared WAL ``persist_path``), waits until it reports READY, lets
    the actor-call workload run, then SIGKILLs the head mid-workload —
    no teardown, exactly like a head-host crash. The next cycle's head
    replays the WAL, re-resolves the named actor, restarts it
    (``max_restarts``), and completes the queued call; the time that
    takes is the recovery sample (reference:
    ``release/nightly_tests/chaos_test`` + GCS FT restart drills).
    """

    READY_PREFIX = "HEADKILLER_READY"

    def __init__(self, persist_path: str, kill_after_s: float = 0.5,
                 spawn_timeout_s: float = 180.0,
                 env: Optional[Dict[str, str]] = None,
                 head_src: str = _HEADKILLER_DRIVER_SRC,
                 seed: Optional[int] = None):
        import random

        self.persist_path = persist_path
        self.kill_after_s = kill_after_s
        self.spawn_timeout_s = spawn_timeout_s
        self.killed: list = []
        self._env = dict(env or {})
        self._head_src = head_src
        # Seeded jitter on the kill point (0.75x-1.25x kill_after_s):
        # varies WHERE in the workload the SIGKILL lands while keeping
        # the whole victim sequence replayable from one seed.
        self.seed = chaos_seed(seed)
        _log_seed("HeadKiller", self.seed)
        self._rng = random.Random(self.seed)

    def _child_env(self) -> Dict[str, str]:
        import os

        repo_root = os.path.dirname(os.path.dirname(
            os.path.abspath(__file__)))
        env = dict(os.environ)
        env.update({
            "RT_NATIVE_CONTROL_STORE": "1",
            "RT_CONTROL_STORE_PERSIST_PATH": self.persist_path,
            "JAX_PLATFORMS": "cpu",
            # Small arena: SIGKILLed heads leak their /dev/shm files
            # until reboot; keep the per-cycle footprint tiny.
            "RT_OBJECT_STORE_MEMORY": str(64 * 1024 * 1024),
            "PYTHONUNBUFFERED": "1",
            "PYTHONPATH": repo_root + os.pathsep + env.get(
                "PYTHONPATH", ""),
        })
        env.update(self._env)
        return env

    def run_cycle(self, kill: bool = True) -> Dict[str, float]:
        """One head lifetime: spawn → READY → (workload) → SIGKILL.

        Returns the parsed READY fields plus ``total_ms`` (process spawn
        to READY — the full restart-to-recovered wall time, imports and
        WAL replay included).
        """
        import signal
        import subprocess
        import sys
        import threading
        import time

        t_spawn = time.monotonic()
        proc = subprocess.Popen(
            [sys.executable, "-c", self._head_src],
            stdout=subprocess.PIPE, stderr=subprocess.DEVNULL,
            text=True, env=self._child_env(),
        )
        watchdog = threading.Timer(self.spawn_timeout_s, proc.kill)
        watchdog.daemon = True
        watchdog.start()
        info: Optional[Dict[str, float]] = None
        try:
            for line in proc.stdout:
                if line.startswith(self.READY_PREFIX):
                    info = {}
                    for kv in line.split()[1:]:
                        k, _, v = kv.partition("=")
                        try:
                            info[k] = float(v)
                        except ValueError:
                            info[k] = v  # type: ignore[assignment]
                    break
        finally:
            watchdog.cancel()
        if info is None:
            proc.kill()
            proc.wait()
            proc.stdout.close()
            raise RuntimeError(
                "head process exited before READY (rc=%s)"
                % proc.returncode)
        info["total_ms"] = (time.monotonic() - t_spawn) * 1000.0
        if kill:
            # let the workload run; seeded jitter moves the kill point
            time.sleep(self.kill_after_s * self._rng.uniform(0.75, 1.25))
            proc.send_signal(signal.SIGKILL)
            proc.wait()
            self.killed.append(proc.pid)
        proc.stdout.close()
        return info

    def run(self, cycles: int) -> list:
        """``cycles`` head lifetimes on one WAL; every cycle after the
        first is a recovery (``created == 0``)."""
        return [self.run_cycle() for _ in range(cycles)]
