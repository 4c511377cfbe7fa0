"""Runtime flag table, env-overridable.

Reference analog: ``src/ray/common/ray_config_def.h`` (167 ``RAY_CONFIG``
entries read via ``RayConfig::instance()``). Here a declarative table of typed
flags, each overridable via environment variable ``RT_<NAME>``, plus a
serialized-dict override path so a head process can propagate one config to
every daemon it starts (reference: ``--system-config`` flag on raylet/gcs).
"""

from __future__ import annotations

import json
import os
import threading
from dataclasses import dataclass
from typing import Any, Callable, Dict, Optional


def _parse_bool(v: str) -> bool:
    return v.lower() in ("1", "true", "yes", "on")


_PARSERS: Dict[type, Callable[[str], Any]] = {
    bool: _parse_bool,
    int: int,
    float: float,
    str: str,
}


@dataclass
class _Flag:
    name: str
    type: type
    default: Any
    doc: str


_FLAGS: Dict[str, _Flag] = {}


def _define(name: str, type_: type, default: Any, doc: str) -> None:
    _FLAGS[name] = _Flag(name, type_, default, doc)


# --- Core object/task limits -------------------------------------------------
_define("max_direct_call_object_size", int, 100 * 1024,
        "Results/args at or below this many bytes are inlined in-band instead "
        "of going through the shared-memory store "
        "(reference: ray_config_def.h max_direct_call_object_size).")
_define("object_store_memory", int, 2 * 1024**3,
        "Default per-node shared-memory object store capacity in bytes.")
_define("object_spilling_threshold", float, 0.8,
        "Fraction of store capacity at which spilling to disk begins.")
_define("min_spilling_size", int, 1024 * 1024,
        "Spill batches are fused until at least this many bytes.")
_define("object_transfer_chunk_bytes", int, 5 * 1024**2,
        "Chunk size for node-to-node object push (reference: 5MiB chunks, "
        "object_manager).")
_define("max_lineage_bytes", int, 256 * 1024**2,
        "Cap on retained task specs for lineage reconstruction per worker.")

# --- Scheduling --------------------------------------------------------------
_define("scheduler_spread_threshold", float, 0.5,
        "Hybrid policy: pack onto nodes below this utilization, then spread "
        "(reference: hybrid_scheduling_policy.h).")
_define("max_pending_lease_requests_per_scheduling_category", int, 10,
        "In-flight worker-lease requests per scheduling key.")
_define("worker_lease_timeout_ms", int, 500,
        "How long an idle leased worker is retained before return.")
_define("max_tasks_in_flight_per_worker", int, 1,
        "Pipelined task pushes per leased worker.")

# --- Health / failure --------------------------------------------------------
_define("num_heartbeats_timeout", int, 30,
        "Missed heartbeats before a node is marked dead "
        "(reference: gcs_heartbeat_manager.h).")
_define("heartbeat_period_ms", int, 100, "Node heartbeat period.")
_define("task_max_retries", int, 3, "Default retries for failed tasks.")
_define("memory_monitor_enabled", bool, True,
        "Enable the node OOM guard (reference: memory_monitor.h).")
_define("memory_usage_threshold", float, 0.95,
        "Node memory fraction above which the worker-killing policy fires.")
_define("actor_max_restarts", int, 0, "Default actor restarts on failure.")

_define("control_store_persist_path", str, "",
        "Durable mutation log for the native control store; empty = "
        "in-memory only (reference: Redis vs in-memory GCS storage).")
_define("native_control_store", bool, False,
        "Back the control store's KV/pubsub/node-liveness with the native "
        "C++ daemon (ray_tpu/_native/control_store.cc) instead of the "
        "in-process Python tables (reference: external gcs_server process).")
_define("gcs_client_retry_attempts", int, 5,
        "Transport-level attempts per control-store call: on a dropped "
        "connection the client re-dials with exponential backoff instead "
        "of failing the first call after a store restart "
        "(reference: gcs_rpc_client.h retry/backoff).")
_define("gcs_client_retry_base_ms", int, 50,
        "Base delay of the control-store client reconnect backoff "
        "(doubles per attempt, capped at 1s).")
_define("daemon_rejoin_attempts", int, 0,
        "After losing the driver connection, a node daemon re-dials the "
        "cluster address this many times (exponential backoff) and "
        "re-registers as a fresh node instead of exiting — head-failover "
        "survivors rejoin the replacement head. Requires the head to "
        "listen on a FIXED cluster_listener_port. 0 = exit on driver "
        "death (default).")
_define("cluster_listener_port", int, 0,
        "Fixed port for the head's cluster (daemon-attach) listener; 0 "
        "picks an ephemeral port. Set it when daemons must survive a "
        "head restart and rejoin the replacement head.")

# --- Workers -----------------------------------------------------------------
_define("num_workers_per_node", int, 0,
        "Size of each node's worker pool; 0 means use num_cpus.")
_define("worker_register_timeout_s", int, 30,
        "Seconds to wait for a spawned worker process to register.")
_define("prestart_workers", bool, True,
        "Pre-start the worker pool at node start instead of on demand.")
_define("node_daemons", bool, False,
        "Run each node as its own OS-process daemon (worker pool + shm "
        "store) attached over TCP, instead of in-process node managers. "
        "Reference: one raylet process per host.")
_define("idle_worker_killing_time_ms", int, 60_000,
        "Idle time before surplus workers above the pool floor are reaped.")

# --- Mesh / TPU --------------------------------------------------------------
_define("mesh_claim_timeout_s", int, 60,
        "Timeout waiting for a mesh claim (TPU subslice) to be granted.")
_define("ici_transfer_hint_bytes", int, 64 * 1024**2,
        "Hint: device arrays above this prefer resharding over host transfer.")

# --- Observability -----------------------------------------------------------
_define("tracing_enabled", bool, False,
        "Record spans around task submission/execution (reference: "
        "opt-in OpenTelemetry tracing, tracing_helper.py).")
_define("log_to_driver", bool, True,
        "Echo worker log lines to the driver's stdout/stderr "
        "(reference: log_monitor.py -> driver printer).")
_define("worker_redirect_logs", bool, True,
        "Redirect worker stdout/stderr to session log files tailed by "
        "the log monitor.")
_define("metrics_report_interval_ms", int, 1000, "Metrics flush interval.")
_define("trace_sample_rate", float, 1.0,
        "Head-side trace sampling: fraction of trace ids the trace store "
        "indexes (deterministic on the trace id, so every span of a "
        "request shares one verdict). Slow/errored traces are kept "
        "regardless via tail-based retention. 1.0 keeps everything.")
_define("trace_store_max_traces", int, 2048,
        "Bounded LRU capacity of the head trace store (distinct trace "
        "ids); evictions are counted in "
        "rt_telemetry_dropped_total{buffer=tracestore}.")
_define("trace_slow_ms", float, 250.0,
        "Tail-retention threshold: a span at least this long (or any "
        "errored span) promotes its sampled-out trace into the store, "
        "so tail exemplars survive head sampling.")
_define("telemetry_enabled", bool, True,
        "Cluster telemetry plane: runtime metric instrumentation plus "
        "per-process metric-delta/span shipping to the head every "
        "metrics_report_interval_ms (reference: _private/metrics_agent.py "
        "per-node agent -> dashboard aggregation). 0 disables for "
        "overhead A/B runs.")
_define("flight_recorder_enabled", bool, True,
        "Per-task flight recorder: stamp lifecycle transitions "
        "(submitted/scheduled/dispatched/finished) on every task record "
        "and aggregate per-function per-stage latency on the head "
        "(reference: gcs_task_manager task events -> `ray summary "
        "tasks`). No effect when telemetry_enabled is off.")
_define("event_log_max_bytes", int, 64 * 1024**2, "Structured event log cap.")
_define("debug_dump_period_ms", int, 10_000,
        "Period for debug-state dumps (reference: "
        "debug_dump_period_milliseconds).")

_ENV_PREFIX = "RT_"


def jax_pinned_to_cpu() -> bool:
    """True where ``JAX_PLATFORMS=cpu`` holds JAX — in this process and,
    by inheritance, in every process it starts — to the CPU."""
    return os.environ.get("JAX_PLATFORMS", "").strip().lower() == "cpu"


def export_compile_cache_dir() -> Optional[str]:
    """Place JAX's persistent compile cache for this process and every
    process it starts: where ``JAX_COMPILATION_CACHE_DIR`` already says,
    else ``.jax_cache`` at the root of the checkout — one fixed path,
    because the path is part of the cache key and a directory that moves
    never hits. Only the environment variable is set (JAX reads it when
    imported, children inherit it), so the caller never imports JAX and
    no code sets another directory.

    No default where JAX is pinned to the CPU: the cache is for the
    chip's minutes-long compiles, and XLA's CPU loader logs a
    machine-feature error for every entry it reads back."""
    if jax_pinned_to_cpu():
        return os.environ.get("JAX_COMPILATION_CACHE_DIR")
    root = os.path.dirname(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))))
    return os.environ.setdefault("JAX_COMPILATION_CACHE_DIR",
                                 os.path.join(root, ".jax_cache"))


class Config:
    """Process-wide config singleton (reference: RayConfig::instance())."""

    _instance = None
    _lock = threading.Lock()

    def __init__(self):
        self._values: Dict[str, Any] = {}
        for flag in _FLAGS.values():
            env = os.environ.get(_ENV_PREFIX + flag.name.upper())
            if env is not None:
                self._values[flag.name] = _PARSERS[flag.type](env)
            else:
                self._values[flag.name] = flag.default

    @classmethod
    def instance(cls) -> "Config":
        with cls._lock:
            if cls._instance is None:
                cls._instance = cls()
            return cls._instance

    @classmethod
    def reset(cls) -> None:
        with cls._lock:
            cls._instance = None

    def apply_overrides(self, overrides: Dict[str, Any]) -> None:
        for k, v in overrides.items():
            if k not in _FLAGS:
                raise KeyError(f"Unknown config flag: {k}")
            self._values[k] = v

    def serialize(self) -> str:
        return json.dumps(self._values)

    @classmethod
    def from_serialized(cls, payload: str) -> "Config":
        cfg = cls()
        cfg.apply_overrides(json.loads(payload))
        return cfg

    def __getattr__(self, name: str) -> Any:
        try:
            return self.__dict__["_values"][name]
        except KeyError:
            raise AttributeError(name) from None

    def get(self, name: str) -> Any:
        return self._values[name]


def config() -> Config:
    return Config.instance()
