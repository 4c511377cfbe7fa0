"""Multi-host bootstrap: control-store rendezvous → jax.distributed.

Reference analog: torch ``init_process_group`` rendezvous via the named
store actor (``util/collective/collective.py:120``,
``train/torch/config.py:69``) and Ray's GCS-driven node bootstrap. Here
the native control store is the rendezvous authority: hosts claim ranks
through atomic KV writes, rank 0 publishes the coordinator address, and
every host then enters ``jax.distributed.initialize`` — after which all
cross-host tensor traffic is XLA collectives over ICI/DCN, never this
module.

Usage (one call per host process)::

    from ray_tpu.parallel.bootstrap import Bootstrap

    bs = Bootstrap(control_store_client, world_size=4)
    rank = bs.claim_rank()
    coord = bs.coordinator_address(port=8476)   # rank 0 publishes, rest poll
    bs.initialize_jax()                         # jax.distributed.initialize
"""

from __future__ import annotations

import socket
import time
import uuid
from typing import Optional


class BootstrapError(RuntimeError):
    pass


def _free_port() -> int:
    with socket.socket() as s:
        s.bind(("", 0))
        return s.getsockname()[1]


def _local_ip() -> str:
    # UDP connect trick: no packets sent, kernel picks the egress iface.
    try:
        with socket.socket(socket.AF_INET, socket.SOCK_DGRAM) as s:
            s.connect(("8.8.8.8", 80))
            return s.getsockname()[0]
    except OSError:
        return "127.0.0.1"


class Bootstrap:
    """One rendezvous session over a control-store client.

    The client only needs ``kv_put(key, value, namespace=..., overwrite=...)``
    and ``kv_get(key, namespace=...)`` — both the native socket client and
    the in-process ``GlobalControlStore`` satisfy it.
    """

    NAMESPACE = "bootstrap"

    def __init__(self, kv_client, world_size: int, session: str = "default",
                 poll_s: float = 0.05, host_id: Optional[str] = None):
        self._kv = kv_client
        self.world_size = int(world_size)
        self.session = session
        self.rank: Optional[int] = None
        self._poll_s = poll_s
        # Stable host_id (e.g. hostname / pod index) lets a crashed host
        # RECLAIM its rank slot on restart; the random default only makes
        # claim_rank idempotent within this process's lifetime.
        self._token = (host_id or uuid.uuid4().hex).encode()

    def _key(self, *parts: str) -> bytes:
        return "/".join((self.session,) + parts).encode()

    # -- rank claim -------------------------------------------------------
    def claim_rank(self) -> int:
        """First-writer-wins rank slots (atomic no-overwrite KV puts)."""
        for rank in range(self.world_size):
            if self._kv.kv_put(self._key("rank", str(rank)), self._token,
                               namespace=self.NAMESPACE, overwrite=False):
                self.rank = rank
                return rank
            # Reclaim our own slot: same-process retry always matches;
            # crash-restart rejoin additionally needs a stable host_id.
            if self._kv.kv_get(self._key("rank", str(rank)),
                               namespace=self.NAMESPACE) == self._token:
                self.rank = rank
                return rank
        raise BootstrapError(
            f"all {self.world_size} ranks already claimed for session "
            f"{self.session!r}")

    # -- coordinator ------------------------------------------------------
    def coordinator_address(self, port: Optional[int] = None,
                            timeout_s: float = 60.0) -> str:
        """Rank 0 publishes ``ip:port``; everyone else polls for it."""
        if self.rank is None:
            raise BootstrapError("claim_rank() first")
        key = self._key("coordinator")
        if self.rank == 0:
            address = f"{_local_ip()}:{port or _free_port()}"
            self._kv.kv_put(key, address.encode(),
                            namespace=self.NAMESPACE)
            self._coordinator = address
            return address
        deadline = time.monotonic() + timeout_s
        while time.monotonic() < deadline:
            value = self._kv.kv_get(key, namespace=self.NAMESPACE)
            if value:
                self._coordinator = value.decode()
                return self._coordinator
            time.sleep(self._poll_s)
        raise BootstrapError("timed out waiting for coordinator address")

    # -- barrier ----------------------------------------------------------
    def barrier(self, name: str = "start", timeout_s: float = 60.0) -> None:
        """All ranks arrive before any proceeds (KV slot counting)."""
        if self.rank is None:
            raise BootstrapError("claim_rank() first")
        self._kv.kv_put(self._key("barrier", name, str(self.rank)), b"1",
                        namespace=self.NAMESPACE)
        deadline = time.monotonic() + timeout_s
        while time.monotonic() < deadline:
            arrived = sum(
                1 for r in range(self.world_size)
                if self._kv.kv_get(self._key("barrier", name, str(r)),
                                   namespace=self.NAMESPACE))
            if arrived == self.world_size:
                return
            time.sleep(self._poll_s)
        raise BootstrapError(f"barrier {name!r} timed out")

    # -- jax hand-off ------------------------------------------------------
    def initialize_jax(self, **kwargs) -> None:
        """Enter the jax.distributed world (multi-host SPMD).

        After this returns on every host, ``jax.devices()`` spans the
        whole pod and mesh construction (``MeshSpec.build``) sees all
        chips; collectives compile onto ICI/DCN.
        """
        import jax

        if self.rank is None:
            raise BootstrapError("claim_rank() first")
        coordinator = getattr(self, "_coordinator", None)
        if coordinator is None:
            coordinator = self.coordinator_address()
        # CPU-hosted SPMD (tests / dryruns): the default CPU client has
        # no cross-process collectives ("Multiprocess computations
        # aren't implemented on the CPU backend") — select the gloo
        # implementation. Probe the PLATFORMS CONFIG, not
        # jax.default_backend(): the latter would initialize backends
        # before jax.distributed, which is exactly the ordering bug
        # this guard exists to avoid.
        if "cpu" in (jax.config.jax_platforms or "").split(","):
            jax.config.update("jax_cpu_collectives_implementation", "gloo")
        jax.distributed.initialize(
            coordinator_address=coordinator,
            num_processes=self.world_size,
            process_id=self.rank,
            **kwargs,
        )
