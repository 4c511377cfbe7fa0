"""Device meshes and mesh claims — topology as a first-class resource.

The reference schedules scalar resources (``{"GPU": n}``,
``src/ray/common/scheduling_resources.h``); TPU pods are structured — chips
wired in an ICI torus, hosts owning fixed chip subsets, slices joined over
DCN. This module makes that structure schedulable:

  - :class:`MeshSpec` — named parallelism axes (dp/fsdp/tp/pp/sp/ep) with
    sizes, mapped onto physical devices in ICI-friendly order.
  - :class:`MeshClaim` — a scheduler reservation of a contiguous subslice
    ("give me a 4x2 mesh"), the PG-bundle analog for device topology
    (reference analog: placement-group bundles,
    ``util/placement_group.py:128``).

Axis convention (outer → inner, DCN-slowest to ICI-fastest):
  ``dp``   data parallel (gradient allreduce; can ride DCN across slices)
  ``fsdp`` fully-sharded data parallel (param/optimizer sharding, ICI)
  ``pp``   pipeline stages (point-to-point ppermute)
  ``sp``   sequence/context parallel (ring attention / Ulysses)
  ``tp``   tensor parallel (innermost: highest-bandwidth ICI axis)
  ``ep``   expert parallel (MoE all_to_all; aliases onto tp or sp ranks)
"""

from __future__ import annotations

import math
from dataclasses import dataclass, field
from typing import Dict, List, Optional, Sequence, Tuple

import numpy as np

AXIS_ORDER = ("dp", "fsdp", "pp", "sp", "tp", "ep")

# Published per-chip peaks, keyed by ``jax.Device.device_kind``. Source:
# Google Cloud documentation, "TPU v5e" system architecture (197 TFLOP/s
# bf16, 16 GB HBM2e at 819 GB/s per chip). A device that is not here has
# no roofline: callers report nothing for it rather than assume a peak.
DEVICE_PEAKS: Dict[str, Dict[str, float]] = {
    "TPU v5 lite": {"hbm_gbps": 819.0, "bf16_tflops": 197.0},
}


def device_triple() -> Dict[str, object]:
    """``{"platform", "kind", "count"}`` of this process's devices as JAX
    reports them. Initialises the backend — for the process that holds
    the chip (a replica, a train worker), never a driver."""
    import jax

    devs = jax.devices()
    return {"platform": devs[0].platform, "kind": devs[0].device_kind,
            "count": len(devs)}


@dataclass(frozen=True)
class MeshSpec:
    """Logical parallelism layout, independent of physical devices."""

    dp: int = 1
    fsdp: int = 1
    pp: int = 1
    sp: int = 1
    tp: int = 1
    ep: int = 1

    def axis_sizes(self) -> Dict[str, int]:
        return {a: getattr(self, a) for a in AXIS_ORDER}

    @property
    def num_devices(self) -> int:
        n = 1
        for a in AXIS_ORDER:
            n *= getattr(self, a)
        return n

    def active_axes(self) -> List[str]:
        return [a for a in AXIS_ORDER if getattr(self, a) > 1]

    @classmethod
    def for_devices(cls, n: int, tp: int = 1, sp: int = 1, pp: int = 1,
                    fsdp: Optional[int] = None, ep: int = 1) -> "MeshSpec":
        """Fill the dp (or fsdp) axis with whatever devices remain."""
        inner = tp * sp * pp * ep if ep > 1 else tp * sp * pp
        if n % inner != 0:
            raise ValueError(f"{n} devices not divisible by tp*sp*pp={inner}")
        rest = n // inner
        if fsdp is None:
            return cls(dp=rest, tp=tp, sp=sp, pp=pp, ep=ep)
        if rest % fsdp != 0:
            raise ValueError(f"remaining {rest} not divisible by fsdp={fsdp}")
        return cls(dp=rest // fsdp, fsdp=fsdp, tp=tp, sp=sp, pp=pp, ep=ep)

    def build(self, devices: Optional[Sequence] = None) -> "jax.sharding.Mesh":
        """Materialize a ``jax.sharding.Mesh``.

        Device order: JAX's device list for a TPU slice enumerates chips in
        topology order, so reshaping into (dp, fsdp, pp, sp, tp, ep) puts
        the innermost (tp) axis on physically adjacent chips — the
        highest-bandwidth ICI links — and dp outermost where DCN hops are
        tolerable. For finer control pass an explicitly ordered ``devices``.
        """
        import jax
        from jax.sharding import Mesh

        if devices is None:
            devices = jax.devices()
        n = self.num_devices
        if len(devices) < n:
            raise ValueError(
                f"MeshSpec needs {n} devices; only {len(devices)} available"
            )
        dev_array = np.asarray(devices[:n], dtype=object).reshape(
            tuple(getattr(self, a) for a in AXIS_ORDER)
        )
        return Mesh(dev_array, AXIS_ORDER)

    def describe(self) -> str:
        parts = [f"{a}={getattr(self, a)}" for a in self.active_axes()]
        return "x".join(parts) if parts else "single-device"


@dataclass
class MeshClaim:
    """A reservation of device topology, schedulable like a PG bundle.

    The autoscaler/scheduler resolve a claim against node topology labels
    (``NodeInfo.topology``): a claim for 8 chips as (2, 4) must land on
    hosts whose chips are ICI-contiguous. On a single host this degrades to
    "k local chips".
    """

    spec: MeshSpec
    slice_type: Optional[str] = None  # e.g. "v5e-8"; None = any
    multislice: bool = False  # allow spanning DCN-linked slices (dp axis only)
    name: str = ""

    def chips(self) -> int:
        return self.spec.num_devices

    def to_bundles(self, chips_per_host: int) -> List[Dict[str, float]]:
        """Lower to placement-group bundles of TPU chips per host."""
        total = self.chips()
        n_hosts = max(1, math.ceil(total / chips_per_host))
        per_host = min(total, chips_per_host)
        return [{"TPU": float(per_host)} for _ in range(n_hosts)]


def local_mesh(tp: int = 1, sp: int = 1, **kwargs) -> "jax.sharding.Mesh":
    """Mesh over this process's devices (tests: the 8 virtual CPU devices)."""
    import jax

    n = len(jax.devices())
    spec = MeshSpec.for_devices(n, tp=tp, sp=sp, **kwargs)
    return spec.build()


def single_device_mesh() -> "jax.sharding.Mesh":
    return MeshSpec().build()
