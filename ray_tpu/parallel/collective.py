"""Collective communication facade — the XLA/ICI replacement for
``ray.util.collective``.

Reference analog: ``python/ray/util/collective/collective.py:120-615`` —
``init_collective_group`` rendezvous + eager ``allreduce/broadcast/
allgather/reducescatter/send/recv`` over NCCL/GLOO process groups.

TPU-native design (SURVEY §2.5): intra-mesh tensor traffic is compiled XLA
collectives over ICI — there is no NCCL analog to call. This module keeps
the reference's *eager* API shape for host-driven code (each op jit-compiles
a tiny psum/all_gather program per (shape, dtype, mesh), cached), and the
``ops`` submodule provides the in-graph forms for use inside pjit/shard_map
programs. Groups are mesh axes, not socket rendezvous.
"""

from __future__ import annotations

import threading
from dataclasses import dataclass
from functools import partial
from typing import Dict, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from .mesh import MeshSpec

_REDUCERS = {"sum": jnp.sum, "max": jnp.max, "min": jnp.min,
             "mean": jnp.mean}
_REDUCE_OPS = tuple(_REDUCERS)
_NP_REDUCERS = {"sum": np.sum, "max": np.max, "min": np.min,
                "mean": np.mean}


@dataclass
class CollectiveGroup:
    """A named group = a mesh + the axis collectives run over.

    Reference analog: the (group_name -> NCCLGroup) registry; rendezvous via
    a named store actor is unnecessary because mesh construction is the
    rendezvous.
    """

    name: str
    mesh: Mesh
    axis: str = "dp"

    @property
    def world_size(self) -> int:
        return dict(zip(self.mesh.axis_names,
                        self.mesh.devices.shape))[self.axis]


_groups: Dict[str, CollectiveGroup] = {}
_lock = threading.Lock()
_DEFAULT = "default"


def init_collective_group(mesh: Optional[Mesh] = None, axis: str = "dp",
                          group_name: str = _DEFAULT) -> CollectiveGroup:
    """Register a collective group over a mesh axis.

    Reference: ``init_collective_group(world_size, rank, backend, name)`` —
    world_size/rank/backend are implied by the mesh.
    """
    if mesh is None:
        n = len(jax.devices())
        mesh = MeshSpec(dp=n).build()
    group = CollectiveGroup(group_name, mesh, axis)
    with _lock:
        _groups[group_name] = group
    return group


def destroy_collective_group(group_name: str = _DEFAULT) -> None:
    with _lock:
        _groups.pop(group_name, None)


def get_group(group_name: str = _DEFAULT) -> CollectiveGroup:
    with _lock:
        group = _groups.get(group_name)
    if group is None:
        group = init_collective_group(group_name=group_name)
    return group


# --------------------------------------------------------------------------
# Eager API (reference: collective.py:258-615). Each call runs a cached
# jit-compiled program whose input/output shardings live on the group mesh.
# --------------------------------------------------------------------------

_compiled_cache: Dict[Tuple, callable] = {}


def _sharded_over_axis(group: CollectiveGroup):
    """Sharding that splits leading dim over the group axis."""
    return NamedSharding(group.mesh, P(group.axis))


def _replicated(group: CollectiveGroup):
    return NamedSharding(group.mesh, P())


def allreduce(tensor, op: str = "sum", group_name: str = _DEFAULT):
    """Eager allreduce of per-shard values.

    The input's leading dim indexes ranks (shape ``[world, ...]`` host-side,
    or an already-sharded jax.Array); returns the reduced value replicated
    over the group.
    """
    if op not in _REDUCE_OPS:
        raise ValueError(f"op must be one of {_REDUCE_OPS}")
    group = get_group(group_name)
    key = ("allreduce", op, group.name, _shape_key(tensor))
    fn = _compiled_cache.get(key)
    if fn is None:
        in_sharding = _sharded_over_axis(group)
        out_sharding = _replicated(group)
        reducer = _REDUCERS[op]

        @partial(jax.jit, in_shardings=in_sharding,
                 out_shardings=out_sharding)
        def fn(x):
            return reducer(x, axis=0)

        _compiled_cache[key] = fn
    return fn(tensor)


def allgather(tensor, group_name: str = _DEFAULT):
    """Gather per-rank shards into the full array on every rank."""
    group = get_group(group_name)
    key = ("allgather", group.name, _shape_key(tensor))
    fn = _compiled_cache.get(key)
    if fn is None:
        in_sharding = _sharded_over_axis(group)
        out_sharding = _replicated(group)

        @partial(jax.jit, in_shardings=in_sharding,
                 out_shardings=out_sharding)
        def fn(x):
            return x

        _compiled_cache[key] = fn
    return fn(tensor)


def reducescatter(tensor, op: str = "sum", group_name: str = _DEFAULT):
    """Reduce over ranks, scatter result shards over the group axis."""
    group = get_group(group_name)
    key = ("reducescatter", op, group.name, _shape_key(tensor))
    fn = _compiled_cache.get(key)
    if fn is None:
        mesh, axis = group.mesh, group.axis
        reducer = _REDUCERS[op]
        in_sharding = NamedSharding(mesh, P(axis))  # [world, world_chunks...]
        out_sharding = NamedSharding(mesh, P(axis))

        @partial(jax.jit, in_shardings=in_sharding,
                 out_shardings=out_sharding)
        def fn(x):
            # x: [world, chunk...] per-rank contributions; reduce over rank
            # dim; XLA lowers the resharding to reduce_scatter over ICI.
            return jax.lax.with_sharding_constraint(
                reducer(x, axis=0), NamedSharding(mesh, P(axis))
            )

        _compiled_cache[key] = fn
    return fn(tensor)


def broadcast(tensor, src_rank: int = 0, group_name: str = _DEFAULT):
    """Replicate rank ``src_rank``'s shard to all ranks."""
    group = get_group(group_name)
    key = ("broadcast", src_rank, group.name, _shape_key(tensor))
    fn = _compiled_cache.get(key)
    if fn is None:
        in_sharding = _sharded_over_axis(group)
        out_sharding = _replicated(group)

        @partial(jax.jit, in_shardings=in_sharding,
                 out_shardings=out_sharding, static_argnums=())
        def fn(x):
            return x[src_rank]

        _compiled_cache[key] = fn
    return fn(tensor)


def barrier(group_name: str = _DEFAULT) -> None:
    """Block the host until all devices in the group reach the barrier."""
    group = get_group(group_name)
    token = jnp.zeros((group.world_size, 1), jnp.float32)
    allreduce(token, "sum", group_name).block_until_ready()


def send_recv(tensor, src_rank: int, dst_rank: int,
              group_name: str = _DEFAULT):
    """Point-to-point shard move: rank ``dst_rank``'s slot is replaced
    by rank ``src_rank``'s shard (reference: the send/recv pair of
    collective.py:258-335, which two processes call separately; the
    single-controller eager facade expresses the pair as one op whose
    ppermute edge compiles to a single ICI hop)."""
    group = get_group(group_name)
    key = ("send_recv", src_rank, dst_rank, group.name,
           _shape_key(tensor))
    fn = _compiled_cache.get(key)
    if fn is None:
        sharding = _sharded_over_axis(group)
        axis = group.axis

        @partial(jax.jit, in_shardings=sharding, out_shardings=sharding)
        def fn(x):
            from .sharding import smap

            def body(shard):
                moved = jax.lax.ppermute(
                    shard, axis, [(src_rank, dst_rank)])
                rank = jax.lax.axis_index(axis)
                return jnp.where(rank == dst_rank, moved, shard)

            spec = P(axis)
            return smap(body, group.mesh, in_specs=spec,
                        out_specs=spec)(x)

        _compiled_cache[key] = fn
    return fn(tensor)


def reduce(tensor, dst_rank: int = 0, op: str = "sum",
           group_name: str = _DEFAULT):
    """Reduce across ranks to the ROOT's slot (reference:
    collective.py:380 reduce). Non-root slots are zeroed — the reference
    leaves them undefined; zero is the defined flavor of undefined."""
    if op not in _REDUCE_OPS:
        raise ValueError(f"op must be one of {_REDUCE_OPS}")
    group = get_group(group_name)
    key = ("reduce", op, dst_rank, group.name, _shape_key(tensor))
    fn = _compiled_cache.get(key)
    if fn is None:
        sharding = _sharded_over_axis(group)
        reducer = _REDUCERS[op]

        @partial(jax.jit, in_shardings=sharding, out_shardings=sharding)
        def fn(x):
            red = reducer(x, axis=0, keepdims=True)
            out = jnp.zeros_like(x)
            return jax.lax.dynamic_update_slice_in_dim(
                out, red.astype(x.dtype), dst_rank, 0)

        _compiled_cache[key] = fn
    return fn(tensor)


def gather(tensor, dst_rank: int = 0, group_name: str = _DEFAULT):
    """Gather every rank's shard onto the ROOT's device (reference:
    collective.py:428 gather). Returns the full ``[world, ...]`` array
    resident on rank ``dst_rank``'s device only."""
    from jax.sharding import SingleDeviceSharding

    group = get_group(group_name)
    axis_idx = group.mesh.axis_names.index(group.axis)
    dev = np.moveaxis(group.mesh.devices, axis_idx, 0)[dst_rank]
    dev = np.asarray(dev).flatten()[0]
    # allgather to replicated (the ICI collective), then pin the result
    # to the root's device — jit cannot mix mesh-sharded inputs with a
    # single-device output sharding in one program.
    full = allgather(tensor, group_name=group_name)
    return jax.device_put(full, SingleDeviceSharding(dev))


def _shape_key(tensor) -> Tuple:
    arr = np.asarray(tensor) if not isinstance(tensor, jax.Array) else tensor
    return (tuple(arr.shape), str(arr.dtype))


# --------------------------------------------------------------------------
# Host-plane collective groups: point-to-point and rooted collectives
# BETWEEN ACTORS, rendezvoused through a named mailbox actor over the
# object plane (reference: collective.py's GLOO-backed process groups —
# the cross-mesh/cross-host transport where no ICI axis connects the
# participants). Each actor constructs a HostGroup(world_size, rank);
# matching is deterministic via per-edge sequence numbers.
# --------------------------------------------------------------------------


class _P2PMailbox:
    """Named rendezvous actor: keyed one-shot slots + epoch barriers."""

    def __init__(self):
        self._slots = {}
        self._barriers = {}

    async def put(self, key, value):
        self._slots[key] = value

    async def take(self, key, timeout: float = 60.0):
        import asyncio
        import time as _t

        deadline = _t.monotonic() + timeout
        while key not in self._slots:
            if _t.monotonic() > deadline:
                raise TimeoutError(f"recv timed out waiting for {key}")
            await asyncio.sleep(0.002)
        return self._slots.pop(key)

    async def arrive(self, group: str, epoch: int, world: int,
                     timeout: float = 60.0):
        import asyncio
        import time as _t

        now = _t.monotonic()
        # lazy sweep of RELEASED entries only (count reached world):
        # an incomplete entry may still have live waiters with long
        # timeouts — deleting it would reset the count under them.
        # Incomplete stale entries are cleared by destroy(). world is
        # not stored per-entry, so released-ness rides a sentinel count.
        for k in [k for k, (c, ts) in self._barriers.items()
                  if c < 0 and now - ts > 600.0]:
            del self._barriers[k]
        k = (group, epoch)
        count, _ = self._barriers.get(k, (0, now))
        if count >= 0:  # negative = already released (late arrival ok)
            count += 1
            self._barriers[k] = (count, now)
        deadline = now + timeout
        while True:
            c, _ = self._barriers.get(k, (0, 0))
            if c < 0 or c >= world:
                break
            if _t.monotonic() > deadline:
                raise TimeoutError(f"barrier {k} timed out")
            await asyncio.sleep(0.002)
        # mark released so the sweep may reclaim it later
        self._barriers[k] = (-1, _t.monotonic())
        return True

    async def reset_group(self, group: str):
        self._slots = {k: v for k, v in self._slots.items()
                       if not (isinstance(k, tuple) and k
                               and k[0] == group)}
        self._barriers = {k: v for k, v in self._barriers.items()
                          if k[0] != group}


class HostGroup:
    """Cross-actor collective group over the object plane.

    Every participant (driver or actor) builds one with the same
    ``name`` and distinct ``rank``; ops then match the reference's
    two-sided semantics: ``send`` on one rank pairs with ``recv`` on
    another, ``reduce``/``gather`` deliver to a root rank only.
    """

    _MAILBOX = "rt::p2p-mailbox"

    def __init__(self, world_size: int, rank: int,
                 name: str = "default-host"):
        from ..core import get_actor, remote

        self.world_size = world_size
        self.rank = rank
        self.name = name
        self._send_seq: Dict[Tuple[int, str], int] = {}
        self._recv_seq: Dict[Tuple[int, str], int] = {}
        self._epoch = 0
        self._box = self._get_or_create_mailbox()

    @classmethod
    def _get_or_create_mailbox(cls):
        """Rendezvous on ONE named mailbox across racing participants.
        A losing creator's failure surfaces asynchronously (named
        registration happens when the head processes the creation), so
        creation is confirmed with a ping before the handle is trusted;
        on any failure we fall back to looking the winner up."""
        import time as _t

        from ..core import get, get_actor, remote

        last = None
        for _ in range(100):
            try:
                return get_actor(cls._MAILBOX)
            except Exception as e:  # noqa: BLE001 — not registered yet
                last = e
            try:
                h = remote(_P2PMailbox).options(
                    name=cls._MAILBOX, lifetime="detached",
                    max_concurrency=64).remote()
                get(h.arrive.remote("__ping__", 0, 1, 5), timeout=30)
                return h
            except Exception as e:  # noqa: BLE001 — lost the race
                last = e
                _t.sleep(0.05)
        raise RuntimeError(f"mailbox rendezvous failed: {last!r}")

    def _key(self, src: int, dst: int, tag: str, seq: int):
        return (self.name, src, dst, tag, seq)

    def send(self, tensor, dst_rank: int, tag: str = "") -> None:
        from ..core import get

        edge = (dst_rank, tag)
        seq = self._send_seq.get(edge, 0)
        get(self._box.put.remote(
            self._key(self.rank, dst_rank, tag, seq),
            np.asarray(tensor)), timeout=60)
        # advance only on success: a timed-out op must not desync the
        # edge's sequence numbering (a retry re-targets the same seq)
        self._send_seq[edge] = seq + 1

    def recv(self, src_rank: int, tag: str = "", timeout: float = 60.0):
        from ..core import get

        edge = (src_rank, tag)
        seq = self._recv_seq.get(edge, 0)
        value = get(self._box.take.remote(
            self._key(src_rank, self.rank, tag, seq), timeout),
            timeout=timeout + 10)
        self._recv_seq[edge] = seq + 1  # advance only on success
        return value

    def reduce(self, tensor, dst_rank: int = 0, op: str = "sum"):
        """Rooted reduce: returns the reduced array on the root, None on
        other ranks (reference: collective.py:380)."""
        if op not in _REDUCE_OPS:
            raise ValueError(f"op must be one of {_REDUCE_OPS}")
        if self.rank != dst_rank:
            self.send(tensor, dst_rank, tag="__reduce__")
            return None
        parts = [np.asarray(tensor)]
        for r in range(self.world_size):
            if r != self.rank:
                parts.append(self.recv(r, tag="__reduce__"))
        return _NP_REDUCERS[op](np.stack(parts), axis=0)

    def gather(self, tensor, dst_rank: int = 0):
        """Rooted gather: root returns [world, ...] in rank order, other
        ranks return None (reference: collective.py:428)."""
        if self.rank != dst_rank:
            self.send(tensor, dst_rank, tag="__gather__")
            return None
        out = [None] * self.world_size
        out[self.rank] = np.asarray(tensor)
        for r in range(self.world_size):
            if r != self.rank:
                out[r] = self.recv(r, tag="__gather__")
        return np.stack(out)

    def destroy(self) -> None:
        """Clear this group's mailbox state (reference:
        destroy_collective_group). Call from ONE rank after the cohort
        finishes; REQUIRED before reusing a group name — a new cohort
        under a stale name would see the old cohort's barrier counts
        and release its barriers early."""
        from ..core import get

        get(self._box.reset_group.remote(self.name), timeout=30)

    def barrier(self, timeout: float = 60.0) -> None:
        from ..core import get

        epoch = self._epoch
        get(self._box.arrive.remote(self.name, epoch, self.world_size,
                                    timeout), timeout=timeout + 10)
        self._epoch += 1  # advance only on success


# --------------------------------------------------------------------------
# In-graph collectives: use inside pjit/shard_map programs. These are thin
# aliases so library code imports one module for both styles.
# --------------------------------------------------------------------------

class ops:
    """In-graph collective ops (compiled into the surrounding program)."""

    psum = staticmethod(jax.lax.psum)
    pmean = staticmethod(jax.lax.pmean)
    pmax = staticmethod(jax.lax.pmax)
    pmin = staticmethod(jax.lax.pmin)
    all_gather = staticmethod(jax.lax.all_gather)
    all_to_all = staticmethod(jax.lax.all_to_all)
    ppermute = staticmethod(jax.lax.ppermute)
    psum_scatter = staticmethod(jax.lax.psum_scatter)
    axis_index = staticmethod(jax.lax.axis_index)

    @staticmethod
    def ring_permute(x, axis_name: str, shift: int = 1):
        """Rotate shards around the ring defined by a mesh axis."""
        n = jax.lax.axis_size(axis_name)
        perm = [(i, (i + shift) % n) for i in range(n)]
        return jax.lax.ppermute(x, axis_name, perm)
