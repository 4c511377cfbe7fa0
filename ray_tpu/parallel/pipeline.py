"""Pipeline parallelism: GPipe-style microbatch pipelining over a mesh axis.

Absent from the reference (SURVEY §2.4: "Pipeline parallelism: absent").
TPU-native design: pipeline stages live on ranks of the ``pp`` mesh axis
(stage parameters sharded over that axis); microbatch activations advance
stage-to-stage via ``jax.lax.ppermute`` — a neighbor ICI transfer — inside
one compiled program, so the whole schedule (fill, steady state, drain) is
a single ``lax.scan`` with no host round-trips.

Schedule: plain GPipe (fill + steady + drain = M + N - 1 ticks for M
microbatches on N stages). Bubble fraction (N-1)/(M+N-1); choose M >= 4N.
"""

from __future__ import annotations

import functools
from typing import Any, Callable

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, PartitionSpec as P


def pipeline_apply_local(stage_fn: Callable, stage_params: Any, microbatches,
                         axis_name: str = "pp"):
    """Run the pipeline from inside shard_map.

    Args:
      stage_fn: ``(params, x) -> y`` — one stage's computation. Every rank
        runs the same code with its own ``stage_params`` shard.
      stage_params: this rank's stage parameters (leading ``stage`` dim
        already consumed by shard_map).
      microbatches: [M, micro_batch, ...] — identical on every rank (the
        first stage reads them; other ranks ignore the injected values).

    Returns [M, micro_batch, ...] outputs, valid on the LAST rank and
    broadcast to all ranks (so the caller's out_spec can be replicated).
    """
    n = jax.lax.axis_size(axis_name)
    rank = jax.lax.axis_index(axis_name)
    m = microbatches.shape[0]
    total_ticks = m + n - 1
    fwd_perm = [(i, (i + 1) % n) for i in range(n)]

    x0 = jnp.zeros_like(microbatches[0])
    outputs0 = jnp.zeros((m,) + tuple(x0.shape), microbatches.dtype)

    def tick(carry, t):
        incoming, outputs = carry
        # Stage 0 injects microbatch t (while t < m); other stages consume
        # what arrived from the left neighbor.
        feed_idx = jnp.minimum(t, m - 1)
        injected = jnp.where(rank == 0, microbatches[feed_idx], incoming)
        y = stage_fn(stage_params, injected)
        # Last stage commits microbatch (t - n + 1) once it exists.
        out_idx = t - (n - 1)
        valid = (rank == n - 1) & (out_idx >= 0)
        outputs = jax.lax.cond(
            valid,
            lambda o: jax.lax.dynamic_update_index_in_dim(
                o, y.astype(o.dtype), jnp.maximum(out_idx, 0), 0),
            lambda o: o,
            outputs,
        )
        # Advance activations one stage to the right (ICI neighbor hop).
        nxt = jax.lax.ppermute(y, axis_name, fwd_perm)
        return (nxt, outputs), None

    (_, outputs), _ = jax.lax.scan(tick, (x0, outputs0),
                                   jnp.arange(total_ticks))
    # Broadcast final outputs from the last stage to all ranks so callers
    # can treat the result as replicated over pp.
    mask = (rank == n - 1).astype(outputs.dtype)
    return jax.lax.psum(outputs * mask, axis_name)


def pipeline_apply(stage_fn: Callable, stacked_params: Any, microbatches,
                   mesh: Mesh, axis_name: str = "pp",
                   params_spec=None, data_spec=None):
    """Sharded entry: stage-shard ``stacked_params`` (leading dim = stage)
    over ``axis_name`` and run the pipeline."""
    from .sharding import smap

    if params_spec is None:
        params_spec = jax.tree.map(lambda _: P(axis_name), stacked_params)
    if data_spec is None:
        data_spec = P()

    def body(params, mb):
        params = jax.tree.map(lambda p: p[0], params)  # drop stage dim
        return pipeline_apply_local(stage_fn, params, mb, axis_name)

    fn = smap(body, mesh, in_specs=(params_spec, data_spec),
              out_specs=data_spec)
    return fn(stacked_params, microbatches)


def num_microbatches_for(batch: int, pp: int, target_bubble: float = 0.2) -> int:
    """Pick M so the GPipe bubble (N-1)/(M+N-1) is below target."""
    if pp <= 1:
        return 1
    m = max(1, int((pp - 1) * (1 - target_bubble) / target_bubble))
    while batch % m != 0 and m > 1:
        m -= 1
    return m
