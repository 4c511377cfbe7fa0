"""Ulysses-style sequence parallelism: all_to_all head/sequence swap.

Absent from the reference (SURVEY §2.4/§5.7). DeepSpeed-Ulysses pattern,
TPU-native: activations arrive sequence-sharded [B, H, S/n, D]; an
``all_to_all`` over the ``sp`` axis re-shards to head-sharded [B, H/n, S, D]
so each device runs *full-sequence* attention for a subset of heads; a
second all_to_all restores sequence sharding. On TPU the all_to_alls ride
ICI; compute per device is identical to tensor-parallel attention.

Requires heads % sp == 0 (use ring attention otherwise).
"""

from __future__ import annotations

import functools
from typing import Callable, Optional

import jax
from jax.sharding import Mesh, PartitionSpec as P

from ..ops.attention import attention as _attention


def ulysses_attention_local(q, k, v, axis_name: str = "sp",
                            causal: bool = True, impl: str = "auto"):
    """Per-shard body (inside shard_map). q/k/v: [B, H, S_local, D]."""
    n = jax.lax.axis_size(axis_name)

    def seq_to_heads(x):
        # [B, H, S/n, D] -> [B, H/n, S, D]: split heads dim, concat seq dim.
        return jax.lax.all_to_all(x, axis_name, split_axis=1, concat_axis=2,
                                  tiled=True)

    def heads_to_seq(x):
        return jax.lax.all_to_all(x, axis_name, split_axis=2, concat_axis=1,
                                  tiled=True)

    qh, kh, vh = seq_to_heads(q), seq_to_heads(k), seq_to_heads(v)
    oh = _attention(qh, kh, vh, causal=causal, impl=impl)
    return heads_to_seq(oh)


def ulysses_attention(q, k, v, mesh: Mesh, axis_name: str = "sp",
                      causal: bool = True, impl: str = "auto",
                      batch_axes=("dp", "fsdp"), heads_axis="tp"):
    """Sharded entry point for [B, H, S, D] global arrays."""
    from .sharding import smap

    spec = P(batch_axes, heads_axis, axis_name, None)
    fn = smap(
        functools.partial(ulysses_attention_local, axis_name=axis_name,
                          causal=causal, impl=impl),
        mesh, in_specs=(spec, spec, spec), out_specs=spec,
    )
    return fn(q, k, v)
