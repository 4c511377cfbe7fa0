"""Model-building primitives: pure-pytree params with logical-axis trees.

Models in this framework are plain functions over parameter pytrees; every
parameter leaf has a parallel *logical axes* leaf (a tuple of axis names)
consumed by ``parallel.sharding`` to produce mesh shardings. No module
framework — maximum control over sharding, donation, and remat, and the
param tree is directly what checkpoints store.
"""

from __future__ import annotations

from typing import Any, Callable, Dict, Optional, Tuple

import jax
import jax.numpy as jnp


def truncated_normal(key, shape, dtype=jnp.float32, stddev=0.02):
    return stddev * jax.random.truncated_normal(key, -2.0, 2.0, shape, dtype)


def param_count(params: Any) -> int:
    return sum(int(p.size) for p in jax.tree.leaves(params))


def param_bytes(params: Any) -> int:
    return sum(int(p.size * p.dtype.itemsize) for p in jax.tree.leaves(params))


def cast_floating(tree: Any, dtype) -> Any:
    def cast(x):
        if jnp.issubdtype(x.dtype, jnp.floating):
            return x.astype(dtype)
        return x

    return jax.tree.map(cast, tree)


def layer_norm(x, scale, bias, eps: float = 1e-5):
    """LayerNorm in fp32 regardless of activation dtype (stability on MXU
    bf16 paths)."""
    xf = x.astype(jnp.float32)
    mean = jnp.mean(xf, axis=-1, keepdims=True)
    var = jnp.var(xf, axis=-1, keepdims=True)
    y = (xf - mean) * jax.lax.rsqrt(var + eps)
    return (y * scale.astype(jnp.float32) + bias.astype(jnp.float32)).astype(
        x.dtype
    )


def rms_norm(x, scale, eps: float = 1e-6):
    xf = x.astype(jnp.float32)
    var = jnp.mean(xf * xf, axis=-1, keepdims=True)
    return (xf * jax.lax.rsqrt(var + eps) * scale.astype(jnp.float32)).astype(
        x.dtype
    )


def cross_entropy_sums(logits, targets, ignore_id: int = -1,
                       vocab_axis=None):
    """Masked token CE in fp32 as (nll_sum, token_count) — the composable
    form, summable across sequence/loss chunks.

    Inside a ``shard_map`` whose ``vocab_axis`` mesh axis (or axes) shards
    the vocab, ``logits`` is this device's slice of it: the log-sum-exp's
    max and sum and the gold logit cross that axis as ``[tokens]``
    vectors, the ``[tokens, vocab]`` matrix never does. The sums are of
    this device's tokens either way.
    """
    logits = logits.astype(jnp.float32)
    mask = (targets != ignore_id).astype(jnp.float32)
    targets = jnp.maximum(targets, 0)
    if not vocab_axis:
        logz = jax.nn.logsumexp(logits, axis=-1)
        gold = jnp.take_along_axis(
            logits, targets[..., None], axis=-1)[..., 0]
    else:
        v = logits.shape[-1]
        top = jax.lax.pmax(
            jax.lax.stop_gradient(logits.max(axis=-1)), vocab_axis)
        logz = top + jnp.log(jax.lax.psum(
            jnp.exp(logits - top[..., None]).sum(axis=-1), vocab_axis))
        local = targets - jax.lax.axis_index(vocab_axis) * v
        here = jnp.take_along_axis(
            logits, jnp.clip(local, 0, v - 1)[..., None], axis=-1)[..., 0]
        gold = jax.lax.psum(
            jnp.where((local >= 0) & (local < v), here, 0.0), vocab_axis)
    return jnp.sum((logz - gold) * mask), mask.sum()


def cross_entropy_loss(logits, targets, ignore_id: int = -1):
    """Token-level CE in fp32; returns (mean_loss, denom)."""
    nll_sum, count = cross_entropy_sums(logits, targets, ignore_id)
    denom = jnp.maximum(count, 1.0)
    return nll_sum / denom, denom
