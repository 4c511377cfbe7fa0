"""Model-building primitives: pure-pytree params with logical-axis trees.

Models in this framework are plain functions over parameter pytrees; every
parameter leaf has a parallel *logical axes* leaf (a tuple of axis names)
consumed by ``parallel.sharding`` to produce mesh shardings. No module
framework — maximum control over sharding, donation, and remat, and the
param tree is directly what checkpoints store.
"""

from __future__ import annotations

import math
from typing import Any, Callable, Dict, Optional, Tuple

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P


def truncated_normal(key, shape, dtype=jnp.float32, stddev=0.02):
    return stddev * jax.random.truncated_normal(key, -2.0, 2.0, shape, dtype)


def draw(key, shape, init, dtype):
    """One seeded parameter of a serving family, as its ``_layer_shapes``
    names it: ``init`` is "ones", "bias", "a_log", "dt_bias" or a standard
    deviation."""
    if init == "ones":
        return jnp.ones(shape, dtype)
    if init == "a_log":
        # the decay's rate a head: log of U(1, 16), in float32 as the
        # families keep it
        return jnp.log(jax.random.uniform(key, shape, jnp.float32, 1.0,
                                          16.0))
    if init == "dt_bias":
        # inverse softplus of a log-uniform dt in [0.001, 0.1], so that
        # exp(g) is neither 0 nor 1 and the state is worth carrying
        dt = jnp.exp(jax.random.uniform(key, shape, jnp.float32,
                                        math.log(1e-3), math.log(1e-1)))
        return dt + jnp.log(-jnp.expm1(-dt))
    # "bias": not zero, so selection by s + b differs from selection by
    # s ("the bias picks, the score weighs"), and small beside the scores'
    # own spread, as a bias trained to even the load out is: at 0.1 a few
    # experts took most rows and a quarter of them none (PERF.md, PR 31)
    std = 0.01 if init == "bias" else init
    return (std * jax.random.truncated_normal(
        key, -2.0, 2.0, shape, jnp.float32)).astype(dtype)


def bulk_key(key):
    """The caller's (threefry) key as a key of the ``rbg`` generator: the
    device's own random-bit instruction instead of a few hundred integer
    operations a word, for the 0.6 G draws of an expert layer."""
    if jnp.issubdtype(key.dtype, jax.dtypes.prng_key):
        key = jax.random.key_data(key)
    return jax.random.wrap_key_data(
        jnp.concatenate([key, key]).astype(jnp.uint32), impl="rbg")


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


def rope_lane_tables(positions, heads: int, d: int, theta: float):
    """The rotary tables of ``positions`` [B, T] for :func:`rope_lanes`:
    (cos, signed sin), each [B, T, heads * d] — a token's heads side by
    side, every head the same d lanes: pair i's angle at lanes 2i and
    2i + 1 of a head and the sine negative at 2i. They depend on the
    positions alone: a loop over layers computes them once, outside."""
    freqs = 1.0 / (theta ** (jnp.arange(0, d, 2, dtype=jnp.float32) / d))
    angles = positions[:, :, None].astype(jnp.float32) * freqs
    cos, sin = jnp.cos(angles), jnp.sin(angles)           # [B, T, d/2]
    shape = angles.shape[:-1] + (d,)
    return tuple(
        jnp.tile(jnp.stack(pair, axis=-1).reshape(shape), (1, 1, heads))
        for pair in ((cos, cos), (-sin, sin)))


def _swap_pairs(x):
    """Lanes 2i and 2i + 1 of the last axis exchanged: each lane takes
    its left or its right neighbour, by its parity (two shifts and a
    select, no lane leaves its pair, so the zeros shifted in are never
    taken)."""
    edge = [(0, 0, 0)] * (x.ndim - 1)
    zero = jnp.zeros((), x.dtype)
    return jnp.where(jnp.arange(x.shape[-1]) % 2 == 0,
                     jax.lax.pad(x, zero, edge + [(-1, 1, 0)]),
                     jax.lax.pad(x, zero, edge + [(1, -1, 0)]))


def rope_lanes(x, tables, mesh=None, lanes_axis=None):
    """``llama.rope`` of a projection's output AS THE MATMUL LEAVES IT, x
    [B, T, heads * D] with a token's heads side by side: ``x * cos +
    swap(x) * signed_sin``, where swap exchanges the two lanes of every
    pair — the same products and the same sum, bit for bit, as rope's
    ``x1 * cos - x2 * sin`` and ``x2 * cos + x1 * sin``.

    Why on the flat lanes and not on [.., heads, D]: a reshape to heads
    between the projection and the rotary step is folded by the TPU
    compiler INTO the projection, which becomes a product batched over
    the heads and wants its weight as [heads, D, d_in], the transpose of
    what is stored — so every layer of every step it slices the layer's
    [d_in, heads * D] weight out of the stacked array and writes it again
    transposed before the matmul reads it (40 MiB of traffic for an 8 MiB
    weight; tests/test_tpu_compile.py holds both engine programs to no
    such copy). Kept flat, the matmul reads the layer where it lies. The
    strided halves of ``rope`` and their re-interleaving are gathers
    and copies on the TPU, a dozen operations a layer; here q and k share
    one elementwise fusion.

    The shifts cross the lanes axis: where that axis is sharded (whole
    heads a shard, so no pair is cut) they run per shard in a shard_map,
    or GSPMD would exchange a halo between chips for lanes never taken.
    """
    def rotate(x, cos, sin):
        return (x.astype(jnp.float32) * cos
                + _swap_pairs(x).astype(jnp.float32) * sin).astype(x.dtype)

    if mesh is None or lanes_axis is None:
        return rotate(x, *tables)
    spec = P(None, None, lanes_axis)
    return jax.shard_map(rotate, mesh=mesh, in_specs=(spec, spec, spec),
                         out_specs=spec, check_vma=False)(x, *tables)


def cross_entropy_terms(logits, targets, ignore_id: int = -1,
                        vocab_axis=None):
    """What masked token CE in fp32 is summed from, a token: ``(logz,
    gold, mask, local)``, the log-sum-exp, the target's logit, 1.0 where
    the target counts, and the target's index into ``logits``' last axis.

    Inside a ``shard_map`` whose ``vocab_axis`` mesh axis (or axes) shards
    the vocab, ``logits`` is this device's slice of it: the log-sum-exp's
    max and sum and the gold logit cross that axis as ``[tokens]``
    vectors, the ``[tokens, vocab]`` matrix never does, and ``local`` lies
    outside the slice where another device holds the target.
    """
    logits = logits.astype(jnp.float32)
    mask = (targets != ignore_id).astype(jnp.float32)
    targets = jnp.maximum(targets, 0)
    if not vocab_axis:
        logz = jax.nn.logsumexp(logits, axis=-1)
        gold = jnp.take_along_axis(
            logits, targets[..., None], axis=-1)[..., 0]
        local = targets
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
    return logz, gold, mask, local


def cross_entropy_sums(logits, targets, ignore_id: int = -1,
                       vocab_axis=None):
    """Masked token CE in fp32 as (nll_sum, token_count) — the composable
    form, summable across sequence/loss chunks. The sums are of this
    device's tokens, whether or not ``vocab_axis`` shards the vocab
    (``cross_entropy_terms``).
    """
    logz, gold, mask, _ = cross_entropy_terms(logits, targets, ignore_id,
                                              vocab_axis)
    return jnp.sum((logz - gold) * mask), mask.sum()


def cross_entropy_loss(logits, targets, ignore_id: int = -1):
    """Token-level CE in fp32; returns (mean_loss, denom)."""
    nll_sum, count = cross_entropy_sums(logits, targets, ignore_id)
    denom = jnp.maximum(count, 1.0)
    return nll_sum / denom, denom
