"""Ring attention: sequence/context parallelism over a mesh axis.

Absent from the reference (SURVEY §5.7: "no ring attention, context/sequence
parallelism anywhere") — designed fresh for TPU: the sequence dim is sharded
over the ``sp`` mesh axis; K/V shards rotate around the ring via
``jax.lax.ppermute`` (compiled to ICI neighbor exchanges) while each device
accumulates attention for its local Q shard with the online-softmax merge,
so peak memory is O(S/n) per device and communication overlaps compute.

Layout: q/k/v ``[batch, heads, seq, head_dim]`` with ``seq`` sharded. Use
inside ``shard_map`` (see :func:`ring_attention` for the sharded wrapper).
"""

from __future__ import annotations

import functools
import math
from typing import Optional

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, PartitionSpec as P

_NEG_INF = -1e30


def ring_attention_local(q, k, v, axis_name: str = "sp",
                         causal: bool = True,
                         scale: Optional[float] = None):
    """Per-shard ring attention body (call inside shard_map).

    q/k/v: local shards [B, H, S_local, D]; sequence is sharded over
    ``axis_name`` in rank order (shard r holds positions
    [r*S_local, (r+1)*S_local)).
    """
    if scale is None:
        scale = 1.0 / math.sqrt(q.shape[-1])
    n = jax.lax.axis_size(axis_name)
    rank = jax.lax.axis_index(axis_name)
    b, h, s_local, d = q.shape

    qf = q.astype(jnp.float32) * scale
    q_pos = rank * s_local + jnp.arange(s_local)  # global Q positions

    perm = [(i, (i + 1) % n) for i in range(n)]

    def step(carry, step_idx):
        m, l, acc, k_cur, v_cur = carry
        src = (rank - step_idx) % n  # whose K/V shard we hold this step
        s = jnp.einsum("bhqd,bhkd->bhqk", qf, k_cur.astype(jnp.float32),
                       preferred_element_type=jnp.float32)
        if causal:
            k_pos = src * s_local + jnp.arange(s_local)
            mask = q_pos[:, None] >= k_pos[None, :]
            s = jnp.where(mask, s, _NEG_INF)
        m_new = jnp.maximum(m, jnp.max(s, axis=-1, keepdims=True))
        # Guard fully-masked rows at step 0 edge cases: keep m finite once
        # any step contributed; exp(-inf - -inf) avoided via where.
        p = jnp.exp(s - m_new)
        p = jnp.where(s <= _NEG_INF / 2, 0.0, p)
        alpha = jnp.exp(jnp.maximum(m - m_new, -80.0))
        l_new = l * alpha + jnp.sum(p, axis=-1, keepdims=True)
        acc_new = acc * alpha + jnp.einsum(
            "bhqk,bhkd->bhqd", p, v_cur.astype(jnp.float32),
            preferred_element_type=jnp.float32,
        )
        # Rotate K/V around the ring (ICI neighbor exchange); overlapped
        # with the next step's compute by XLA's async collective scheduling.
        k_next = jax.lax.ppermute(k_cur, axis_name, perm)
        v_next = jax.lax.ppermute(v_cur, axis_name, perm)
        return (m_new, l_new, acc_new, k_next, v_next), None

    m0 = jnp.full((b, h, s_local, 1), _NEG_INF, jnp.float32)
    l0 = jnp.zeros((b, h, s_local, 1), jnp.float32)
    acc0 = jnp.zeros((b, h, s_local, d), jnp.float32)
    (m, l, acc, _, _), _ = jax.lax.scan(
        step, (m0, l0, acc0, k, v), jnp.arange(n)
    )
    safe_l = jnp.where(l == 0, 1.0, l)
    return (acc / safe_l).astype(q.dtype)


def ring_flash_attention_local(q, k, v, axis_name: str = "sp",
                               causal: bool = True,
                               scale: Optional[float] = None,
                               block_impl: str = "auto"):
    """Ring attention whose per-step block compute is the FLASH kernel
    (``ops.attention``): each step runs one flash forward of the local Q
    shard against the K/V shard currently held, and partial outputs
    merge across steps through their log-sum-exp — mathematically the
    same online-softmax as :func:`ring_attention_local`, but the inner
    S_local x S_local work runs on the fused pallas block instead of a
    materialized fp32 score matrix. Forward-only (serving / long-context
    inference); training through ring attention uses the autodiff-able
    einsum body above.

    Three block modes per step under causal masking: the diagonal step
    (src == rank) is plain causal flash; earlier shards (src < rank)
    attend fully; later shards are skipped via lax.switch with an
    lse of -1e30 so the merge weight is exactly 0.
    """
    from ..ops.attention import attention_with_lse

    if scale is None:
        scale = 1.0 / math.sqrt(q.shape[-1])
    n = jax.lax.axis_size(axis_name)
    rank = jax.lax.axis_index(axis_name)
    b, h, s_local, d = q.shape
    perm = [(i, (i + 1) % n) for i in range(n)]

    def diag_step(kv):
        k_cur, v_cur = kv
        return attention_with_lse(q, k_cur, v_cur, causal=True,
                                  scale=scale, impl=block_impl)

    def full_step(kv):
        k_cur, v_cur = kv
        return attention_with_lse(q, k_cur, v_cur, causal=False,
                                  scale=scale, impl=block_impl)

    def skip_step(kv):
        return (jnp.zeros((b, h, s_local, d), q.dtype),
                jnp.full((b, h, s_local), _NEG_INF, jnp.float32))

    def step(carry, step_idx):
        out, lse, k_cur, v_cur = carry
        src = (rank - step_idx) % n
        if causal:
            branch = jnp.where(src == rank, 0,
                               jnp.where(src < rank, 1, 2))
        else:
            branch = jnp.ones((), jnp.int32)
        o_i, lse_i = jax.lax.switch(
            branch, [diag_step, full_step, skip_step], (k_cur, v_cur))
        new_lse = jnp.logaddexp(lse, lse_i)
        w_old = jnp.exp(lse - new_lse)[..., None]
        w_new = jnp.exp(lse_i - new_lse)[..., None]
        out = out * w_old + o_i.astype(jnp.float32) * w_new
        k_next = jax.lax.ppermute(k_cur, axis_name, perm)
        v_next = jax.lax.ppermute(v_cur, axis_name, perm)
        return (out, new_lse, k_next, v_next), None

    out0 = jnp.zeros((b, h, s_local, d), jnp.float32)
    lse0 = jnp.full((b, h, s_local), _NEG_INF, jnp.float32)
    (out, _, _, _), _ = jax.lax.scan(
        step, (out0, lse0, k, v), jnp.arange(n))
    return out.astype(q.dtype)


def ring_attention(q, k, v, mesh: Mesh, axis_name: str = "sp",
                   causal: bool = True,
                   batch_axes=("dp", "fsdp"), heads_axis="tp",
                   impl: str = "einsum"):
    """Sharded entry point: shard_map-wraps the ring body.

    q/k/v: global arrays [B, H, S, D]; S must divide by the sp axis
    size. ``impl='flash'`` uses the fused flash block per step
    (forward-only); ``'einsum'`` is the autodiff-able training body.
    """
    from .sharding import smap

    body = (ring_flash_attention_local if impl == "flash"
            else ring_attention_local)
    spec = P(batch_axes, heads_axis, axis_name, None)
    fn = smap(
        functools.partial(body, axis_name=axis_name, causal=causal),
        mesh, in_specs=(spec, spec, spec), out_specs=spec,
    )
    return fn(q, k, v)
