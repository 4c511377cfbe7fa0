"""Expert parallelism: mixture-of-experts layer with all_to_all dispatch.

Absent from the reference (SURVEY §2.4: "Expert parallelism: absent").
TPU-native design: experts are sharded over the ``ep`` mesh axis; tokens are
routed top-k, dispatched to expert shards with ``jax.lax.all_to_all`` over
ICI, processed as dense batched matmuls (MXU-friendly: fixed expert
capacity, no ragged shapes), and combined back weighted by router probs.

Static shapes throughout: capacity = ceil(tokens_per_device * k *
capacity_factor / num_experts); overflow tokens are dropped (standard
Switch/GShard behavior) — the router's aux loss pushes load balance.
"""

from __future__ import annotations

import functools
from typing import Optional, Tuple

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, PartitionSpec as P


def router_topk(logits, k: int):
    """Top-k gating with normalized probs. logits: [tokens, E]."""
    probs = jax.nn.softmax(logits.astype(jnp.float32), axis=-1)
    gate_vals, gate_idx = jax.lax.top_k(probs, k)  # [tokens, k]
    gate_vals = gate_vals / jnp.maximum(
        jnp.sum(gate_vals, axis=-1, keepdims=True), 1e-9
    )
    return gate_vals, gate_idx, probs


def load_balance_loss(probs, gate_idx, num_experts: int):
    """Switch-transformer aux loss: mean_prob * mean_assignment per expert."""
    assign = jax.nn.one_hot(gate_idx[..., 0], num_experts)  # top-1 assignment
    density = jnp.mean(assign, axis=0)
    density_proxy = jnp.mean(probs, axis=0)
    return num_experts * jnp.sum(density * density_proxy)


def _dispatch_mask(gate_idx, gate_vals, num_experts: int, capacity: int):
    """Build dispatch/combine tensors with fixed capacity.

    Returns:
      dispatch: [tokens, E, C] one-hot (token t occupies slot c of expert e)
      combine:  [tokens, E, C] dispatch * gate weight
    """
    tokens, k = gate_idx.shape
    flat_expert = gate_idx.reshape(-1)  # [tokens*k] in k-major order
    onehot = jax.nn.one_hot(flat_expert, num_experts,
                            dtype=jnp.float32)  # [T*k, E]
    # Position of each (token, k) pair within its expert's queue.
    pos = jnp.cumsum(onehot, axis=0) - onehot  # [T*k, E]
    slot = jnp.einsum("te,te->t", pos, onehot)  # slot index per pair
    keep = slot < capacity
    slot = jnp.where(keep, slot, 0).astype(jnp.int32)
    slot_onehot = jax.nn.one_hot(slot, capacity, dtype=jnp.float32)
    dispatch_k = (onehot * keep[:, None])[:, :, None] * slot_onehot[:, None, :]
    dispatch_k = dispatch_k.reshape(tokens, k, num_experts, capacity)
    dispatch = dispatch_k.sum(axis=1)
    combine = jnp.einsum("tkec,tk->tec", dispatch_k, gate_vals)
    return dispatch, combine


def moe_ffn_local(x, router_w, w_in, w_out, *, num_experts: int,
                  top_k: int = 2, capacity_factor: float = 1.25,
                  axis_name: Optional[str] = "ep",
                  activation=jax.nn.gelu) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """MoE FFN body (inside shard_map when axis_name is an ep axis).

    x: [tokens_local, model]; router_w: [model, E] (replicated);
    w_in: [E_local, model, hidden]; w_out: [E_local, hidden, model] —
    experts sharded over ``axis_name`` (E_local = E / ep).

    Returns (y [tokens_local, model], aux_loss scalar).
    """
    tokens, model = x.shape
    ep = jax.lax.axis_size(axis_name) if axis_name else 1
    e_local = num_experts // ep

    logits = x.astype(jnp.float32) @ router_w.astype(jnp.float32)
    gate_vals, gate_idx, probs = router_topk(logits, top_k)
    aux = load_balance_loss(probs, gate_idx, num_experts)

    capacity = max(1, int(capacity_factor * tokens * top_k / num_experts))
    # Pad capacity to a lane-friendly multiple.
    capacity = -(-capacity // 8) * 8
    dispatch, combine = _dispatch_mask(gate_idx, gate_vals, num_experts,
                                       capacity)

    # Gather expert inputs: [E, C, model]. Device d owns global experts
    # [d*e_local, (d+1)*e_local) — device-major numbering matching the
    # router's global expert ids.
    expert_in = jnp.einsum("tec,tm->ecm", dispatch, x.astype(jnp.float32))
    if axis_name and ep > 1:
        # Tiled all_to_all: split the expert dim into ep pieces (piece j =
        # dev j's experts, device-major) and concat received pieces along
        # the slot dim: [E, C, m] -> [e_local, ep*C, m], slot dim in
        # source-device-major blocks of C.
        expert_in = jax.lax.all_to_all(expert_in, axis_name, split_axis=0,
                                       concat_axis=1, tiled=True)
    else:
        expert_in = expert_in.reshape(e_local, capacity, model)

    # Dense batched expert matmuls (MXU path).
    h = jnp.einsum("ecm,emh->ech", expert_in, w_in.astype(jnp.float32))
    h = activation(h)
    y = jnp.einsum("ech,ehm->ecm", h, w_out.astype(jnp.float32))

    if axis_name and ep > 1:
        # Strict inverse: split the slot dim back into its ep source
        # blocks and concat along the expert dim -> [E, C, m] with
        # device-major expert ids again.
        y = jax.lax.all_to_all(y, axis_name, split_axis=1, concat_axis=0,
                               tiled=True)
    else:
        y = y.reshape(num_experts, capacity, model)

    out = jnp.einsum("tec,ecm->tm", combine, y)
    return out.astype(x.dtype), aux
