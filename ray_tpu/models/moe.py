"""The sparse expert layer the serving families share: a sigmoid router
over ALL of a model's routed experts, a grouped product over the experts
THIS chip holds, and the shared experts every chip computes alike.

    s = sigmoid(W_g u) in float32;  sel = top_k(s + b)   (b picks only)
    w = s[sel] / (sum s[sel] + 1e-6) * routed_scaling_factor
    out = sum_{e in sel, e held} w_e W2_e(silu(W1_e u) * W3_e u)

``held`` says which experts the chip holds: ``None`` for all of them, or
``(first, count)`` for the run ``first .. first + count - 1`` of a layer
that a deployment spreads over several chips (expert parallelism: the
router keeps its published width and its picks, and the chip computes its
own experts' part of the result for the rows routed to them). A pick of
an expert that is not held joins the rows that belong to no group, as an
invalid row's picks do, so it costs no product; what the absent experts
would add is left out, and nothing here stands in for the other chips or
for their exchange. The weights ``p["w_gate_up"]`` / ``p["w_down"]``
hold the held experts only, in order.

The config is read by attribute (``num_experts``, ``num_experts_per_tok``,
``d_expert``, ``use_expert_bias``, ``norm_topk_prob``,
``routed_scaling_factor``): ``models/lfm2.py``'s and ``models/solar.py``'s
both carry them.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

from ..ops.grouped_matmul import grouped_matmul

# what ``experts_ffn`` counts, in this order (SlotEngine.STEP_COUNTERS)
EXPERT_COUNTERS = ("experts_hit", "expert_rows", "expert_rows_max")


def route(u, p, cfg):
    """u [N, d] float32, the normed input before it is rounded to the
    model's dtype -> (experts [N, k] int32, weights [N, k] float32), all
    in float32 at the highest matmul precision: a bfloat16 score would
    reorder the 4th and 5th expert far more often than the reference's
    own near-ties do. Over every published expert, held here or not."""
    scores = jax.nn.sigmoid(jnp.dot(
        u.astype(jnp.float32), p["router"].astype(jnp.float32),
        precision=jax.lax.Precision.HIGHEST))
    pick_by = scores
    if cfg.use_expert_bias:
        pick_by = scores + p["expert_bias"].astype(jnp.float32)
    _, experts = jax.lax.top_k(pick_by, cfg.num_experts_per_tok)
    weights = jnp.take_along_axis(scores, experts, axis=-1)
    if cfg.norm_topk_prob:
        weights = weights / (weights.sum(axis=-1, keepdims=True) + 1e-6)
    return experts.astype(jnp.int32), weights * cfg.routed_scaling_factor


def experts_ffn(u, experts, weights, valid, p, cfg, held=None):
    """The held routed experts' SwiGLU on u [N, d] -> (out [N, d], counts
    [3] int32 as EXPERT_COUNTERS names them, over the held experts).

    Every (row, pick) is one row of a grouped product: sorted by expert,
    an expert multiplies exactly its own rows, as many as there are —
    nothing is dropped and nothing is padded to a capacity. Rows that are
    not ``valid`` (parked decode rows, a chunk's tail) and picks of an
    expert that is not ``held`` sort behind every expert's and belong to
    no group, so they cost no product."""
    n, d = u.shape
    e, k, f = cfg.num_experts, cfg.num_experts_per_tok, cfg.d_expert
    if held is None:
        flat = jnp.where(valid[:, None], experts, e).reshape(-1)  # [N * k]
    else:
        first, e = held
        here = valid[:, None] & (experts >= first) & (experts < first + e)
        flat = jnp.where(here, experts - first, e).reshape(-1)
    order = jnp.argsort(flat, stable=True)
    sizes = jnp.bincount(flat, length=e).astype(jnp.int32)     # [E]
    rows = u[order // k]                                       # [N * k, d]
    hidden = grouped_matmul(rows, p["w_gate_up"].astype(u.dtype), sizes)
    act = jax.nn.silu(hidden[:, :f]) * hidden[:, f:]
    y = grouped_matmul(act, p["w_down"].astype(u.dtype), sizes)
    # what lies behind the last group was never computed: it is whatever
    # the buffer held, and must not reach a sum even times zero
    y = jnp.where((jnp.arange(n * k) < sizes.sum())[:, None], y, 0)
    # back to (row, pick) order; a row's picks are weighed in float32
    back = jnp.argsort(order)
    y = y[back].reshape(n, k, d).astype(jnp.float32)
    w = jnp.where(valid[:, None], weights, 0.0)
    out = jnp.einsum("nkd,nk->nd", y, w).astype(u.dtype)
    counts = jnp.stack([(sizes > 0).sum(), sizes.sum(), sizes.max()])
    return out, counts.astype(jnp.int32)


def shared_ffn(u, p):
    """The shared experts' SwiGLU on u [N, d]: every row, no routing and
    no weight; ``n_shared_experts`` experts side by side are one SwiGLU of
    their summed width (``shared_gate_up [d, 2 f_s]``: gate columns
    ``[:f_s]``, up columns ``[f_s:]``; ``shared_down [f_s, d]``)."""
    f = p["shared_down"].shape[0]
    hidden = u @ p["shared_gate_up"].astype(u.dtype)
    return (jax.nn.silu(hidden[:, :f]) * hidden[:, f:]) @ p[
        "shared_down"].astype(u.dtype)
