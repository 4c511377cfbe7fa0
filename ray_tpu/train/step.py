"""Sharded training-step construction: the device-plane core of Train.

Reference analog: where Ray Train wraps user ``train_func`` around torch DDP
(``train/torch/train_loop_utils.py:56`` prepare_model → DDP allreduce), here
the framework OWNS the training step: one pjit-compiled program whose
gradient allreduce / param-shard all-gathers are XLA collectives laid out by
the mesh + logical-axis rules. No process groups, no wrapper hooks.
"""

from __future__ import annotations

from functools import partial
from typing import Any, Callable, Optional, Tuple

import jax
import jax.numpy as jnp
import optax
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from ..parallel.sharding import (
    Rules,
    prune_rules_for_mesh,
    shardings_for,
    spec_for,
)


def default_optimizer(lr: float = 3e-4, weight_decay: float = 0.1,
                      warmup: int = 100, total_steps: int = 10_000,
                      grad_clip: float = 1.0) -> optax.GradientTransformation:
    schedule = optax.warmup_cosine_decay_schedule(
        0.0, lr, warmup, max(total_steps, warmup + 1), end_value=lr * 0.1
    )
    return optax.chain(
        optax.clip_by_global_norm(grad_clip),
        optax.adamw(schedule, b1=0.9, b2=0.95, weight_decay=weight_decay),
    )


def build_sharded_train(
    init_fn: Callable[[jax.Array], Tuple[Any, Any]],
    loss_fn: Callable[[Any, Any], jax.Array],
    mesh: Mesh,
    rules: Optional[Rules] = None,
    optimizer: Optional[optax.GradientTransformation] = None,
    batch_logical_axes: Tuple = ("batch", "seq"),
    donate: bool = True,
    master_fp32: bool = False,
):
    """Compile (init, step) over a mesh.

    Args:
      init_fn: ``key -> (params, logical_axes)``.
      loss_fn: ``(params, batch) -> scalar loss`` (already mesh-rule aware
        via ``constrain`` annotations inside the model).
      mesh: the device mesh; rules are pruned to its non-trivial axes.
      master_fp32: standard TPU mixed precision — live params (and hence
        grads) are bf16 while an fp32 master copy lives in the optimizer
        state; each step updates the master and re-casts. Halves the
        gradient HBM footprint vs fp32 params.

    Returns (sharded_init, sharded_step, placed_rules) where
      sharded_init: ``key -> (params, opt_state)`` placed on the mesh
      sharded_step: ``(params, opt_state, step, batch) ->
                      (params, opt_state, step, metrics)``
    """
    rules = prune_rules_for_mesh(mesh, rules)
    optimizer = optimizer or default_optimizer()
    batch_spec = spec_for(batch_logical_axes, rules)

    # Derive param shardings from the logical-axes tree (shape-eval only).
    sample_axes = {}

    def _init(key):
        params, axes = init_fn(key)
        sample_axes["axes"] = axes
        return params

    key0 = jax.random.PRNGKey(0)
    param_shapes = jax.eval_shape(_init, key0)
    axes_tree = sample_axes["axes"]
    param_shardings = shardings_for(mesh, axes_tree, rules)

    def opt_shardings_like(params_sh):
        """Match optimizer-state leaves to param shardings by shape."""
        def init_opt(params):
            return optimizer.init(params)

        opt_shape = jax.eval_shape(init_opt, param_shapes)
        flat_params, _ = jax.tree.flatten(param_shapes)
        flat_shard, _ = jax.tree.flatten(params_sh)
        shape_to_shard = {}
        for p, s in zip(flat_params, flat_shard):
            shape_to_shard.setdefault(tuple(p.shape), s)
        replicated = NamedSharding(mesh, P())

        def pick(leaf):
            return shape_to_shard.get(tuple(leaf.shape), replicated)

        return jax.tree.map(pick, opt_shape)

    inner_opt_shardings = opt_shardings_like(param_shardings)
    if master_fp32:
        opt_shardings = {"master": param_shardings,
                         "inner": inner_opt_shardings}
    else:
        opt_shardings = inner_opt_shardings
    step_sharding = NamedSharding(mesh, P())

    @partial(jax.jit,
             out_shardings=(param_shardings, opt_shardings, step_sharding))
    def sharded_init(key):
        params = _init(key)
        if master_fp32:
            master = params
            opt_state = {"master": master,
                         "inner": optimizer.init(master)}
            params = jax.tree.map(
                lambda p: p.astype(jnp.bfloat16)
                if jnp.issubdtype(p.dtype, jnp.floating) else p,
                master)
        else:
            opt_state = optimizer.init(params)
        return params, opt_state, jnp.zeros((), jnp.int32)

    batch_sharding = NamedSharding(mesh, batch_spec)

    @partial(
        jax.jit,
        in_shardings=(param_shardings, opt_shardings, step_sharding, None),
        out_shardings=(param_shardings, opt_shardings, step_sharding, None),
        donate_argnums=(0, 1) if donate else (),
    )
    def sharded_step(params, opt_state, step, batch):
        batch = jax.tree.map(
            lambda x: jax.lax.with_sharding_constraint(
                x, NamedSharding(mesh, batch_spec)
            ) if hasattr(x, "ndim") and x.ndim >= 2 else x,
            batch,
        )
        # Scope names (metadata on the HLO, nothing else): JAX marks the
        # transposed half of fwd_bwd ``transpose(jvp(...))`` and the
        # recomputation ``rematted_computation`` by itself, so a device
        # trace splits forward, recompute, backward and optimizer.
        with jax.named_scope("fwd_bwd"):
            loss, grads = jax.value_and_grad(loss_fn)(params, batch)
        with jax.named_scope("optimizer"):
            if master_fp32:
                master, inner = opt_state["master"], opt_state["inner"]
                grads32 = jax.tree.map(
                    lambda g: g.astype(jnp.float32)
                    if jnp.issubdtype(g.dtype, jnp.floating) else g, grads)
                updates, inner = optimizer.update(grads32, inner, master)
                master = optax.apply_updates(master, updates)
                params = jax.tree.map(
                    lambda m, p: m.astype(p.dtype), master, params)
                opt_state = {"master": master, "inner": inner}
            else:
                updates, opt_state = optimizer.update(grads, opt_state,
                                                      params)
                params = optax.apply_updates(params, updates)
        with jax.named_scope("grad_norm"):
            gnorm = optax.global_norm(grads)
        return params, opt_state, step + 1, {"loss": loss, "grad_norm": gnorm}

    # constrain() uses bare PartitionSpecs, which need an ambient mesh
    # during tracing — bind it around every call.
    return (_under_mesh(mesh, sharded_init),
            _under_mesh(mesh, sharded_step), rules)


def _under_mesh(mesh: Mesh, fn):
    from ..parallel.sharding import under_mesh

    return under_mesh(mesh, fn)


def make_eval_step(loss_fn, mesh: Mesh, rules: Optional[Rules],
                   param_shardings):
    rules = prune_rules_for_mesh(mesh, rules)

    @partial(jax.jit, in_shardings=(param_shardings, None))
    def eval_step(params, batch):
        return loss_fn(params, batch)

    return eval_step
