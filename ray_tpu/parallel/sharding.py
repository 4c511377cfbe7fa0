"""Logical-axis sharding rules: annotate once, run on any mesh.

The reference has no analog — model sharding is delegated to user code
(SURVEY §2.4 "Model sharding inside Train workers: delegated"). Here it is
first-class: parameters and activations carry *logical* axis names
("embed", "mlp", "heads", "batch", "seq"), and a rule table maps logical
axes to mesh axes. Changing the parallelism layout = changing the rule
table, not the model.

This is the standard scaling-book recipe: pick a mesh, annotate shardings,
let XLA insert collectives.
"""

from __future__ import annotations

from typing import Any, Dict, List, Optional, Sequence, Tuple, Union

import jax
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

# logical axis -> mesh axis (or tuple of mesh axes, or None = replicate)
Rules = Dict[str, Union[str, Tuple[str, ...], None]]

# Default rule table for transformer LMs. fsdp shards the embed dim of
# params (ZeRO-3 style); tp shards heads/mlp; sp shards activation seq.
DEFAULT_RULES: Rules = {
    "batch": ("dp", "fsdp"),
    "seq": "sp",
    "embed": "fsdp",
    "heads": "tp",
    "kv": None,
    "mlp": "tp",
    "vocab": "tp",
    "layers": None,
    "stage": "pp",
    "expert": "ep",
    "qkv": "tp",
}


def spec_for(logical_axes: Sequence[Optional[str]],
             rules: Optional[Rules] = None) -> P:
    """Map a tuple of logical axis names to a PartitionSpec."""
    rules = dict(DEFAULT_RULES, **(rules or {}))
    out = []
    for ax in logical_axes:
        if ax is None:
            out.append(None)
        else:
            out.append(rules.get(ax))
    # Trim trailing Nones for cleanliness.
    while out and out[-1] is None:
        out.pop()
    return P(*out)


def mesh_axes_for(logical: str,
                  rules: Optional[Rules] = None) -> Tuple[str, ...]:
    """The mesh axes the rules shard one logical axis over (() = none):
    what a ``shard_map`` body names in its collectives."""
    axes = dict(DEFAULT_RULES, **(rules or {})).get(logical)
    if axes is None:
        return ()
    return (axes,) if isinstance(axes, str) else tuple(axes)


def tree_spec(logical_tree: Any, rules: Optional[Rules] = None) -> Any:
    """Map a pytree of logical-axis tuples to a pytree of PartitionSpecs."""
    return jax.tree.map(
        lambda axes: spec_for(axes, rules),
        logical_tree,
        is_leaf=lambda x: isinstance(x, tuple) and all(
            a is None or isinstance(a, str) for a in x
        ),
    )


def shardings_for(mesh: Mesh, logical_tree: Any,
                  rules: Optional[Rules] = None) -> Any:
    """Pytree of NamedShardings for placing arrays on the mesh."""
    specs = tree_spec(logical_tree, rules)
    return jax.tree.map(
        lambda s: NamedSharding(mesh, s),
        specs,
        is_leaf=lambda x: isinstance(x, P),
    )


def constrain(x, logical_axes: Sequence[Optional[str]],
              rules: Optional[Rules] = None):
    """``with_sharding_constraint`` by logical axis names (inside jit).

    No-op when there is no ambient mesh (single-device jit, driver compile
    checks): model code stays mesh-agnostic.
    """
    spec = spec_for(logical_axes, rules)
    if not len(spec):
        return x
    if current_mesh() is None and jax.sharding.get_abstract_mesh().empty:
        return x
    return jax.lax.with_sharding_constraint(x, spec)


def prune_rules_for_mesh(mesh: Mesh, rules: Optional[Rules] = None) -> Rules:
    """Drop rule entries referring to axes absent from (or trivial in) the
    mesh so the same model code runs on any mesh shape."""
    rules = dict(DEFAULT_RULES, **(rules or {}))
    sizes = dict(zip(mesh.axis_names, mesh.devices.shape))

    def keep(mesh_axis):
        return mesh_axis is not None and sizes.get(mesh_axis, 1) > 1

    out: Rules = {}
    for logical, mesh_axis in rules.items():
        if mesh_axis is None:
            out[logical] = None
        elif isinstance(mesh_axis, tuple):
            kept = tuple(a for a in mesh_axis if keep(a))
            out[logical] = kept if kept else None
        else:
            out[logical] = mesh_axis if keep(mesh_axis) else None
    return out


def place(mesh: Mesh, tree: Any, logical_tree: Any,
          rules: Optional[Rules] = None) -> Any:
    """Device-put a pytree onto the mesh under the rule table."""
    shardings = shardings_for(mesh, logical_tree, rules)
    return jax.device_put(tree, shardings)


_CURRENT_MESH: list = [None]


def set_current_mesh(mesh: Optional[Mesh]) -> None:
    _CURRENT_MESH[0] = mesh


def current_mesh() -> Optional[Mesh]:
    return _CURRENT_MESH[0]


def under_mesh(mesh: Mesh, fn):
    """Wrap ``fn`` so every call runs with ``mesh`` as BOTH the repo's
    current mesh (so :func:`constrain` resolves) and the ambient jax
    mesh (so bare PartitionSpecs inside jit resolve). The standard way
    to invoke a compiled program whose model code uses logical-axis
    constraints — used by the sharded train step and the tp-sharded
    serving engine alike."""

    def _call(target, *args, **kwargs):
        prev = current_mesh()
        set_current_mesh(mesh)
        try:
            with jax.set_mesh(mesh):
                return target(*args, **kwargs)
        finally:
            set_current_mesh(prev)

    def wrapped(*args, **kwargs):
        return _call(fn, *args, **kwargs)

    # AOT path (compile checks with abstract inputs, no execution).
    if hasattr(fn, "lower"):
        wrapped.lower = lambda *a, **kw: _call(fn.lower, *a, **kw)
    return wrapped


def smap(f, mesh: Mesh, in_specs, out_specs):
    """``jax.shard_map`` without the varying-manual-axes check (the ring
    and pipeline bodies mix replicated and per-shard values freely)."""
    return jax.shard_map(f, mesh=mesh, in_specs=in_specs,
                         out_specs=out_specs, check_vma=False)
