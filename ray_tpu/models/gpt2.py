"""GPT-2 family — the flagship model (north-star config: 1.5B at ≥45% MFU).

Pure-function transformer LM over param pytrees (see ``models/common.py``):
learned positional embeddings, pre-LN blocks, GELU MLP, tied LM head —
matching the GPT-2 architecture the baseline targets
(``BASELINE.md``: "GPT-2 355M/1.5B DP over ICI").

TPU design choices:
  - bf16 activations + matmuls with fp32 layernorm/softmax/loss
  - per-layer ``jax.checkpoint`` (remat) so 1.5B trains at seq 1024+
  - layers stacked into one scanned super-layer (single compile of the
    block; XLA unrolls collectives per iteration); where the remat policy
    saves the flash kernel's operands and results alone (``mem2``) the
    loop's backward pass is written out too (``_blocks_saving``), so the
    backward kernel reads what its layer saved where the forward loop
    stacked it and no operand is copied out of a stack first
  - attention pluggable: flash (pallas), reference, ring (sp), ulysses (sp)
  - every activation/param annotated with logical axes for the
    dp/fsdp/tp/sp rule table (``parallel/sharding.py``)
"""

from __future__ import annotations

import math
from dataclasses import dataclass, field
from functools import partial
from typing import Any, Callable, Dict, Optional, Tuple

import jax
import jax.numpy as jnp

from ..ops.attention import (attention as attention_op, attention_of_saved,
                             attention_saving, packed_heads_for, saved_stacks)
from ..parallel.sharding import constrain, mesh_axes_for
from .common import cross_entropy_terms, layer_norm, truncated_normal


@dataclass(frozen=True)
class GPT2Config:
    vocab_size: int = 50304  # padded to 128 multiple (50257 -> 50304)
    max_seq: int = 1024
    num_layers: int = 12
    num_heads: int = 12
    d_model: int = 768
    d_mlp: Optional[int] = None
    dropout: float = 0.0  # benchmark configs run dropout-free
    dtype: Any = jnp.bfloat16
    attention_impl: str = "auto"  # auto|flash|reference|ring|ulysses
    remat: bool = True
    # "full" recomputes everything; "dots" saves matmul outputs and
    # recomputes only cheap elementwise ops — the standard transformer
    # trade (much better MFU, modestly more memory); "none" disables.
    remat_policy: str = "dots"
    sp_axis: str = "sp"
    # MoE (expert-parallel) FFN: >0 replaces every block's dense MLP with
    # a top-k routed mixture over ``num_experts`` experts sharded on the
    # ``ep`` mesh axis (parallel/moe.py all_to_all dispatch).
    num_experts: int = 0
    moe_top_k: int = 2
    moe_capacity_factor: float = 1.25
    moe_aux_weight: float = 0.01
    ep_axis: str = "ep"

    @property
    def mlp_dim(self) -> int:
        return self.d_mlp or 4 * self.d_model

    @property
    def head_dim(self) -> int:
        return self.d_model // self.num_heads

    def num_params(self) -> int:
        wpe = self.max_seq * self.d_model
        wte = self.vocab_size * self.d_model
        per_layer = (
            4 * self.d_model * self.d_model  # qkv + proj
            + 2 * self.d_model * self.mlp_dim  # mlp in/out
            + 2 * self.d_model * 2  # lns
            + 4 * self.d_model + self.mlp_dim + self.d_model  # biases(ish)
        )
        return wte + wpe + self.num_layers * per_layer + 2 * self.d_model


# Published GPT-2 sizes (vocab padded for lane alignment).
CONFIGS: Dict[str, GPT2Config] = {
    "gpt2-124m": GPT2Config(num_layers=12, num_heads=12, d_model=768),
    "gpt2-355m": GPT2Config(num_layers=24, num_heads=16, d_model=1024),
    "gpt2-774m": GPT2Config(num_layers=36, num_heads=20, d_model=1280),
    "gpt2-1.5b": GPT2Config(num_layers=48, num_heads=25, d_model=1600),
}


def init_params(key, cfg: GPT2Config) -> Tuple[Dict, Dict]:
    """Returns (params, logical_axes) pytrees with identical structure."""
    keys = jax.random.split(key, 8)
    d, h, m = cfg.d_model, cfg.num_heads, cfg.mlp_dim
    L = cfg.num_layers
    proj_std = 0.02 / math.sqrt(2 * L)

    def layer_init(k):
        ks = jax.random.split(k, 5)
        base = {
            "ln1_scale": jnp.ones((L, d)),
            "ln1_bias": jnp.zeros((L, d)),
            "qkv_w": truncated_normal(ks[0], (L, d, 3 * d)),
            "qkv_b": jnp.zeros((L, 3 * d)),
            "proj_w": truncated_normal(ks[1], (L, d, d), stddev=proj_std),
            "proj_b": jnp.zeros((L, d)),
            "ln2_scale": jnp.ones((L, d)),
            "ln2_bias": jnp.zeros((L, d)),
        }
        if cfg.num_experts > 0:
            E = cfg.num_experts
            base.update({
                "router_w": truncated_normal(ks[2], (L, d, E)),
                "moe_in_w": truncated_normal(ks[3], (L, E, d, m)),
                "moe_out_w": truncated_normal(
                    ks[4], (L, E, m, d), stddev=proj_std),
            })
        else:
            base.update({
                "mlp_in_w": truncated_normal(ks[2], (L, d, m)),
                "mlp_in_b": jnp.zeros((L, m)),
                "mlp_out_w": truncated_normal(
                    ks[3], (L, m, d), stddev=proj_std),
                "mlp_out_b": jnp.zeros((L, d)),
            })
        return base

    params = {
        "wte": truncated_normal(keys[0], (cfg.vocab_size, d)),
        "wpe": truncated_normal(keys[1], (cfg.max_seq, d), stddev=0.01),
        "blocks": layer_init(keys[2]),
        "lnf_scale": jnp.ones((d,)),
        "lnf_bias": jnp.zeros((d,)),
    }
    block_axes = {
        "ln1_scale": ("layers", None),
        "ln1_bias": ("layers", None),
        "qkv_w": ("layers", "embed", "qkv"),
        "qkv_b": ("layers", "qkv"),
        "proj_w": ("layers", "qkv", "embed"),
        "proj_b": ("layers", "embed"),
        "ln2_scale": ("layers", None),
        "ln2_bias": ("layers", None),
    }
    if cfg.num_experts > 0:
        block_axes.update({
            "router_w": ("layers", "embed", None),
            "moe_in_w": ("layers", "expert", "embed", "mlp"),
            "moe_out_w": ("layers", "expert", "mlp", "embed"),
        })
    else:
        block_axes.update({
            "mlp_in_w": ("layers", "embed", "mlp"),
            "mlp_in_b": ("layers", "mlp"),
            "mlp_out_w": ("layers", "mlp", "embed"),
            "mlp_out_b": ("layers", "embed"),
        })
    axes = {
        "wte": ("vocab", "embed"),
        "wpe": (None, "embed"),
        "blocks": block_axes,
        "lnf_scale": (None,),
        "lnf_bias": (None,),
    }
    return params, axes


def _attend(q, k, v, cfg: GPT2Config, rules, packed: bool = False):
    """q, k, v [B, H, S, hd] -> o in the same layout; ``packed``: all four
    in the kernel's packed layout (``_packed_heads``)."""
    from ..parallel.sharding import current_mesh, smap, spec_for

    impl = cfg.attention_impl
    if impl in ("auto", "flash", "reference"):
        from jax.ad_checkpoint import checkpoint_name

        o = attention_op(
            q, k, v, causal=True, impl=impl, mesh=current_mesh(),
            spec=spec_for(("batch", "heads", None, None), rules),
            head_dim=cfg.head_dim if packed else None)
        # Named for the "dots_attn" remat policy: saving attention outputs
        # skips re-running the flash kernel in the backward pass (the
        # single biggest recompute in the block at ~400MB saved for 355M).
        return checkpoint_name(o, "attn_out")
    # Sequence-parallel impls: nest a shard_map over the ambient mesh so the
    # GSPMD program hands locally-sharded blocks to the ring/a2a body.
    from functools import partial as _partial

    mesh = current_mesh()
    if mesh is None:
        raise RuntimeError(
            f"attention_impl={impl!r} needs an ambient mesh "
            "(run via build_sharded_train or set_current_mesh)"
        )
    spec = spec_for(("batch", "heads", "seq", None), rules)
    if impl == "ring":
        from ..parallel.ring import ring_attention_local

        body = _partial(ring_attention_local, axis_name=cfg.sp_axis)
    elif impl == "ulysses":
        from ..parallel.ulysses import ulysses_attention_local

        body = _partial(ulysses_attention_local, axis_name=cfg.sp_axis)
    else:
        raise ValueError(f"unknown attention_impl {impl!r}")
    fn = smap(body, mesh, in_specs=(spec, spec, spec), out_specs=spec)
    return fn(q, k, v)


def _moe_ffn(y, p, cfg: GPT2Config, rules):
    """Expert-parallel FFN (parallel/moe.py): tokens are routed top-k and
    dispatched to ``ep``-sharded experts with all_to_all. The batch rule
    must include ``ep`` (each ep rank owns a distinct token shard — the
    standard expert-parallel layout); non-expert params stay replicated
    over ep and XLA inserts their gradient all-reduce. Returns (out, aux).
    """
    from jax.sharding import PartitionSpec as P

    from ..parallel.moe import moe_ffn_local
    from ..parallel.sharding import current_mesh, smap, spec_for

    b, s, d = y.shape
    mesh = current_mesh()
    ep = cfg.ep_axis
    have_ep = (mesh is not None and ep in mesh.axis_names
               and dict(zip(mesh.axis_names, mesh.devices.shape))[ep] > 1)
    if not have_ep:
        out, aux = moe_ffn_local(
            y.reshape(b * s, d), p["router_w"], p["moe_in_w"],
            p["moe_out_w"], num_experts=cfg.num_experts,
            top_k=cfg.moe_top_k, capacity_factor=cfg.moe_capacity_factor,
            axis_name=None)
        return out.reshape(b, s, d), aux

    x_spec = spec_for(("batch", "seq", None), rules)
    all_axes = tuple(mesh.axis_names)

    def body(yb, rw, wi, wo):
        bb, sb, dd = yb.shape
        out, aux = moe_ffn_local(
            yb.reshape(bb * sb, dd), rw, wi, wo,
            num_experts=cfg.num_experts, top_k=cfg.moe_top_k,
            capacity_factor=cfg.moe_capacity_factor, axis_name=ep)
        # aux differs per token shard: mean over the whole mesh so the
        # out_spec can be replicated.
        aux = jax.lax.pmean(aux, axis_name=all_axes)
        return out.reshape(bb, sb, dd), aux

    fn = smap(body, mesh,
              in_specs=(x_spec, P(), spec_for(("expert",), rules),
                        spec_for(("expert",), rules)),
              out_specs=(x_spec, P()))
    return fn(y, p["router_w"], p["moe_in_w"], p["moe_out_w"])


def _packed_heads(cfg: GPT2Config, seq: int, rules) -> int:
    """Heads a 128-lane row in the layout the block's projections write
    and read (``ops/attention.py flash_attention``); 1: heads whole,
    through [B, S, 3D] and a transpose, as the sequence-parallel impls, the
    reference and a mesh that shards the heads take them."""
    from ..parallel.sharding import current_mesh

    mesh = current_mesh() or jax.sharding.get_abstract_mesh()
    split = [a for name in ("qkv", "heads")
             for a in mesh_axes_for(name, rules)
             if dict(mesh.shape).get(a, 1) > 1]
    if cfg.attention_impl not in ("auto", "flash") or split:
        return 1
    return packed_heads_for(cfg.head_dim, cfg.attention_impl, seq)


def _packed_attention(y, p, cfg: GPT2Config, rules, n: int, attend=None):
    """The attention half of a block from the normed input to the output
    projection with q, k, v and o never in another layout than the flash
    kernel's: ``qkv_w`` [D, 3D] is read as [D, 3, rows, n * hd] and
    ``proj_w`` [D, D] as [rows, n * hd, D] (bitcasts of the stored
    parameters), so each projection's matmul writes, or reads, [B, rows,
    S, n * hd] and no split, reshape or transpose of an activation stands
    between a matmul and a kernel. Heads that do not fill the last row
    (GPT-2 XL's 25) are made up with zero heads in the weights' columns:
    exact, because a zero head adds zero to ``o @ proj_w`` and its
    gradient is cut off again. Three products and not one stacked: a
    stacked result is cut into thirds again, a copy a pass; the price is
    ``dy`` summed from three bfloat16 results (0.24 % rms from float32
    where one ``K = 3D`` matmul reads 0.17 %, PERF.md Findings PR 50).
    ``attend``: ``(q, k, v) -> o`` in place of ``_attend``, for a loop
    that keeps the kernel's operands itself (``_blocks_saving``)."""
    from jax.ad_checkpoint import checkpoint_name

    d, h, hd = cfg.d_model, cfg.num_heads, cfg.head_dim
    rows = -(-h // n)
    fill = ((0, rows * n - h), (0, 0))

    def columns(w, lead):  # [.., H * hd] -> [.., rows, n * hd]
        w = w.astype(y.dtype).reshape(lead + (h, hd))
        w = jnp.pad(w, ((0, 0),) * len(lead) + fill)
        return w.reshape(lead + (rows, n * hd))

    w = columns(p["qkv_w"], (d, 3))
    bias = columns(p["qkv_b"], (3,))
    q, k, v = (
        checkpoint_name(
            jnp.einsum("bsd,drl->brsl", y, w[:, c])
            + bias[c][None, :, None, :], "qkv")
        for c in range(3))
    o = attend(q, k, v) if attend else _attend(q, k, v, cfg, rules,
                                               packed=True)
    proj = jnp.pad(p["proj_w"].astype(o.dtype).reshape(h, hd, d),
                   fill + ((0, 0),)).reshape(rows, n * hd, d)
    return jnp.einsum("brsl,rld->bsd", o, proj)


def _block(x, p, cfg: GPT2Config, rules, attend=None):
    """One transformer block. x: [B, S, D]; p: this layer's param slice.
    Returns (x, aux_loss) — aux is 0 for dense blocks, the router
    load-balance loss for MoE blocks. ``attend``: see
    ``_packed_attention``, the one layout that takes it."""
    b, s, d = x.shape
    h, hd = cfg.num_heads, cfg.head_dim

    from jax.ad_checkpoint import checkpoint_name

    with jax.named_scope("attn"):
        y = layer_norm(x, p["ln1_scale"], p["ln1_bias"])
        n = _packed_heads(cfg, s, rules)
        if n > 1:
            o = _packed_attention(y, p, cfg, rules, n, attend)
        else:
            qkv = (y @ p["qkv_w"].astype(y.dtype)) \
                + p["qkv_b"].astype(y.dtype)
            qkv = constrain(qkv, ("batch", "seq", "qkv"), rules)
            qkv = checkpoint_name(qkv, "qkv")
            q, k, v = jnp.split(qkv, 3, axis=-1)

            def heads(t):  # [B,S,D] -> [B,H,S,hd]
                return t.reshape(b, s, h, hd).transpose(0, 2, 1, 3)

            o = _attend(heads(q), heads(k), heads(v), cfg, rules)
            o = o.transpose(0, 2, 1, 3).reshape(b, s, d) \
                @ p["proj_w"].astype(o.dtype)
        o = o + p["proj_b"].astype(o.dtype)
        x = x + constrain(o, ("batch", "seq", None), rules)

    with jax.named_scope("mlp"):
        y = layer_norm(x, p["ln2_scale"], p["ln2_bias"])
        if cfg.num_experts > 0:
            out, aux = _moe_ffn(y, p, cfg, rules)
            return x + constrain(out, ("batch", "seq", None), rules), aux

        hdn = (y @ p["mlp_in_w"].astype(y.dtype)) \
            + p["mlp_in_b"].astype(y.dtype)
        hdn = constrain(hdn, ("batch", "seq", "mlp"), rules)
        hdn = checkpoint_name(hdn, "mlp_in")
        hdn = jax.nn.gelu(hdn, approximate=True)
        out = (hdn @ p["mlp_out_w"].astype(hdn.dtype)) \
            + p["mlp_out_b"].astype(hdn.dtype)
        x = x + constrain(out, ("batch", "seq", None), rules)
    return x, jnp.zeros((), jnp.float32)


def _embed_lookup(wte, tokens, rules):
    """Token-embedding gather, partitioned by the INDICES (batch/seq).

    GSPMD insists on partitioning a table gather along the embed (offset)
    dim and then pays an involuntary full-rematerialization reshard to the
    activation layout. A shard_map pins the data-parallel decomposition:
    replicated table, (batch, seq)-sharded indices, purely local gathers.
    """
    from ..parallel.sharding import current_mesh, smap, spec_for
    from jax.sharding import PartitionSpec as P

    mesh = current_mesh()
    idx_spec = spec_for(("batch", "seq"), rules)
    if mesh is None or idx_spec == P(None, None):
        return wte[tokens]
    out_spec = spec_for(("batch", "seq", None), rules)
    lookup = smap(lambda w, t: w[t], mesh,
                  in_specs=(P(), idx_spec), out_specs=out_spec)
    return lookup(wte, tokens)


# What the flash kernel reads and writes, by the names ``_block`` and
# ``ops/attention.py`` give them: q, k, v; o; the log-sum-exp.
_KERNELS_OWN = ("qkv", "attn_out", "attn_lse")
# The policies that save by name. "mem": the three big matmul outputs the
# backward pass actually consumes (qkv feeds flash dq/dkv, attn_out feeds
# proj bwd, pre-gelu mlp_in feeds gelu bwd); residual-branch outputs
# (proj/mlp_out) are recomputed — one extra d×d matmul per block (~3% step
# FLOPs) for ~25% less activation HBM. "mem2", the leanest: drop mlp_in too
# (recomputed by re-running the mlp_in matmul in backward, ~+1/6 fwd matmul
# FLOPs) — fits 774M at batch 8 / 1.5B at batch 2 on a 16GB chip.
_SAVED_NAMES = {"mem": _KERNELS_OWN + ("mlp_in",), "mem2": _KERNELS_OWN}


def _remat_block(cfg: GPT2Config, rules):
    """``_block`` of ``cfg`` under ``jax.checkpoint`` with the policy
    ``cfg.remat_policy`` names: ``(x, layer) -> (x, aux)``."""
    block = partial(_block, cfg=cfg, rules=rules)
    if not cfg.remat or cfg.remat_policy == "none":
        return block
    names = jax.checkpoint_policies.save_only_these_names
    dots = jax.checkpoint_policies.dots_with_no_batch_dims_saveable
    policies = {
        "dots": dots,
        "dots_attn": jax.checkpoint_policies.save_from_both_policies(
            dots, names("attn_out", "attn_lse")),
        **{policy: names(*saved) for policy, saved in _SAVED_NAMES.items()},
    }
    # any other name ("full"): everything recomputed
    return jax.checkpoint(block, policy=policies.get(cfg.remat_policy))


def _owns_backward(cfg: GPT2Config, seq: int, rules) -> bool:
    """Whether the layer loop's backward pass is ``_blocks_saving``'s and
    not ``lax.scan``'s transpose: where what a layer saves is its input
    and the flash kernel's operands and results, no more and no less, in
    the layout the kernel takes them (packed rows: the kernel runs, heads
    whole on every device). Every other policy saves or recomputes
    something else, and the reference, ring, ulysses, a mesh that shards
    the heads and the pipeline have no such kernel to hand a stack to. A
    head that fills a row (head dim 128) keeps the checkpointed scan and
    its four copies a layer too: the unpacked branch of ``_block`` takes
    no ``attend`` (ROADMAP Queue A item 6(a))."""
    return (cfg.remat and _SAVED_NAMES.get(cfg.remat_policy) == _KERNELS_OWN
            and _packed_heads(cfg, seq, rules) > 1)


def _blocks_saving(cfg: GPT2Config, rules):
    """``(blocks, x) -> (x, aux)``, every block in turn, for
    ``_owns_backward``'s configurations: one ``jax.custom_vjp`` of two
    ``lax.scan``s over the SAME ``_block``, which differ in what stands
    for attention alone.

    Forward: ``attention_saving``, and the scan keeps each layer's input
    and the ``(q, k, v, o, lse)`` its flash kernel read and wrote: what
    ``jax.checkpoint`` under the policy saves. The input, q, k and v are
    the scan's stacked outputs (XLA fusions make them, and write them into
    their stacks in place); the o and lse stacks are CARRIED, because a
    Mosaic call's result cannot be pointed into a slice of a larger
    buffer: the kernel takes them with the layer number and writes its
    layer itself, where stacking o after the call was a copy of it a layer
    and lse a ``reduce`` (5.7 ms of gpt2-large's 346 ms step, PERF.md
    Findings PR 57), and the block's projection reads o at ``[layer]`` of
    the stack inside its own fusion. Backward: the layers in
    reverse, the stacks loop constants and the layer number the scanned
    value; a layer is ``jax.vjp`` of the block with
    ``attention_of_saved``, whose o is read from the stack (the q, k, v
    products it is handed are dead code, as under ``jax.checkpoint``) and
    whose gradient is the backward kernel reading layer ``i`` of the
    stacks in place.

    ``lax.scan``'s own transpose hands the backward body SLICES of what
    the forward stacked, and a Mosaic call takes no slice of a buffer as
    an operand: XLA copied q, k, v and o out of their stacks in front of
    every backward kernel, 13 ms of gpt2-large's 360 ms step (PERF.md
    Findings PR 55). Saved, recomputed and summed are what the
    checkpointed scan saves, recomputes and sums, in the same order: loss
    and gradients are equal, not close (``tests/test_models.py``). Still
    two ``while``s: a TPU program is straight-line code that only a loop
    shares, and an unrolled one is an executable ``setup_s`` cannot pay
    (ibid.)."""
    from ..parallel.sharding import current_mesh, spec_for

    where = dict(mesh=current_mesh(), head_dim=cfg.head_dim,
                 spec=spec_for(("batch", "heads", None, None), rules))
    block = partial(_block, cfg=cfg, rules=rules)

    @jax.custom_vjp
    def run(blocks, x):
        return _scan_blocks(block, blocks, x)

    def forward(blocks, x):
        def body(carry, at):
            (x, aux, stacks), (layer, i) = carry, at
            kept = []

            def attend(q, k, v):
                o, saved = attention_saving(q, k, v, stacks=stacks, layer=i,
                                            **where)
                kept.append(saved)
                return o

            y, a = block(x, layer, attend=attend)
            ((q, k, v, *stacks),) = kept
            return (y, aux + a, tuple(stacks)), (x, (q, k, v))

        (b, s, _), n = x.shape, _packed_heads(cfg, x.shape[1], rules)
        layers = jax.tree.leaves(blocks)[0].shape[0]
        stacks = saved_stacks(
            layers, (b, -(-cfg.num_heads // n), s, n * cfg.head_dim),
            x.dtype, head_dim=cfg.head_dim)
        (y, aux, stacks), (xs, qkv) = jax.lax.scan(
            body, (x, jnp.zeros((), jnp.float32), stacks),
            (blocks, jnp.arange(layers, dtype=jnp.int32)))
        return (y, aux), (blocks, xs, (*qkv, *stacks))

    def backward(kept, cts):
        (blocks, xs, saved), (dx, daux) = kept, cts

        def body(dx, at):
            layer, x, i = at
            attend = partial(attention_of_saved, saved=saved, layer=i,
                             **where)
            _, pull = jax.vjp(partial(block, attend=attend), x, layer)
            # every layer's aux was added into the sum: each gets daux
            return pull((dx, daux))

        layers = jnp.arange(xs.shape[0], dtype=jnp.int32)
        dx, dblocks = jax.lax.scan(body, dx, (blocks, xs, layers),
                                   reverse=True)
        return dblocks, dx

    run.defvjp(forward, backward)
    return run


def _scan_blocks(block, blocks, x):
    def body(carry, layer):
        x, aux = carry
        x, a = block(x, layer)
        return (x, aux + a), None

    return jax.lax.scan(body, (x, jnp.zeros((), jnp.float32)), blocks)[0]


def forward_features(params, tokens, cfg: GPT2Config, rules=None):
    """tokens [B, S] -> final hidden states [B, S, D] (pre LM head)."""
    b, s = tokens.shape
    # The embedding table is stored vocab/embed-sharded (tp/fsdp) for the
    # LM head matmul; a gather over a sharded table forces GSPMD into
    # involuntary full rematerialization of the output. Constrain the
    # lookup operand to fully replicated (one explicit all-gather, same
    # cost class as an fsdp weight gather): with indices sharded over
    # (batch, seq) the gather is then local and its output is ALREADY in
    # the activation sharding — no resharding transition at all.
    wte = constrain(params["wte"], (None, None), rules)
    wpe = constrain(params["wpe"], (None, None), rules)
    x = _embed_lookup(wte, tokens, rules)
    x = x.astype(cfg.dtype) + wpe[:s].astype(cfg.dtype)[None]
    x = constrain(x, ("batch", "seq", None), rules)

    if _owns_backward(cfg, s, rules):
        x, aux = _blocks_saving(cfg, rules)(params["blocks"], x)
    else:
        x, aux = _scan_blocks(_remat_block(cfg, rules), params["blocks"], x)

    x = layer_norm(x, params["lnf_scale"], params["lnf_bias"])
    return x, aux


def forward(params, tokens, cfg: GPT2Config, rules=None):
    """tokens [B, S] -> logits [B, S, vocab]."""
    x, _ = forward_features(params, tokens, cfg, rules)
    # Tied LM head (fp32 logits for a stable loss), laid out as
    # ``_head_ce_sums`` lays it out: wte gathered over its embed (fsdp)
    # axis, so each device contracts the whole of ``d`` for its own
    # tokens and no partial logits are summed across chips.
    logits = jnp.einsum(
        "bsd,vd->bsv", constrain(x, ("batch", "seq", None), rules),
        constrain(params["wte"].astype(cfg.dtype), ("vocab", None), rules),
        preferred_element_type=jnp.float32,
    )
    return constrain(logits, ("batch", "seq", "vocab"), rules)


# Rules table that maps every logical axis to "replicated" — used inside
# shard_map bodies (pp pipeline) where with_sharding_constraint is invalid.
_NULL_RULES = None


def _null_rules():
    global _NULL_RULES
    if _NULL_RULES is None:
        from ..parallel.sharding import DEFAULT_RULES

        _NULL_RULES = {k: None for k in DEFAULT_RULES}
    return _NULL_RULES


def _pp_axis_size(rules) -> int:
    """Size of the pp mesh axis if the ambient mesh pipelines layers."""
    from ..parallel.sharding import current_mesh

    mesh = current_mesh()
    if mesh is None or "pp" not in mesh.axis_names:
        return 1
    if rules is None or rules.get("layers") != "pp":
        return 1
    return dict(zip(mesh.axis_names, mesh.devices.shape))["pp"]


def _pp_forward_features(params, tokens, cfg: GPT2Config, rules):
    """GPipe pipeline over the ``pp`` mesh axis: stage i owns layers
    [i*L/pp, (i+1)*L/pp); microbatch activations hop stage-to-stage via
    ppermute inside one compiled program (parallel/pipeline.py). Embedding
    and final LN/head run replicated over pp (cheap vs the blocks).

    Enabled by rules {"layers": "pp"} on a mesh with pp>1 — the same
    ``loss_fn`` entrypoint dispatches here, so the Trainer selects
    pipeline parallelism purely through its ScalingConfig mesh axes.
    """
    from jax.sharding import PartitionSpec as P

    from ..parallel.pipeline import num_microbatches_for, pipeline_apply_local
    from ..parallel.sharding import current_mesh, smap, spec_for

    if cfg.num_experts > 0:
        raise NotImplementedError(
            "pp+MoE is not supported yet: the pipeline carry does not "
            "thread the router aux loss, which would silently disable "
            "load balancing — train MoE with dp/fsdp/ep axes instead")
    mesh = current_mesh()
    pp = _pp_axis_size(rules)
    b, s = tokens.shape

    wte = constrain(params["wte"], (None, None), rules)
    wpe = constrain(params["wpe"], (None, None), rules)
    x = _embed_lookup(wte, tokens, rules)
    x = x.astype(cfg.dtype) + wpe[:s].astype(cfg.dtype)[None]

    m = num_microbatches_for(b, pp)
    micro = x.reshape(m, b // m, s, x.shape[-1])

    null = _null_rules()
    block = partial(_block, cfg=cfg, rules=null)
    if cfg.remat and cfg.remat_policy != "none":
        block = jax.checkpoint(block)

    def stage_fn(stage_params, xmb):
        def body(xc, layer):
            xc, _ = block(xc, layer)
            return xc, None

        y, _ = jax.lax.scan(body, xmb, stage_params)
        return y

    blocks_spec = jax.tree.map(lambda _: P("pp"), params["blocks"])
    data_spec = spec_for((None, "batch", "seq", None), rules)

    def pp_body(blocks_local, micro_local):
        return pipeline_apply_local(stage_fn, blocks_local, micro_local,
                                    axis_name="pp")

    fn = smap(pp_body, mesh, in_specs=(blocks_spec, data_spec),
              out_specs=data_spec)
    out = fn(params["blocks"], micro)
    x = out.reshape(b, s, -1)
    x = layer_norm(x, params["lnf_scale"], params["lnf_bias"])
    return x, jnp.zeros((), jnp.float32)


def _chunk_sums_and_grads(xc, tc, wte, vocab_axes):
    """The head's chunk loop: ``(nll_sum, count)`` of one device's tokens
    ``xc [chunks, c, d]`` / ``tc [chunks, c]`` against ``wte [V, d]``, and
    ``nll_sum``'s gradient ``(dx [chunks, c, d], d wte [V, d])``, float32
    logits a chunk at a time and each chunk's ONCE.

    Soft-max cross-entropy's gradient is known where the logits are: ``g =
    (softmax - onehot) * mask``, float32, handed to the MXU in x's dtype
    (what its default pass makes of autodiff's float32 ``d logits``
    too). So a chunk is three vocab-wide products, each accumulating in
    float32: the logits, ``dx_i = g @ wte`` and ``d wte += g.T @ x_i``,
    whose sum over the chunks is carried in the table's dtype. A chunk of
    x is read once, so ``dx_i`` is written where ``x_i`` was and ``dx``
    needs no buffer of its own."""
    def contract(a, b, dims):
        return jax.lax.dot_general(a, b, (dims, ((), ())),
                                   preferred_element_type=jnp.float32)

    def chunk(i, carry):
        nll_sum, count, dwte, held = carry
        xi, ti = held[i], tc[i]
        logits = contract(xi, wte, ((1,), (1,)))
        logz, gold, mask, local = cross_entropy_terms(
            logits, ti, vocab_axis=vocab_axes)
        onehot = local[:, None] == jax.lax.broadcasted_iota(
            jnp.int32, logits.shape, 1)
        g = ((jnp.exp(logits - logz[:, None]) - onehot)
             * mask[:, None]).astype(xi.dtype)
        dwte = (dwte + contract(g, xi, ((0,), (0,)))).astype(wte.dtype)
        dxi = contract(g, wte, ((1,), (0,))).astype(xi.dtype)
        return (nll_sum + jnp.sum((logz - gold) * mask), count + mask.sum(),
                dwte, jax.lax.dynamic_update_index_in_dim(held, dxi, i, 0))

    zero = jnp.zeros((), jnp.float32)
    nll_sum, count, dwte, dx = jax.lax.fori_loop(
        0, xc.shape[0], chunk, (zero, zero, jnp.zeros_like(wte), xc))
    return (nll_sum, count), (dx, dwte)


@partial(jax.custom_vjp, nondiff_argnums=(3,))
def _chunk_sums(xc, tc, wte, vocab_axes):
    """``(nll_sum, count)`` of ``_chunk_sums_and_grads``. Differentiated,
    the forward pass keeps that loop's ``dx`` and ``d wte`` and the
    backward pass only scales them by ``nll_sum``'s cotangent: no logits
    are saved and none made again, three vocab-wide products a chunk
    (``jax.checkpoint`` of the chunk would run four). Undifferentiated,
    nothing reads the two gradients and the compiler drops their
    products."""
    return _chunk_sums_and_grads(xc, tc, wte, vocab_axes)[0]


def _chunk_sums_bwd(vocab_axes, grads, cts):
    dx, dwte = grads
    ct, _ = cts  # the count has no gradient
    if vocab_axes:  # what the forward's psums over them transpose to
        ct = jax.lax.psum(ct, vocab_axes)
    return ((dx * ct).astype(dx.dtype), None, (dwte * ct).astype(dwte.dtype))


_chunk_sums.defvjp(_chunk_sums_and_grads, _chunk_sums_bwd)


def _ce_sums_local(x, targets, wte, loss_chunk, token_axes, vocab_axes,
                   embed_axes):
    """(nll_sum, count) over ALL tokens from one device's share of them.

    x [b, s, d] and targets [b, s] are the tokens this device holds, wte
    its shard of the stored table. The table is gathered over
    ``embed_axes`` here, outside the chunk loop (it keeps its slice of
    the vocab under ``vocab_axes``): once a step, and the transpose is
    one reduce-scatter of ``d wte``, which accumulates over the chunks on
    the device (the TPU compiler makes it an all-reduce and a slice where
    the shard is off the 128-lane tiling, as gpt2-xl's 400 is). The head
    matmul + CE run in token chunks (``_chunk_sums``), so the float32
    logits live a chunk at a time.
    """
    if embed_axes:
        wte = jax.lax.all_gather(wte, embed_axes, axis=1, tiled=True)
    d = x.shape[-1]
    xf = x.reshape(-1, d)
    tf = targets.reshape(-1)
    n = xf.shape[0]
    # Even chunks (rounded to 256 lanes) minimize padding waste: e.g.
    # 6138 tokens → 2×3072 (0.1% pad) instead of 2×4096 (33% pad).
    n_chunks = max(1, -(-n // loss_chunk))
    per_chunk = -(-n // n_chunks)
    chunk = min(n, -(-per_chunk // 256) * 256) if n >= 256 else n
    pad = (-n) % chunk
    if pad:
        xf = jnp.pad(xf, ((0, pad), (0, 0)))
        tf = jnp.pad(tf, (0, pad), constant_values=-1)  # ignore_id
    n_chunks = xf.shape[0] // chunk
    sums = _chunk_sums(xf.reshape(n_chunks, chunk, d),
                       tf.reshape(n_chunks, chunk), wte, vocab_axes)
    if token_axes:  # the two scalars are all that crosses chips
        sums = jax.lax.psum(sums, token_axes)
    return sums


@jax.custom_vjp
def _leave_together(x, wte):
    """Identity whose two cotangents leave the backward pass together (an
    optimization barrier): ``d wte`` is reduced to its shard before the
    blocks' backward pass starts on ``dx``. Left to itself XLA's
    scheduler parks the reduction after that pass, beside the lookup's,
    and holds a whole unreduced table through it."""
    return x, wte


_leave_together.defvjp(
    lambda x, wte: ((x, wte), None),
    lambda _, cts: jax.lax.optimization_barrier(cts))


def _head_ce_sums(x, targets, wte, rules, loss_chunk):
    """Tied LM head + CE, decomposed over TOKENS: every device scores the
    tokens it already holds (batch / seq sharded, as the blocks leave
    them) against a wte whole in ``d``, so the contraction is one on-chip
    float32 accumulation and no ``[tokens, vocab]`` partial logits are
    all-reduced. Under GSPMD alone ``d wte`` would cross the chunk loop
    reduced, a collective a chunk; a shard_map pins the decomposition (as
    ``_embed_lookup`` does for the same table): the loop is local, the
    table's gather and its gradient's reduction happen once a step, and
    the reduced gradient is a shard, not a whole table held through the
    backward pass until the lookup's joins it. ``loss_chunk`` thereby
    caps the logits one DEVICE holds."""
    from jax.sharding import PartitionSpec as P

    from ..parallel.sharding import (current_mesh, mesh_axes_for, smap,
                                     spec_for)

    mesh = current_mesh()
    if mesh is None:
        return _ce_sums_local(x, targets, wte, loss_chunk, (), (), ())
    embed_axes = mesh_axes_for("embed", rules)
    body = partial(
        _ce_sums_local, loss_chunk=loss_chunk,
        token_axes=mesh_axes_for("batch", rules) + mesh_axes_for("seq", rules),
        vocab_axes=mesh_axes_for("vocab", rules), embed_axes=embed_axes)
    fn = smap(body, mesh,
              in_specs=(spec_for(("batch", "seq", None), rules),
                        spec_for(("batch", "seq"), rules),
                        spec_for(("vocab", "embed"), rules)),
              out_specs=(P(), P()))
    if embed_axes:  # there is a reduction to place
        x, wte = _leave_together(x, wte)
    return fn(x, targets, wte)


def loss_fn(params, batch, cfg: GPT2Config, rules=None,
            loss_chunk: int = 4096):
    """batch: {"tokens": [B, S+1]} → next-token CE loss.

    The LM head + CE run in token chunks: fp32 logits for the full batch
    are B*S*vocab*4 bytes (1.65GB at 774M batch 8), so a DEVICE holds
    ``loss_chunk`` tokens' at a time (chunks are cut from a device's own
    tokens, ``_head_ce_sums``), which is what lets the large-batch configs
    fit one chip. Differentiated, a chunk forms the loss's gradient in the
    pass that forms its logits (``_chunk_sums``), and between the forward
    and the backward pass the head holds ``dx [tokens, d]`` and ``d wte
    [V, d]`` in the activations' dtype (21 MB and 129 MB at 774M batch 8).
    Reverse mode only: a ``custom_vjp`` has no forward-mode rule.
    """
    tokens = batch["tokens"]
    inputs, targets = tokens[:, :-1], tokens[:, 1:]
    if _pp_axis_size(rules) > 1:
        x, aux = _pp_forward_features(params, inputs, cfg, rules)
    else:
        x, aux = forward_features(params, inputs, cfg, rules)
    with jax.named_scope("ce"):
        nll_sum, denom = _head_ce_sums(
            x, targets, params["wte"].astype(cfg.dtype), rules, loss_chunk)
    loss = nll_sum / jnp.maximum(denom, 1.0)
    if cfg.num_experts > 0:
        loss = loss + cfg.moe_aux_weight * aux / cfg.num_layers
    return loss


def flops_per_token(cfg: GPT2Config, seq: int) -> float:
    """Training FLOPs/token: 6N + attention term (PaLM appendix formula)."""
    n = cfg.num_params() - cfg.vocab_size * cfg.d_model * 0  # full params
    attn = 12 * cfg.num_layers * cfg.d_model * seq
    return 6.0 * n + attn
