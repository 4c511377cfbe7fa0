"""LFM2-MoE-architecture LM for the serving engine: gated short
convolutions among grouped-query attention layers, a dense SwiGLU FFN in
the leading layers and sigmoid-routed sparse experts in the rest.

The layer, ``u`` the normed input (RMSNorm with the config's ``norm_eps``):

    h = x + Op(RMSNorm_op(x));  y = h + FFN(RMSNorm_ffn(h))

* Op "conv" (``conv_L_cache`` taps, no bias): ``[B, C, X] = split3(W_in
  u)``, ``z = B * X``, ``c_t = sum_j k[j] * z_{t - (L-1) + j}`` (depthwise,
  causal, zeros before position 0), ``out = W_out (C * c)``. What a
  sequence carries from one token to the next is its last ``L - 1`` rows
  of ``z``: a fixed-size state a SLOT, whatever the sequence's length.
* Op "full_attention": q / k / v projections without bias, RMSNorm over
  each q head and each k head (one ``[head_dim]`` weight each), THEN the
  rotary step, causal softmax, ``W_o``. Its K and V live in pages.
* FFN of the first ``num_dense_layers`` layers: ``W2(silu(W1 u) * W3 u)``.
* FFN of the others: ``s = sigmoid(W_g u)`` in float32; ``sel = top_k(s +
  b)`` with ``b`` the expert bias (selection only); ``w = s[sel]``,
  normalised to sum 1 (``norm_topk_prob``), times
  ``routed_scaling_factor``; ``sum_e w_e W2_e(silu(W1_e u) * W3_e u)``.
  No capacity and no dropped token: rows are sorted by expert and each
  expert multiplies exactly the rows routed to it (``models/moe.py``,
  the expert layer the sparse serving families share;
  ``ops/grouped_matmul.py``).

What it offers the engine (``models/serving.py``): one step over a cache
of two kinds side by side — ``cache["kv"]``, the page pool of the
ATTENTION layers only (its leading axis counts attention layers, not
layers; layout and kernel are ``models/step.py``'s and
``ops/paged_attention.py``'s), and ``cache["conv"]``, one ``[slots, L - 1,
d]`` array a conv layer — and a :class:`serving.SlotState` for the second.
The layers are unrolled, not scanned: a period holds two kinds of
operator and the lead another FFN, and an expert layer's weights reach
the grouped matmul as arrays of their own (sliced out of a stacked array
they would be copied, 1.2 GB a layer a step).

One program. The TPU's matmuls do not give a row the same bits at 64
rows as at 128, nor in two programs that multiply the same shapes
(measured on the chip, PERF.md Findings PR 31: after ONE layer every
decode row's logits differed between the step with a chunk and the step
without, by up to 0.014, with the row counts made equal too), and here
one differing bit in a router's input sooner or later picks another
expert, after which the sequence is another sequence. A greedy request
has to repeat, so the family sets ``one_program``: the engine runs the
step WITH a chunk on every dispatch, an empty one (``n_valid`` 0) when no
prompt is pending. Within one compiled program a row's bits depend on
nothing but the row: every product is row-wise, and the grouped product
takes k whole, one accumulation a tile, wherever the row is sorted to.
The empty lane costs nothing that is measured: the step is bound by the
bytes of its weights.

Scope names (``jax.named_scope``; metadata only): ``conv``, ``attn``
(projections, head norms, rotary step, kernel, ``W_o``), ``mlp`` (the
lead's dense FFN), ``moe.route``, ``moe.experts``, ``embed``, ``lm_head``.
"""

from __future__ import annotations

import math
from dataclasses import dataclass
from functools import partial
from typing import Any, Dict, Tuple

import jax
import jax.numpy as jnp

from . import serving, step
from .common import (bulk_key, draw, rms_norm, rope_lane_tables,
                     rope_lanes)
# the expert layer is the one every sparse serving family calls; this
# family holds all of its experts (``held=None``)
from .moe import EXPERT_COUNTERS, experts_ffn, route  # noqa: F401

CONV, ATTN = "conv", "full_attention"
PERIOD = (ATTN, CONV, CONV, CONV)
# what a step counts, in this order (SlotEngine.STEP_COUNTERS)
STEP_COUNTERS = EXPERT_COUNTERS


@dataclass(frozen=True)
class Lfm2Config:
    vocab_size: int = 65536
    max_seq: int = 2048
    d_model: int = 2048
    num_heads: int = 32
    num_kv_heads: int = 8
    layer_types: Tuple[str, ...] = (CONV, CONV) + PERIOD * 9 + (ATTN, CONV)
    num_dense_layers: int = 2
    d_mlp: int = 11776            # intermediate_size: the lead's dense FFN
    d_expert: int = 1536          # moe_intermediate_size
    num_experts: int = 64
    num_experts_per_tok: int = 4
    norm_topk_prob: bool = True
    use_expert_bias: bool = True
    routed_scaling_factor: float = 1.0
    conv_L_cache: int = 3
    norm_eps: float = 1e-5
    rope_theta: float = 1e6
    dtype: Any = jnp.bfloat16

    @property
    def num_layers(self) -> int:
        return len(self.layer_types)

    @property
    def head_dim(self) -> int:
        return self.d_model // self.num_heads

    def __post_init__(self):
        unknown = set(self.layer_types) - {CONV, ATTN}
        if unknown:
            raise ValueError(f"unknown layer types {sorted(unknown)}")
        if self.num_experts_per_tok > self.num_experts:
            raise ValueError("more experts a token than experts")


CONFIGS = {
    # the published LFM2-24B-A2B: 30 conv + 10 attention layers
    "lfm2-24b-a2b": Lfm2Config(),
    # one lead layer and one whole period: the CPU tests' size
    "lfm2-tiny": Lfm2Config(
        vocab_size=512, max_seq=128, d_model=64, num_heads=4,
        num_kv_heads=2, layer_types=(CONV,) + PERIOD, num_dense_layers=1,
        d_mlp=160, d_expert=48, num_experts=8, num_experts_per_tok=2,
        dtype=jnp.float32),
}


def _layer_kinds(cfg: Lfm2Config):
    """(operator, "dense" | "moe") of every layer."""
    return [(op, "dense" if i < cfg.num_dense_layers else "moe")
            for i, op in enumerate(cfg.layer_types)]


def _layer_shapes(cfg: Lfm2Config, op: str, ffn: str) -> Dict[str, tuple]:
    """name -> (shape, logical axes, init) of one layer's parameters;
    init is "ones", "bias" or a standard deviation."""
    d, hd = cfg.d_model, cfg.head_dim
    kv, e, f = cfg.num_kv_heads * hd, cfg.num_experts, cfg.d_expert
    out_std = 0.02 / math.sqrt(2 * cfg.num_layers)
    shapes = {"op_norm": ((d,), (None,), "ones"),
              "ffn_norm": ((d,), (None,), "ones")}
    if op == CONV:
        shapes.update(
            w_in=((d, 3 * d), ("embed", None), 0.02),
            # tap j of every channel in row j: conv_k[j] is k[:, j]
            conv_k=((cfg.conv_L_cache, d), (None, None), 0.3),
            w_out=((d, d), (None, "embed"), out_std))
    else:
        shapes.update(
            wq=((d, d), ("embed", "qkv"), 0.02),
            wk=((d, kv), ("embed", "kv"), 0.02),
            wv=((d, kv), ("embed", "kv"), 0.02),
            wo=((d, d), ("qkv", "embed"), out_std),
            q_norm=((hd,), (None,), "ones"),
            k_norm=((hd,), (None,), "ones"))
    if ffn == "dense":
        m = cfg.d_mlp
        shapes.update(w_gate=((d, m), ("embed", "mlp"), 0.02),
                      w_up=((d, m), ("embed", "mlp"), 0.02),
                      w_down=((m, d), ("mlp", "embed"), out_std))
    else:
        shapes.update(
            router=((d, e), ("embed", None), 0.02),
            expert_bias=((e,), (None,), "bias"),
            # an expert's W1 (gate) and W3 (up) side by side: columns
            # [:f] and [f:], so one grouped product reads both
            w_gate_up=((e, d, 2 * f), (None, "embed", None), 0.02),
            w_down=((e, f, d), (None, None, "embed"), out_std))
    return shapes


def param_axes(cfg: Lfm2Config = None) -> Dict:
    cfg = cfg or CONFIGS["lfm2-24b-a2b"]
    return {"wte": ("vocab", "embed"), "final_norm": (None,),
            "layers": [{k: axes for k, (_, axes, _) in
                        _layer_shapes(cfg, op, ffn).items()}
                       for op, ffn in _layer_kinds(cfg)]}


@partial(jax.jit, static_argnums=(1, 2, 3))
def _init_layer(key, cfg: Lfm2Config, op: str, ffn: str):
    shapes = _layer_shapes(cfg, op, ffn)
    keys = jax.random.split(bulk_key(key), len(shapes))
    return {name: draw(k, shape, init, cfg.dtype)
            for k, (name, (shape, _, init)) in zip(keys, shapes.items())}


@partial(jax.jit, static_argnums=(1,))
def _init_embedding(key, cfg: Lfm2Config):
    return draw(bulk_key(key), (cfg.vocab_size, cfg.d_model), 0.02,
                 cfg.dtype)


def init_params(key, cfg: Lfm2Config) -> Tuple[Dict, Dict]:
    """Seeded weights in ``cfg.dtype``, drawn by one jitted program a
    layer (three distinct programs: the kinds of layer there are) and one
    for the embedding, never op by op and never in float32 first: at the
    published widths an expert layer is 0.6 G parameters."""
    keys = jax.random.split(key, cfg.num_layers + 1)
    params = {
        "wte": _init_embedding(keys[0], cfg),
        "final_norm": jnp.ones((cfg.d_model,), cfg.dtype),
        "layers": [_init_layer(k, cfg, op, ffn)
                   for k, (op, ffn) in zip(keys[1:], _layer_kinds(cfg))],
    }
    return params, param_axes(cfg)


# -- the cache: pages for the attention layers, state a slot for the conv ----

def init_cache(cfg: Lfm2Config, num_pages: int, page_size: int):
    return step.init_pool(cfg.layer_types.count(ATTN), cfg, num_pages,
                          page_size)


def attach_slot_state(cfg: Lfm2Config, cache, num_slots: int):
    """The pages' tree with every conv layer's state beside them: the
    last ``conv_L_cache - 1`` rows of ``z`` of each slot's sequence, zero
    for a sequence that has not begun."""
    shape = (num_slots, cfg.conv_L_cache - 1, cfg.d_model)
    return dict(cache, conv=[jnp.zeros(shape, cfg.dtype)
                             for _ in range(cfg.layer_types.count(CONV))])


def reset_slot_state(cache, slots):
    """Those slots' conv state zeroed (jit with the cache donated)."""
    return dict(cache, conv=[c.at[slots].set(0) for c in cache["conv"]])


def cache_axes(cfg: Lfm2Config) -> Dict:
    return {"kv": step.PAGED_KV_AXES,
            "conv": [(None, None, None)] * cfg.layer_types.count(CONV)}


def check_shardable(cfg: Lfm2Config, tp: int) -> None:
    if tp > 1:
        raise ValueError(
            "the lfm2 family serves on one chip: its expert layer holds "
            "every expert and no rule shards them yet")


# -- the operators ---------------------------------------------------------------

def short_conv(u, state, p, cfg: Lfm2Config, b: int, valid, chunk_at):
    """The gated short convolution of one layer on a step's rows.

    u [N, d]: rows ``[:b]`` are the decode rows, one token of slot i
    each; rows ``[b:]`` (if any) are one slot's prompt chunk, in order.
    state [slots, L - 1, d]: each slot's last L - 1 rows of ``z``.
    valid [b] bool: decode rows that are not parked. chunk_at: None, or
    (slot, n_valid) of the chunk; an empty chunk (n_valid 0) leaves its
    slot's state alone too. Returns (out [N, d], new state): a
    parked row's state and the state of every slot not in the step are
    left as they were; the chunk's slot gets the state after its
    n_valid-th token, which is where its next chunk (or its decode row)
    starts from."""
    taps = cfg.conv_L_cache
    k = p["conv_k"].astype(jnp.float32)
    gate_b, gate_c, x = jnp.split(u @ p["w_in"].astype(u.dtype), 3, axis=-1)
    z = gate_b * x                                            # [N, d]
    zd = z[:b]
    # a decode row's window: its slot's state, then its own z
    window = jnp.concatenate([state, zd[:, None]], axis=1)   # [b, L, d]
    conv = jnp.einsum("bjd,jd->bd", window.astype(jnp.float32), k)
    new_state = jnp.where(valid[:, None, None], window[:, 1:], state)
    if chunk_at is not None:
        slot, n_valid = chunk_at
        zc = z[b:]
        c = zc.shape[0]
        before = jax.lax.dynamic_index_in_dim(state, slot, 0, keepdims=False)
        zz = jnp.concatenate([before, zc], axis=0)           # [L - 1 + C, d]
        conv_c = sum(k[j] * zz[j:j + c].astype(jnp.float32)
                     for j in range(taps))
        conv = jnp.concatenate([conv, conv_c], axis=0)
        # rows n_valid .. n_valid + L - 2 of zz are z_{n-L+1} .. z_{n-1}
        after = jax.lax.dynamic_slice_in_dim(zz, n_valid, taps - 1, 0)
        kept = jax.lax.dynamic_index_in_dim(new_state, slot, 0,
                                            keepdims=False)
        new_state = jax.lax.dynamic_update_slice_in_dim(
            new_state, jnp.where(n_valid > 0, after, kept)[None], slot, 0)
    out = (gate_c * conv.astype(u.dtype)) @ p["w_out"].astype(u.dtype)
    return out, new_state


def _head_norm(x, scale, heads: int, eps: float):
    """RMSNorm over each head's lanes of x [.., heads * hd]."""
    lead = x.shape[:-1]
    return rms_norm(x.reshape(lead + (heads, -1)), scale, eps).reshape(
        x.shape)


def _dense_ffn(u, p):
    gate = jax.nn.silu(u @ p["w_gate"].astype(u.dtype))
    return (gate * (u @ p["w_up"].astype(u.dtype))) @ p["w_down"].astype(
        u.dtype)


def _lm_head(x, params, cfg: Lfm2Config):
    """[N, d] hidden states -> [N, vocab] float32 logits (tied head)."""
    with jax.named_scope("lm_head"):
        x = rms_norm(x, params["final_norm"], cfg.norm_eps)
        return jnp.einsum("bd,vd->bv", x, params["wte"].astype(cfg.dtype),
                          preferred_element_type=jnp.float32)


def paged_step(params, cache, tables, tokens, pos, chunk, cfg: Lfm2Config,
               page_size: int, rules=None):
    """One continuous-batching step: the contract of
    ``models/serving.py``'s ``step`` over the rows of
    ``step.step_rows``, with a fourth result: the expert layers' counts,
    summed over the layers, as :data:`STEP_COUNTERS` names them."""
    rows = step.step_rows(tables, tokens, pos, chunk, cfg.max_seq)
    h, hd, hkv = cfg.num_heads, cfg.head_dim, cfg.num_kv_heads
    with jax.named_scope("embed"):
        x = params["wte"][rows.packed()].astype(cfg.dtype)
    # how the decode rows [B, 1, ..] and the chunk [1, C, ..] are cut out
    # of a step's rows, and the rotary tables of each: once a step
    b = rows.b
    cuts, positions = [lambda a: a[:b, None]], [pos[:, None]]
    if chunk is not None:
        _, _, pre_p0, _ = chunk
        cuts.append(lambda a: a[None, b:])
        positions.append((pre_p0 + jnp.arange(rows.c))[None])
    angles = [[rope_lane_tables(at, n, hd, cfg.rope_theta) for n in (h, hkv)]
              for at in positions]

    def attention(u, kv, p, layer):
        """u [N, d] -> (out [N, d], pool)."""
        q = _head_norm(u @ p["wq"].astype(u.dtype), p["q_norm"], h,
                       cfg.norm_eps)
        k_new = _head_norm(u @ p["wk"].astype(u.dtype), p["k_norm"], hkv,
                           cfg.norm_eps)
        v_new = u @ p["wv"].astype(u.dtype)
        parts = []
        for cut, (at_q, at_k) in zip(cuts, angles):
            qp = rope_lanes(cut(q), at_q)
            parts.append((qp.reshape(qp.shape[:2] + (h, hd)),
                          rope_lanes(cut(k_new), at_k), cut(v_new)))
        o, kv = step.attend(rows, parts, kv, layer, cfg, page_size, rules)
        return o @ p["wo"].astype(u.dtype), kv

    kv, conv = cache["kv"], list(cache["conv"])
    counts = jnp.zeros((len(STEP_COUNTERS),), jnp.int32)
    n_attn = n_conv = 0
    for p, (op, ffn) in zip(params["layers"], _layer_kinds(cfg)):
        if op == CONV:
            with jax.named_scope("conv"):
                out, conv[n_conv] = short_conv(
                    rms_norm(x, p["op_norm"], cfg.norm_eps), conv[n_conv],
                    p, cfg, rows.b, rows.valid, rows.chunk_at)
            n_conv += 1
        else:
            with jax.named_scope("attn"):
                out, kv = attention(
                    rms_norm(x, p["op_norm"], cfg.norm_eps), kv, p,
                    jnp.int32(n_attn))
            n_attn += 1
        x = x + out
        if ffn == "dense":
            with jax.named_scope("mlp"):
                x = x + _dense_ffn(rms_norm(x, p["ffn_norm"], cfg.norm_eps),
                                   p)
            continue
        with jax.named_scope("moe.route"):
            u = rms_norm(x.astype(jnp.float32), p["ffn_norm"], cfg.norm_eps)
            experts, weights = route(u, p, cfg)
        with jax.named_scope("moe.experts"):
            out, layer_counts = experts_ffn(u.astype(x.dtype), experts,
                                            weights, rows.live, p, cfg)
        x = x + out
        counts = counts + layer_counts
    head = partial(_lm_head, params=params, cfg=cfg)
    return *step.logits_of(rows, x, head), {"kv": kv, "conv": conv}, counts


# ``param_axes()`` and ``cache_axes`` are read only under a mesh, which
# ``check_shardable`` refuses for now: they describe the published depth.
serving.register(serving.ServingModel(
    config_type=Lfm2Config, configs=CONFIGS, init_params=init_params,
    param_axes=param_axes, check_shardable=check_shardable,
    init_cache=init_cache, cache_axes=cache_axes(CONFIGS["lfm2-24b-a2b"]),
    step=paged_step, **step.PAGE_FUNCTIONS,
    slot_state=serving.SlotState(attach=attach_slot_state,
                                 reset=reset_slot_state),
    step_counters=STEP_COUNTERS, one_program=True))
