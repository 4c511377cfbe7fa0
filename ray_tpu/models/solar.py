"""Solar-Open2-architecture LM for the serving engine: Kimi-Delta-
Attention (KDA) layers, whose memory is a matrix a head that a delta rule
rewrites token by token, among gated grouped-query attention layers with
no positional term, and in every layer sigmoid-routed sparse experts
beside a shared expert — held here as ONE CHIP'S SHARE of a deployment:
some of a layer's routed experts, a slice of the vocabulary, some of the
layers.

The layer, ``u`` the normed input (RMSNorm, ``norm_eps``):

    h = x + Op(RMSNorm_op(x));  y = h + FFN(RMSNorm_ffn(h))

* Op "kda" (``kda_heads`` heads, ``d_k = d_v = kda_head_dim``), per head:
  ``q, k, v = SiLU(conv(W_q u), conv(W_k u), conv(W_v u))``, each a causal
  depthwise convolution of ``conv_kernel`` taps over its own channels
  (zeros before position 0); q and k L2-normalised over the head's lanes
  (``x / sqrt(sum x^2 + 1e-6)``), q scaled by ``d_k^-1/2``.
  ``g = -exp(A_log) * softplus(W_f2 (W_f1 u) + dt_bias)``, a log decay
  for every key channel (the low-rank form, ``kda_rank`` wide);
  ``b = 2 sigmoid(w_b u)``, one number a head (the 2 lets the transition
  have negative eigenvalues).
  ``S_t = (I - b k k^T) Diag(exp g) S_{t-1} + b k v^T;  o_t = S_t^T q``.
  ``out = W_o [RMSNorm_head(o) * sigmoid(W_g2 (W_g1 u))]``.
  What a sequence carries from one token to the next is ``S`` of every
  head, in float32, and the last ``conv_kernel - 1`` inputs of the three
  convolutions: a fixed-size state a SLOT, whatever the sequence's length.
* Op "gqa": q / k / v projections without bias, norm or rotary step,
  causal softmax at scale ``head_dim^-1/2``, ``out = W_o [o *
  sigmoid(W_gate u)]``, the gate one number an element. Its K and V live
  in pages.
* FFN, every layer: ``models/moe.py``'s router over ALL ``num_experts``
  published experts, the grouped product over the ``experts_held`` here,
  plus the shared experts' SwiGLU on every row.

The share. ``experts_held = (first, count)``: the layer's weights hold
that run of the routed experts and a pick of any other costs nothing and
adds nothing (what the other chips of the deployment would add is left
out, as it is in the reference given the same share). ``vocab_held =
(first, count)``: the embedding and the untied head hold those rows; a
sliced vocabulary is a smaller vocabulary, so token ids are 0 .. count -
1 and logits are over the slice. ``layer_types`` lists the layers kept.
``None`` for either share means all of it.

What it offers the engine (``models/serving.py``): one step over a cache
of three kinds side by side — ``cache["kv"]``, the page pool of the GQA
layers only; ``cache["kda"]``, ONE ``[kda layers, slots, heads, d_k,
d_v]`` float32 array that ``ops/delta_rule.py`` updates in place; and
``cache["conv"]``, one ``[taps - 1, slots, 3 x heads x d_k]`` array a KDA
layer (tap-major: ``step.carried_conv`` says why) — and a
:class:`serving.SlotState` for the last two. The step's rows and pages
are ``models/step.py``'s; the layers are unrolled, and the family is
``one_program`` (a router amplifies an ulp).

Scope names (``jax.named_scope``; metadata only): ``kda.proj`` (every
projection of u), ``kda.conv``, ``kda.scan`` (the recurrence),
``kda.out`` (head norm, gate, ``W_o``), ``attn``, ``moe.route``,
``moe.experts``, ``moe.shared``, ``embed``, ``lm_head``.
"""

from __future__ import annotations

import math
from dataclasses import dataclass
from functools import partial
from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp

from ..ops.delta_rule import delta_rule
from ..ops.slot_stream import step_plan
from . import serving, step
from .common import bulk_key, draw, rms_norm
from .moe import EXPERT_COUNTERS, experts_ffn, route, shared_ffn

GQA, KDA = "gqa", "kda"
PERIOD = (GQA, KDA, KDA, KDA)
# what a step counts, in this order (SlotEngine.STEP_COUNTERS): the expert
# layers' counts over the HELD experts; every valid row's picks, held or
# not; rows whose recurrent state the step read and wrote (active decode
# rows + 1 for a non-empty chunk) — each summed over the layers that count
STEP_COUNTERS = EXPERT_COUNTERS + ("expert_picks", "kda_rows")


@dataclass(frozen=True)
class SolarConfig:
    vocab_size: int = 196608
    max_seq: int = 2048
    d_model: int = 4096
    num_heads: int = 64
    num_kv_heads: int = 8
    head_dim: int = 128
    kda_heads: int = 64
    kda_head_dim: int = 128
    kda_rank: int = 128           # width of the low-rank decay / gate
    conv_kernel: int = 4
    layer_types: Tuple[str, ...] = PERIOD * 12
    d_expert: int = 1280          # moe_intermediate_size
    num_experts: int = 320        # n_routed_experts, the router's width
    num_experts_per_tok: int = 8
    num_shared_experts: int = 1
    norm_topk_prob: bool = True
    use_expert_bias: bool = True
    routed_scaling_factor: float = 1.0
    norm_eps: float = 1e-5
    dtype: Any = jnp.bfloat16
    # this chip's share: (first, count), None = all
    experts_held: Optional[Tuple[int, int]] = None
    vocab_held: Optional[Tuple[int, int]] = None

    @property
    def num_layers(self) -> int:
        return len(self.layer_types)

    @property
    def experts_here(self) -> int:
        return self.experts_held[1] if self.experts_held else self.num_experts

    @property
    def vocab_here(self) -> int:
        return self.vocab_held[1] if self.vocab_held else self.vocab_size

    @property
    def kda_width(self) -> int:
        return self.kda_heads * self.kda_head_dim

    def __post_init__(self):
        unknown = set(self.layer_types) - {GQA, KDA}
        if unknown:
            raise ValueError(f"unknown layer types {sorted(unknown)}")
        if self.num_experts_per_tok > self.num_experts:
            raise ValueError("more experts a token than experts")
        for name, held, whole in (
                ("experts_held", self.experts_held, self.num_experts),
                ("vocab_held", self.vocab_held, self.vocab_size)):
            if held is not None and not (
                    0 <= held[0] and held[1] > 0
                    and held[0] + held[1] <= whole):
                raise ValueError(f"{name} {held} is no run of 0 .. {whole}")


CONFIGS = {
    # the published Solar-Open2-250B: 12 GQA + 36 KDA layers, no chip's
    # share taken
    "solar-open2-250b-whole": SolarConfig(),
    # one whole period, 4 of 16 experts held: the CPU tests' size
    "solar-tiny": SolarConfig(
        vocab_size=512, max_seq=128, d_model=64, num_heads=4,
        num_kv_heads=2, head_dim=16, kda_heads=4, kda_head_dim=16,
        kda_rank=16, layer_types=PERIOD, d_expert=32, num_experts=16,
        num_experts_per_tok=4, experts_held=(4, 4), vocab_held=(128, 256),
        dtype=jnp.float32),
}


def _layer_shapes(cfg: SolarConfig, op: str) -> Dict[str, tuple]:
    """name -> (shape, init) of one layer's parameters; init is "ones",
    "bias", "a_log", "dt_bias" or a standard deviation."""
    d, hd = cfg.d_model, cfg.head_dim
    qw, kv = cfg.num_heads * hd, cfg.num_kv_heads * hd
    kw, rank = cfg.kda_width, cfg.kda_rank
    f, fs = cfg.d_expert, cfg.d_expert * cfg.num_shared_experts
    out_std = 0.02 / math.sqrt(2 * cfg.num_layers)
    shapes = {"op_norm": ((d,), "ones"), "ffn_norm": ((d,), "ones")}
    if op == KDA:
        shapes.update(
            # W_q, W_k, W_v side by side: columns [:kw], [kw:2kw], [2kw:]
            w_qkv=((d, 3 * kw), 0.02),
            # tap j of every one of those channels in row j
            conv_k=((cfg.conv_kernel, 3 * kw), 0.3),
            w_f1=((d, rank), 0.02), w_f2=((rank, kw), 0.02),
            a_log=((cfg.kda_heads,), "a_log"), dt_bias=((kw,), "dt_bias"),
            w_b=((d, cfg.kda_heads), 0.02),
            w_g1=((d, rank), 0.02), w_g2=((rank, kw), 0.02),
            o_norm=((cfg.kda_head_dim,), "ones"),
            wo=((kw, d), out_std))
    else:
        shapes.update(
            wq=((d, qw), 0.02), wk=((d, kv), 0.02), wv=((d, kv), 0.02),
            w_gate=((d, qw), 0.02), wo=((qw, d), out_std))
    shapes.update(
        router=((d, cfg.num_experts), 0.02),
        expert_bias=((cfg.num_experts,), "bias"),
        # the HELD experts' W1 (gate) and W3 (up) side by side
        w_gate_up=((cfg.experts_here, d, 2 * f), 0.02),
        w_down=((cfg.experts_here, f, d), out_std),
        shared_gate_up=((d, 2 * fs), 0.02),
        shared_down=((fs, d), out_std))
    return shapes


def param_axes(cfg: SolarConfig = None) -> Dict:
    cfg = cfg or CONFIGS["solar-open2-250b-whole"]
    return {"wte": (None, None), "lm_head": (None, None),
            "final_norm": (None,),
            "layers": [{k: (None,) * len(shape) for k, (shape, _) in
                        _layer_shapes(cfg, op).items()}
                       for op in cfg.layer_types]}


@partial(jax.jit, static_argnums=(1, 2))
def _init_layer(key, cfg: SolarConfig, op: str):
    shapes = _layer_shapes(cfg, op)
    keys = jax.random.split(bulk_key(key), len(shapes))
    return {name: draw(k, shape, init, cfg.dtype)
            for k, (name, (shape, init)) in zip(keys, shapes.items())}


@partial(jax.jit, static_argnums=(1,))
def _init_table(key, cfg: SolarConfig):
    return draw(bulk_key(key), (cfg.vocab_here, cfg.d_model), 0.02,
                 cfg.dtype)


def init_params(key, cfg: SolarConfig) -> Tuple[Dict, Dict]:
    """Seeded weights in ``cfg.dtype`` (``a_log`` and ``dt_bias`` in
    float32), one jitted program a kind of layer, the held rows of the
    embedding and of the untied head, the held experts of every layer."""
    keys = jax.random.split(key, cfg.num_layers + 2)
    params = {
        "wte": _init_table(keys[0], cfg),
        "lm_head": _init_table(keys[1], cfg),
        "final_norm": jnp.ones((cfg.d_model,), cfg.dtype),
        "layers": [_init_layer(k, cfg, op)
                   for k, op in zip(keys[2:], cfg.layer_types)],
    }
    return params, param_axes(cfg)


# -- the cache: pages for the GQA layers, state a slot for the KDA layers ----

def init_cache(cfg: SolarConfig, num_pages: int, page_size: int):
    return step.init_pool(cfg.layer_types.count(GQA), cfg, num_pages,
                          page_size)


def attach_slot_state(cfg: SolarConfig, cache, num_slots: int):
    """The pages' tree with the KDA layers' state beside them, zero for a
    sequence that has not begun: every head's matrix in float32, all
    layers in one array, and a layer's last ``conv_kernel - 1`` inputs of
    its three convolutions."""
    n, hd = cfg.layer_types.count(KDA), cfg.kda_head_dim
    return dict(
        cache,
        kda=jnp.zeros((n, num_slots, cfg.kda_heads, hd, hd), jnp.float32),
        conv=[jnp.zeros((cfg.conv_kernel - 1, num_slots, 3 * cfg.kda_width),
                        cfg.dtype) for _ in range(n)])


def reset_slot_state(cache, slots):
    """Those slots' state zeroed (jit with the cache donated)."""
    return dict(cache, kda=cache["kda"].at[:, slots].set(0),
                conv=[c.at[:, slots].set(0) for c in cache["conv"]])


def cache_axes(cfg: SolarConfig) -> Dict:
    n = cfg.layer_types.count(KDA)
    return {"kv": step.PAGED_KV_AXES, "kda": (None,) * 5,
            "conv": [(None, None, None)] * n}


def check_shardable(cfg: SolarConfig, tp: int) -> None:
    if tp > 1:
        raise ValueError(
            "the solar family serves one chip's share on one chip: the "
            "chips that share a layer each run this step on their own "
            "batch, and no rule shards it further yet")


# -- the operators ---------------------------------------------------------------

def _l2_norm(x):
    return x * jax.lax.rsqrt(jnp.sum(x * x, axis=-1, keepdims=True) + 1e-6)


def _kda_project(u, p, cfg: SolarConfig):
    """Every projection of a KDA layer's input u [.., d] -> (qkv [.., 3
    kw] before the convolutions, log decay g [.., H, hd] float32, beta
    [.., H] float32, output gate [.., kw] float32)."""
    f32, lead = jnp.float32, u.shape[:-1]
    qkv = u @ p["w_qkv"].astype(u.dtype)
    decay = ((u @ p["w_f1"].astype(u.dtype))
             @ p["w_f2"].astype(u.dtype)).astype(f32)
    g = -jnp.exp(p["a_log"].astype(f32))[:, None] * jax.nn.softplus(
        (decay + p["dt_bias"].astype(f32)).reshape(
            lead + (cfg.kda_heads, cfg.kda_head_dim)))
    beta = 2.0 * jax.nn.sigmoid((u @ p["w_b"].astype(u.dtype)).astype(f32))
    gate = jax.nn.sigmoid(((u @ p["w_g1"].astype(u.dtype))
                           @ p["w_g2"].astype(u.dtype)).astype(f32))
    return qkv, g, beta, gate


def _kda_heads(conv, cfg: SolarConfig):
    """The convolutions' outputs [.., 3 kw] float32 -> q, k, v [.., H,
    hd]: SiLU, q and k L2-normalised a head, q scaled."""
    hd = cfg.kda_head_dim
    q, k, v = (x.reshape(x.shape[:-1] + (cfg.kda_heads, hd))
               for x in jnp.split(jax.nn.silu(conv), 3, axis=-1))
    return _l2_norm(q) * hd ** -0.5, _l2_norm(k), v


def _kda_output(o, gate, p, cfg: SolarConfig, dtype):
    """o [.., H, dv] float32 -> the layer's output [.., d]: RMSNorm over
    each head's values, the gate, ``W_o``."""
    o = rms_norm(o, p["o_norm"], cfg.norm_eps).reshape(gate.shape) * gate
    return o.astype(dtype) @ p["wo"].astype(dtype)


def kda(u, scan_state, conv_state, p, cfg: SolarConfig, layer, rows, plan):
    """One KDA layer on a step's rows u [N, d] -> (out [N, d], the
    layers' matrix states, this layer's conv state). ``layer`` indexes
    ``scan_state``'s leading axis; ``rows`` is the step's
    ``step.StepRows`` and ``plan`` its ``step_plan(rows.valid,
    rows.chunk_at)``, the same for every layer."""
    with jax.named_scope("kda.proj"):
        qkv, g, beta, gate = _kda_project(u, p, cfg)
    with jax.named_scope("kda.conv"):
        conv, conv_state = step.carried_conv(
            qkv, conv_state, p["conv_k"].astype(jnp.float32), rows.b,
            rows.valid, rows.chunk_at)
        q, k, v = _kda_heads(conv, cfg)
    with jax.named_scope("kda.scan"):
        # the step's rows as they are: the decode rows, one token of slot
        # i each, then the chunk; a parked row and an empty chunk are not
        # in the step
        o, scan_state = delta_rule(scan_state, layer, plan, q, k, v, g, beta)
    with jax.named_scope("kda.out"):
        out = _kda_output(o, gate, p, cfg, u.dtype)
    return out, scan_state, conv_state


def _gqa_gated(o, u, p):
    """y = W_o [o * sigmoid(W_gate u)], the gate one number an element."""
    gate = jax.nn.sigmoid(
        (u @ p["w_gate"].astype(u.dtype)).astype(jnp.float32))
    return (o.astype(jnp.float32) * gate).astype(u.dtype) @ p["wo"].astype(
        u.dtype)


def _lm_head(x, params, cfg: SolarConfig):
    """[N, d] hidden states -> [N, held vocabulary] float32 logits."""
    with jax.named_scope("lm_head"):
        x = rms_norm(x, params["final_norm"], cfg.norm_eps).astype(cfg.dtype)
        return jnp.einsum("bd,vd->bv", x,
                          params["lm_head"].astype(cfg.dtype),
                          preferred_element_type=jnp.float32)


def paged_step(params, cache, tables, tokens, pos, chunk, cfg: SolarConfig,
               page_size: int, rules=None):
    """One continuous-batching step: the contract of
    ``models/serving.py``'s ``step`` over the rows of
    ``step.step_rows``, with a fourth result: the counts
    :data:`STEP_COUNTERS` names."""
    rows = step.step_rows(tables, tokens, pos, chunk, cfg.max_seq)
    # The residual stream is float32: a step's rows are few (B + C), so
    # it costs nothing beside the weights' read, and a bfloat16 stream
    # rounds every layer's sum by 2^-9 of the STREAM, more than the
    # products' own rounding adds (PERF.md Findings PR 39). Every product
    # takes its input rounded to the model's dtype, as the weights are.
    with jax.named_scope("embed"):
        x = params["wte"][rows.packed()].astype(jnp.float32)

    def attention(u, kv, p, layer):
        """u [N, d] -> (out [N, d], pool). No positional term: the causal
        order of the pages is all the order there is."""
        parts = rows.parts(u @ p["wq"].astype(u.dtype),
                           u @ p["wk"].astype(u.dtype),
                           u @ p["wv"].astype(u.dtype), cfg.num_heads)
        o, kv = step.attend(rows, parts, kv, layer, cfg, page_size, rules)
        return _gqa_gated(o, u, p), kv

    # which rows' matrix states the step reads and writes: once a step,
    # for every KDA layer
    with jax.named_scope("kda.scan"):
        plan = step_plan(rows.valid, rows.chunk_at)
    kv, scan, conv = cache["kv"], cache["kda"], list(cache["conv"])
    counts = jnp.zeros((len(EXPERT_COUNTERS),), jnp.int32)
    n_gqa = n_kda = 0
    for p, op in zip(params["layers"], cfg.layer_types):
        u = rms_norm(x, p["op_norm"], cfg.norm_eps).astype(cfg.dtype)
        if op == KDA:
            out, scan, conv[n_kda] = kda(u, scan, conv[n_kda], p, cfg,
                                         jnp.int32(n_kda), rows, plan)
            n_kda += 1
        else:
            with jax.named_scope("attn"):
                out, kv = attention(u, kv, p, jnp.int32(n_gqa))
            n_gqa += 1
        x = x + out.astype(jnp.float32)
        with jax.named_scope("moe.route"):
            u = rms_norm(x, p["ffn_norm"], cfg.norm_eps)
            experts, weights = route(u, p, cfg)
        u = u.astype(cfg.dtype)
        with jax.named_scope("moe.experts"):
            out, layer_counts = experts_ffn(u, experts, weights, rows.live,
                                            p, cfg, held=cfg.experts_held)
        with jax.named_scope("moe.shared"):
            out = out + shared_ffn(u, p)
        x = x + out.astype(jnp.float32)
        counts = counts + layer_counts
    n_rows = rows.live.sum().astype(jnp.int32)
    counts = jnp.concatenate([counts, jnp.stack([
        n_rows * cfg.num_experts_per_tok * cfg.num_layers,
        rows.state_rows() * n_kda])])
    head = partial(_lm_head, params=params, cfg=cfg)
    return *step.logits_of(rows, x, head), {"kv": kv, "kda": scan, "conv": conv}, counts


# ``param_axes()`` and ``cache_axes`` are read only under a mesh, which
# ``check_shardable`` refuses for now.
serving.register(serving.ServingModel(
    config_type=SolarConfig, configs=CONFIGS, init_params=init_params,
    param_axes=param_axes, check_shardable=check_shardable,
    init_cache=init_cache,
    cache_axes=cache_axes(CONFIGS["solar-open2-250b-whole"]),
    step=paged_step, **step.PAGE_FUNCTIONS,
    slot_state=serving.SlotState(attach=attach_slot_state,
                                 reset=reset_slot_state),
    step_counters=STEP_COUNTERS, one_program=True))
