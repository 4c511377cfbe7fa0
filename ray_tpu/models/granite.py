"""Granite-4.0-H-architecture LM for the serving engine
(``granitemoehybrid`` with no experts): Mamba-2 state-space layers among
a few grouped-query attention layers with no positional term, every layer
ending in a dense SwiGLU, and four published scalars that no other family
here has.

    h = embedding_multiplier * E[token]
    h = h + residual_multiplier * Mixer(RMSNorm_op(h))
    h = h + residual_multiplier * W_down[silu(g) * u],  [g | u] = W_gate_up RMSNorm_ffn(h)
    logits = (E RMSNorm(h)) / logits_scaling            (the head is E, tied)

* Mixer "mamba" (``ssm_heads`` heads of ``ssm_head_dim`` channels,
  ``d_inner`` = heads x head_dim channels, a state of ``ssm_state`` = N
  numbers a channel, ONE group: B and C are shared by all heads), ``u``
  the normed input:
  ``[z | xBC | dt] = W_in u``, widths d_inner | d_inner + 2 N | heads
  (held as ``w_in`` and ``w_dt``: ``_layer_shapes`` says why);
  ``xBC = silu(conv(xBC) + b_conv)``, a causal depthwise convolution of
  ``conv_kernel`` taps over its own channels (zeros before position 0);
  ``[x | B | C] = xBC``, widths d_inner | N | N;
  ``dt = softplus(dt + dt_bias)``, ``a = exp(-exp(A_log) dt)``, one of
  each a head; per head p: ``S_p <- a_p S_p + (dt_p x_p) B^T``,
  ``y_p = S_p C + D_p x_p``;
  ``out = W_out [RMSNorm_{d_inner}(y * silu(z)) * w]``: the gate first,
  then ONE norm over all d_inner channels.
  What a sequence carries from one token to the next is ``S`` of every
  head, in float32, and the convolution's last ``conv_kernel - 1`` inputs:
  a fixed-size state a SLOT, whatever the sequence's length.
* Mixer "attention": q / k / v projections without bias, norm or rotary
  step, causal softmax at scale ``attention_multiplier`` (NOT
  ``head_dim^-1/2``), ``W_o``. Its K and V live in pages.

What it offers the engine (``models/serving.py``): one step over a cache
of three kinds side by side — ``cache["kv"]``, the page pool of the
attention layers only; ``cache["ssm"]``, ONE ``[mamba layers, slots, G,
N, W]`` float32 array (``ops/ssm_scan.py`` says why the channels lie in G
blocks of W along lanes, N along sublanes) that the kernel updates in
place; ``cache["conv"]``, ``[mamba layers, taps - 1, slots, d_inner + 2
N]`` — and a :class:`serving.SlotState` for the last two. The step's
rows and pages are ``models/step.py``'s; what is this family's alone: the
layers are STACKED BY KIND and the step loops over them (a loop over the
whole periods of ``layer_types``, inside it one loop a run of mamba
layers: 40 layers compile as two mamba bodies and one attention body),
indexing a layer of the stacked weights where it lies. ``one_program``,
as the two sparse families: no router amplifies an ulp here, but hundreds
of greedy tokens do (the record below says what the chip showed). A
layer's window in ``cache["conv"]`` is ``[taps - 1, slots, channels]``,
tap-major, as ``step.carried_conv`` takes it and hands it back.

Scope names (``jax.named_scope``; metadata only): ``ssm.proj`` (the
input projection), ``ssm.conv`` (the convolution, and what turns its
output into the recurrence's operands), ``ssm.scan`` (the recurrence),
``ssm.out`` (skip, gate, norm, ``W_out``), ``attn``, ``mlp``, ``embed``,
``lm_head``, ``layers`` round the loops.
"""

from __future__ import annotations

import math
from dataclasses import dataclass
from functools import partial
from typing import Any, Dict, Tuple

import jax
import jax.numpy as jnp

from ..ops.slot_stream import step_plan
from ..ops.ssm_scan import blocks_of, ssm_scan
from . import serving, step
from .common import bulk_key, draw, rms_norm

ATTENTION, MAMBA = "attention", "mamba"
PERIOD = (MAMBA,) * 5 + (ATTENTION,) + (MAMBA,) * 4
# what a step counts (SlotEngine.STEP_COUNTERS): rows whose recurrent state
# the step read and wrote (active decode rows + 1 for a non-empty chunk),
# summed over the mamba layers
STEP_COUNTERS = ("ssm_rows",)


@dataclass(frozen=True)
class GraniteConfig:
    vocab_size: int = 100352
    max_seq: int = 131072
    d_model: int = 2048
    num_heads: int = 32
    num_kv_heads: int = 8
    head_dim: int = 64
    d_mlp: int = 8192             # shared_intermediate_size
    ssm_heads: int = 64
    ssm_head_dim: int = 64
    ssm_state: int = 128          # N
    conv_kernel: int = 4
    layer_types: Tuple[str, ...] = PERIOD * 4
    embedding_multiplier: float = 12.0
    residual_multiplier: float = 0.22
    attention_multiplier: float = 0.015625
    logits_scaling: float = 8.0
    norm_eps: float = 1e-5
    dtype: Any = jnp.bfloat16

    @property
    def num_layers(self) -> int:
        return len(self.layer_types)

    @property
    def d_inner(self) -> int:
        return self.ssm_heads * self.ssm_head_dim

    @property
    def conv_width(self) -> int:
        return self.d_inner + 2 * self.ssm_state

    def __post_init__(self):
        unknown = set(self.layer_types) - {ATTENTION, MAMBA}
        if unknown:
            raise ValueError(f"unknown layer types {sorted(unknown)}")
        blocks_of(self.d_inner)


CONFIGS = {
    # the published Granite-4.0-H-Micro: 36 mamba + 4 attention layers
    "granite-4.0-h-micro": GraniteConfig(),
    # one attention layer among three mamba layers, twice: the CPU tests'
    # size (a state of 128 a channel is the kernel's one size)
    "granite-tiny": GraniteConfig(
        vocab_size=512, max_seq=128, d_model=64, num_heads=4,
        num_kv_heads=2, head_dim=16, d_mlp=128, ssm_heads=8,
        ssm_head_dim=16, ssm_state=16,
        layer_types=(MAMBA, MAMBA, ATTENTION, MAMBA) * 2,
        dtype=jnp.float32),
}


def layout(cfg: GraniteConfig):
    """-> (period, periods, runs): ``layer_types`` as ``periods``
    repetitions of its shortest ``period``, and the period as runs ``(kind,
    first, count)`` of consecutive layers of one kind, ``first`` counted
    among the period's layers of that kind."""
    types, n = cfg.layer_types, cfg.num_layers
    size = next(p for p in range(1, n + 1)
                if n % p == 0 and types == types[:p] * (n // p))
    runs, seen = [], {ATTENTION: 0, MAMBA: 0}
    for kind in types[:size]:
        if runs and runs[-1][0] == kind:
            runs[-1][2] += 1
        else:
            runs.append([kind, seen[kind], 1])
        seen[kind] += 1
    return types[:size], n // size, [tuple(r) for r in runs]


def _layer_shapes(cfg: GraniteConfig, kind: str) -> Dict[str, tuple]:
    """name -> (shape, init) of ONE layer's parameters (``params[kind]``
    holds every layer of the kind stacked along a leading axis); init is
    "ones", "a_log", "dt_bias" or a standard deviation."""
    d, hd, f = cfg.d_model, cfg.head_dim, cfg.d_mlp
    qw, kv = cfg.num_heads * hd, cfg.num_kv_heads * hd
    di, h = cfg.d_inner, cfg.ssm_heads
    # every layer's part reaches the stream times ``residual_multiplier``,
    # and the stream starts at ``embedding_multiplier`` x the table: the
    # draws carry the inverse of each, so that stream and parts have the
    # sizes they have in the families without multipliers (see
    # ``_init_table``)
    out_std = 0.02 / math.sqrt(2 * cfg.num_layers) / cfg.residual_multiplier
    shapes = {"op_norm": ((d,), "ones"), "ffn_norm": ((d,), "ones")}
    if kind == MAMBA:
        shapes.update(
            # the input projection's [z | x | B | C] columns: d_inner,
            # d_inner, N, N; its dt columns (one a head) lie apart and
            # transposed, [heads, d], so that every stack's last axis
            # fills lanes: at 8512 = 66.5 x 128 columns the compiler keeps
            # the stack transposed and copies all of it into the products'
            # layout every step (PERF.md Findings PR 43)
            w_in=((d, di + cfg.conv_width), 0.02), w_dt=((h, d), 0.02),
            # tap j of every one of the x | B | C channels in row j
            conv_k=((cfg.conv_kernel, cfg.conv_width), 0.3),
            conv_b=((cfg.conv_width,), 0.1),
            a_log=((h,), "a_log"), dt_bias=((h,), "dt_bias"),
            d_skip=((h,), "ones"), y_norm=((di,), "ones"),
            w_out=((di, d), out_std))
    else:
        # q and k are drawn so that a score has deviation 2 at the model's
        # scale, neither a uniform softmax nor a one-hot one: at 0.02 it
        # would be 0.1 at the published 1/64 (a scale meant for q and k
        # that training has aligned), the softmax uniform whatever scales
        # it, and an attention layer's part of the stream the mean of its
        # values: a thirtieth of a mamba layer's
        qk_std = math.sqrt(2.0 / (cfg.attention_multiplier * math.sqrt(hd)
                                  * d))
        shapes.update(wq=((d, qw), qk_std), wk=((d, kv), qk_std),
                      wv=((d, kv), 0.02), wo=((qw, d), out_std))
    shapes.update(w_gate_up=((d, 2 * f), 0.02), w_down=((f, d), out_std))
    return shapes


_FLOAT32 = ("a_log", "dt_bias", "d_skip")  # as the family keeps them


def param_axes(cfg: GraniteConfig = None) -> Dict:
    cfg = cfg or CONFIGS["granite-4.0-h-micro"]
    return {"wte": (None, None), "final_norm": (None,),
            **{kind: {k: (None,) * (1 + len(shape)) for k, (shape, _) in
                      _layer_shapes(cfg, kind).items()}
               for kind in (MAMBA, ATTENTION)}}


@partial(jax.jit, static_argnums=(1, 2))
def _init_stack(key, cfg: GraniteConfig, kind: str):
    n = cfg.layer_types.count(kind)
    shapes = _layer_shapes(cfg, kind)
    keys = jax.random.split(bulk_key(key), len(shapes))
    return {name: draw(k, (n,) + shape, init,
                        jnp.float32 if name in _FLOAT32 else cfg.dtype)
            for k, (name, (shape, init)) in zip(keys, shapes.items())}


@partial(jax.jit, static_argnums=(1,))
def _init_table(key, cfg: GraniteConfig):
    """The embedding, which is the head too, at 0.02 /
    ``embedding_multiplier``: drawn at 0.02 the tied head would read back
    ``multiplier x |E[t]|^2`` for the token just fed, several deviations
    above every other logit, and a greedy request would repeat its last
    token whatever the layers compute."""
    return draw(bulk_key(key), (cfg.vocab_size, cfg.d_model),
                 0.02 / cfg.embedding_multiplier, cfg.dtype)


def init_params(key, cfg: GraniteConfig) -> Tuple[Dict, Dict]:
    """Seeded weights in ``cfg.dtype`` (``a_log``, ``dt_bias`` and ``D`` in
    float32), every layer of a kind stacked, one jitted program a kind;
    the embedding is the head too."""
    keys = jax.random.split(key, 3)
    params = {"wte": _init_table(keys[0], cfg),
              "final_norm": jnp.ones((cfg.d_model,), cfg.dtype),
              MAMBA: _init_stack(keys[1], cfg, MAMBA),
              ATTENTION: _init_stack(keys[2], cfg, ATTENTION)}
    return params, param_axes(cfg)


# -- the cache: pages for the attention layers, state a slot for the rest ----

def init_cache(cfg: GraniteConfig, num_pages: int, page_size: int):
    return step.init_pool(cfg.layer_types.count(ATTENTION), cfg, num_pages,
                          page_size)


def attach_slot_state(cfg: GraniteConfig, cache, num_slots: int):
    """The pages' tree with the mamba layers' state beside them, zero for
    a sequence that has not begun: every channel's N numbers in float32,
    all layers in one array, and the layers' last ``conv_kernel - 1``
    inputs of their convolution in another."""
    n = cfg.layer_types.count(MAMBA)
    g, w = blocks_of(cfg.d_inner)
    return dict(
        cache,
        ssm=jnp.zeros((n, num_slots, g, cfg.ssm_state, w), jnp.float32),
        conv=jnp.zeros((n, cfg.conv_kernel - 1, num_slots, cfg.conv_width),
                       cfg.dtype))


def reset_slot_state(cache, slots):
    """Those slots' state zeroed (jit with the cache donated)."""
    return dict(cache, ssm=cache["ssm"].at[:, slots].set(0),
                conv=cache["conv"].at[:, :, slots].set(0))


CACHE_AXES = {"kv": step.PAGED_KV_AXES, "ssm": (None,) * 5, "conv": (None,) * 4}


def check_shardable(cfg: GraniteConfig, tp: int) -> None:
    if tp > 1:
        raise ValueError(
            "the granite family serves on one chip: the model fits it "
            "whole and no rule shards the state-space layers yet")


# -- the operators ---------------------------------------------------------------

def _rounded(x, dtype):
    """float32 ``x`` as a product takes it: rounded to the model's
    ``dtype``, by an operation the compiler keeps. A bare ``astype`` in
    front of a product, or a product's output in bfloat16 in front of
    float32 arithmetic, is a rounding XLA is free to skip where it fuses
    the two (``xla_allow_excess_precision``), and it fuses differently in
    the engine's two step programs: on the chip a decode row's logits
    then differed by a bfloat16 ulp between them, and a greedy request of
    hundreds of tokens gave other tokens when it ran beside other rows
    (PERF.md Findings PR 43). Rounded here, the cast that follows is
    exact, kept or not."""
    if jnp.dtype(dtype) == jnp.float32:
        return x
    info = jnp.finfo(dtype)
    return jax.lax.reduce_precision(x, info.nexp, info.nmant).astype(dtype)


def _product(u, w):
    """``u @ w`` of operands in the model's dtype, accumulated and
    returned in float32: where its result is rounded is the caller's to
    say (:func:`_rounded`), not the compiler's."""
    return jnp.dot(u, w.astype(u.dtype), preferred_element_type=jnp.float32)


def _gated_norm(y, z, scale, eps):
    """The gate first, then ONE norm over all of a row's channels."""
    return rms_norm(y * jax.nn.silu(z), scale, eps)


def mamba(u, ssm_state, conv_state, p, cfg: GraniteConfig, layer, rows,
          plan):
    """One mamba layer's mixer on a step's rows u [R, d] -> (out [R, d],
    the layers' states, the layers' conv states). ``layer`` indexes both
    states' leading axis; ``rows`` is the step's ``step.StepRows`` and
    ``plan`` its ``step_plan(rows.valid, rows.chunk_at)``, the same for
    every layer."""
    f32, r = jnp.float32, u.shape[0]
    di, n, h = cfg.d_inner, cfg.ssm_state, cfg.ssm_heads
    with jax.named_scope("ssm.proj"):
        # the projection's outputs are values of the model's dtype (what
        # the convolution's window keeps of them is exact), in float32
        proj = _rounded(_product(u, p["w_in"]), u.dtype).astype(f32)
        z, xbc = proj[:, :di], proj[:, di:]
        dt = _rounded(jnp.einsum("rd,hd->rh", u, p["w_dt"].astype(u.dtype),
                                 preferred_element_type=f32),
                      u.dtype).astype(f32)
    with jax.named_scope("ssm.conv"):
        # a layer's window as it lies in the cache: [tap, slot, channel]
        conv, window = step.carried_conv(
            xbc, jax.lax.dynamic_index_in_dim(conv_state, layer, 0,
                                              keepdims=False),
            p["conv_k"].astype(f32), rows.b, rows.valid, rows.chunk_at,
            bias=p["conv_b"].astype(f32))
        conv_state = jax.lax.dynamic_update_index_in_dim(conv_state, window,
                                                         layer, 0)
        xbc = jax.nn.silu(conv)
        x = xbc[:, :di].reshape(r, h, cfg.ssm_head_dim)
        bc = xbc[:, di:].reshape(r, 2, n)
        dt = jax.nn.softplus(dt + p["dt_bias"])                  # [R, H]
        decay = jnp.exp(-jnp.exp(p["a_log"]) * dt)
        blocks = (r,) + blocks_of(di)
        dx = (x * dt[:, :, None]).reshape(blocks)
        decay = jnp.broadcast_to(decay[:, :, None], x.shape).reshape(blocks)
    with jax.named_scope("ssm.scan"):
        # the step's rows as they are: the decode rows, one token of slot
        # i each, then the chunk; a parked row and an empty chunk are not
        # in the step
        y, ssm_state = ssm_scan(ssm_state, layer, plan, dx, decay, bc)
    with jax.named_scope("ssm.out"):
        # The kernel leaves the y of a row that is not in the step as it
        # found the memory, which may hold NaN, and such a row must stay
        # finite: the paged-attention kernel lays a chunk's new K and V
        # into their pages by a 0/1 product over ALL the chunk's tokens,
        # and 0 x NaN of a tail token is NaN in every live one (on the
        # chip, at 64 slots, every request but a few read NaN logits).
        y = jnp.where(rows.live[:, None, None], y, 0.0)
        y = y.reshape(x.shape) + p["d_skip"][:, None] * x
        y = _gated_norm(y.reshape(r, di), z, p["y_norm"], cfg.norm_eps)
        out = _product(_rounded(y, u.dtype), p["w_out"])
    return out, ssm_state, conv_state


def _mlp(x, p, cfg: GraniteConfig):
    """x [R, d] float32 -> the SwiGLU's output [R, d] float32."""
    u = _rounded(rms_norm(x, p["ffn_norm"], cfg.norm_eps), cfg.dtype)
    hidden = _product(u, p["w_gate_up"])
    gate, up = hidden[:, :cfg.d_mlp], hidden[:, cfg.d_mlp:]
    return _product(_rounded(jax.nn.silu(gate) * up, cfg.dtype),
                    p["w_down"])


def _lm_head(x, params, cfg: GraniteConfig):
    """[R, d] hidden states -> [R, vocab] float32 logits: the embedding
    is the head, and the logits are divided by ``logits_scaling``."""
    with jax.named_scope("lm_head"):
        x = _rounded(rms_norm(x, params["final_norm"], cfg.norm_eps),
                     cfg.dtype)
        return jnp.einsum("bd,vd->bv", x, params["wte"].astype(cfg.dtype),
                          preferred_element_type=jnp.float32) \
            / cfg.logits_scaling


def _layer_of(stack, i):
    """Layer i of a kind's stacked parameters, each where it lies."""
    return jax.tree.map(lambda w: jax.lax.dynamic_index_in_dim(
        w, i, 0, keepdims=False), stack)


def paged_step(params, cache, tables, tokens, pos, chunk, cfg: GraniteConfig,
               page_size: int, rules=None):
    """One continuous-batching step: the contract of
    ``models/serving.py``'s ``step`` over the rows of
    ``step.step_rows``, with a fourth result: the counts
    :data:`STEP_COUNTERS` names."""
    rows = step.step_rows(tables, tokens, pos, chunk, cfg.max_seq)
    f32 = jnp.float32
    # The residual stream is float32, as ``models/solar.py``'s is and for
    # its reason: a step's rows are few, and a bfloat16 stream rounds
    # every layer's sum by 2^-9 of the STREAM. Every product takes its
    # input rounded to the model's dtype, as the weights are, adds up in
    # float32 and hands on float32: each rounding is written out
    # (``_rounded``), none is left to where the compiler fuses.
    with jax.named_scope("embed"):
        x = params["wte"][rows.packed()].astype(f32) \
            * cfg.embedding_multiplier
    # The kernel scales scores by head_dim^-1/2; the model's scale is
    # ``attention_multiplier``, so q carries the ratio (2^-3 for the
    # published 1/64 at a head of 64: exact in any float dtype).
    q_scale = cfg.attention_multiplier * math.sqrt(cfg.head_dim)

    def attention(u, kv, p, layer):
        """u [R, d] -> (out [R, d], pool). No positional term: the causal
        order of the pages is all the order there is."""
        parts = rows.parts(
            _rounded(_product(u, p["wq"]) * q_scale, u.dtype),
            _rounded(_product(u, p["wk"]), u.dtype),
            _rounded(_product(u, p["wv"]), u.dtype), cfg.num_heads)
        o, kv = step.attend(rows, parts, kv, layer, cfg, page_size, rules)
        return _product(o.astype(u.dtype), p["wo"]), kv

    # which rows' states the step reads and writes: once a step, for
    # every mamba layer
    with jax.named_scope("ssm.scan"):
        plan = step_plan(rows.valid, rows.chunk_at)

    def layer_step(carry, kind, i):
        """Layer i of ``kind`` (counted among the kind's layers)."""
        x, kv, ssm, conv = carry
        p = _layer_of(params[kind], i)
        u = _rounded(rms_norm(x, p["op_norm"], cfg.norm_eps), cfg.dtype)
        if kind == MAMBA:
            out, ssm, conv = mamba(u, ssm, conv, p, cfg, i, rows, plan)
        else:
            with jax.named_scope("attn"):
                out, kv = attention(u, kv, p, i)
        x = x + cfg.residual_multiplier * out
        with jax.named_scope("mlp"):
            x = x + cfg.residual_multiplier * _mlp(x, p, cfg)
        return x, kv, ssm, conv

    period, periods, runs = layout(cfg)
    per_kind = {kind: period.count(kind) for kind in (ATTENTION, MAMBA)}

    def period_step(carry, j):
        for kind, first, count in runs:
            at = j * per_kind[kind] + first
            if count == 1:
                carry = layer_step(carry, kind, at)
            else:
                carry, _ = jax.lax.scan(
                    lambda carry, i, kind=kind: (
                        layer_step(carry, kind, i), None),
                    carry, at + jnp.arange(count, dtype=jnp.int32))
        return carry

    carry = (x, cache["kv"], cache["ssm"], cache["conv"])
    with jax.named_scope("layers"):  # the states are the carry, never scanned
        if periods == 1:
            carry = period_step(carry, jnp.int32(0))
        else:
            carry, _ = jax.lax.scan(
                lambda carry, j: (period_step(carry, j), None), carry,
                jnp.arange(periods, dtype=jnp.int32))
    x, kv, ssm, conv = carry
    counts = jnp.reshape(
        rows.state_rows() * cfg.layer_types.count(MAMBA), (1,))
    head = partial(_lm_head, params=params, cfg=cfg)
    return *step.logits_of(rows, x, head), {"kv": kv, "ssm": ssm, "conv": conv}, counts


# ``param_axes()`` and ``CACHE_AXES`` are read only under a mesh, which
# ``check_shardable`` refuses for now.
serving.register(serving.ServingModel(
    config_type=GraniteConfig, configs=CONFIGS, init_params=init_params,
    param_axes=param_axes, check_shardable=check_shardable,
    init_cache=init_cache, cache_axes=CACHE_AXES, step=paged_step,
    **step.PAGE_FUNCTIONS,
    slot_state=serving.SlotState(attach=attach_slot_state,
                                 reset=reset_slot_state),
    step_counters=STEP_COUNTERS,
    # No router picks another expert on an ulp, but a greedy request of
    # hundreds of tokens does amplify one: its argmax turns on a near-tie
    # every few hundred tokens and every later token follows. The engine's
    # two programs give a decode row's float32 sums in another order (64
    # rows against 64 + a lane), so a request's tokens depended on what
    # rode beside it (on the chip the repeated request of the benchmark
    # gave other tokens in both of two runs: PERF.md Findings PR 43).
    one_program=True))
