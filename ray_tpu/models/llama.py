"""Llama-architecture LM: RMSNorm, RoPE, GQA, SwiGLU. Same pure-pytree +
logical-axes design as ``gpt2.py``. Three parts: the training forward,
the plain dense-cache reference of decoding, and what the serving engine
runs — one step over a paged KV cache, offered through
``models/serving.py`` (static shapes throughout).
"""

from __future__ import annotations

import math
from dataclasses import dataclass
from functools import partial
from typing import Any, Dict, Tuple

import jax
import jax.numpy as jnp

from ..ops.attention import attention as attention_op
from ..parallel.sharding import (constrain, current_mesh, mesh_axes_for,
                                 spec_for)
from . import serving, step
from .common import (cross_entropy_loss, rms_norm, rope_lane_tables,
                     rope_lanes, truncated_normal)


@dataclass(frozen=True)
class LlamaConfig:
    vocab_size: int = 32000
    max_seq: int = 2048
    num_layers: int = 32
    num_heads: int = 32
    num_kv_heads: int = 32
    d_model: int = 4096
    d_mlp: int = 11008
    rope_theta: float = 10000.0
    dtype: Any = jnp.bfloat16
    remat: bool = True

    @property
    def head_dim(self) -> int:
        return self.d_model // self.num_heads


CONFIGS = {
    "llama2-7b": LlamaConfig(),
    "llama-tiny": LlamaConfig(vocab_size=512, max_seq=128, num_layers=2,
                              num_heads=4, num_kv_heads=2, d_model=64,
                              d_mlp=172, dtype=jnp.float32, remat=False),
    "llama2-13b": LlamaConfig(num_layers=40, num_heads=40, num_kv_heads=40,
                              d_model=5120, d_mlp=13824),
    # TinyLlama-1.1B geometry — the serve-bench model: fits one v5e chip
    # in bf16 (~2.2GB params) with an 8-slot KV cache to spare.
    "llama-1b": LlamaConfig(num_layers=22, num_heads=32, num_kv_heads=4,
                            d_model=2048, d_mlp=5632, max_seq=2048),
}


def param_axes() -> Dict:
    """Logical-axis tree matching :func:`init_params`' pytree — the
    input to ``parallel.sharding.place``/``shardings_for`` when placing
    params on a mesh (training AND the tp-sharded serving engine)."""
    return {
        "wte": ("vocab", "embed"),
        "blocks": {
            "attn_norm": ("layers", None),
            "wq": ("layers", "embed", "qkv"),
            "wk": ("layers", "embed", "kv"),
            "wv": ("layers", "embed", "kv"),
            "wo": ("layers", "qkv", "embed"),
            "ffn_norm": ("layers", None),
            "w_gate": ("layers", "embed", "mlp"),
            "w_up": ("layers", "embed", "mlp"),
            "w_down": ("layers", "mlp", "embed"),
        },
        "final_norm": (None,),
    }


def init_params(key, cfg: LlamaConfig) -> Tuple[Dict, Dict]:
    keys = jax.random.split(key, 8)
    d, m, L = cfg.d_model, cfg.d_mlp, cfg.num_layers
    hd = cfg.head_dim
    kv_dim = cfg.num_kv_heads * hd
    params = {
        "wte": truncated_normal(keys[0], (cfg.vocab_size, d)),
        "blocks": {
            "attn_norm": jnp.ones((L, d)),
            "wq": truncated_normal(keys[1], (L, d, d)),
            "wk": truncated_normal(keys[2], (L, d, kv_dim)),
            "wv": truncated_normal(keys[3], (L, d, kv_dim)),
            "wo": truncated_normal(keys[4], (L, d, d),
                                   stddev=0.02 / math.sqrt(2 * L)),
            "ffn_norm": jnp.ones((L, d)),
            "w_gate": truncated_normal(keys[5], (L, d, m)),
            "w_up": truncated_normal(keys[6], (L, d, m)),
            "w_down": truncated_normal(keys[7], (L, m, d),
                                       stddev=0.02 / math.sqrt(2 * L)),
        },
        "final_norm": jnp.ones((d,)),
    }
    return params, param_axes()


def rope(x, positions, theta: float):
    """Rotary embeddings. x: [B, H, S, D]; positions: [S] or [B, S]."""
    d = x.shape[-1]
    freqs = 1.0 / (theta ** (jnp.arange(0, d, 2, dtype=jnp.float32) / d))
    if positions.ndim == 1:
        angles = positions[:, None].astype(jnp.float32) * freqs[None, :]
        angles = angles[None, None]  # [1,1,S,D/2]
    else:
        angles = positions[:, None, :, None].astype(jnp.float32) * freqs
    cos, sin = jnp.cos(angles), jnp.sin(angles)
    x1, x2 = x[..., ::2], x[..., 1::2]
    xf1, xf2 = x1.astype(jnp.float32), x2.astype(jnp.float32)
    out1 = xf1 * cos - xf2 * sin
    out2 = xf2 * cos + xf1 * sin
    out = jnp.stack([out1, out2], axis=-1).reshape(x.shape)
    return out.astype(x.dtype)


def _repeat_kv(x, n_rep: int):
    if n_rep == 1:
        return x
    return jnp.repeat(x, n_rep, axis=1)


def _block(x, p, cfg: LlamaConfig, rules, positions):
    b, s, d = x.shape
    h, hd, hkv = cfg.num_heads, cfg.head_dim, cfg.num_kv_heads

    y = rms_norm(x, p["attn_norm"])
    q = (y @ p["wq"].astype(y.dtype)).reshape(b, s, h, hd).transpose(0, 2, 1, 3)
    k = (y @ p["wk"].astype(y.dtype)).reshape(b, s, hkv, hd).transpose(0, 2, 1, 3)
    v = (y @ p["wv"].astype(y.dtype)).reshape(b, s, hkv, hd).transpose(0, 2, 1, 3)
    q = rope(q, positions, cfg.rope_theta)
    k = rope(k, positions, cfg.rope_theta)
    k = _repeat_kv(k, h // hkv)
    v = _repeat_kv(v, h // hkv)
    o = attention_op(
        q, k, v, causal=True, mesh=current_mesh(),
        spec=spec_for(("batch", "heads", None, None), rules))
    o = o.transpose(0, 2, 1, 3).reshape(b, s, d)
    o = o @ p["wo"].astype(o.dtype)
    x = x + constrain(o, ("batch", "seq", None), rules)

    y = rms_norm(x, p["ffn_norm"])
    gate = jax.nn.silu(y @ p["w_gate"].astype(y.dtype))
    up = y @ p["w_up"].astype(y.dtype)
    hidden = constrain(gate * up, ("batch", "seq", "mlp"), rules)
    out = hidden @ p["w_down"].astype(hidden.dtype)
    return x + constrain(out, ("batch", "seq", None), rules)


def forward(params, tokens, cfg: LlamaConfig, rules=None):
    """tokens [B, S] -> logits [B, S, vocab] (training/prefill path)."""
    b, s = tokens.shape
    x = params["wte"][tokens].astype(cfg.dtype)
    positions = jnp.arange(s)
    block = partial(_block, cfg=cfg, rules=rules, positions=positions)
    if cfg.remat:
        block = jax.checkpoint(block)

    def scan_body(x, layer):
        return block(x, layer), None

    x, _ = jax.lax.scan(scan_body, x, params["blocks"])
    x = rms_norm(x, params["final_norm"])
    return jnp.einsum("bsd,vd->bsv", x, params["wte"].astype(cfg.dtype),
                      preferred_element_type=jnp.float32)


def loss_fn(params, batch, cfg: LlamaConfig, rules=None):
    tokens = batch["tokens"]
    logits = forward(params, tokens[:, :-1], cfg, rules)
    loss, _ = cross_entropy_loss(logits, tokens[:, 1:])
    return loss


# ---------------------------------------------------------------------------
# The plain reference of decoding: a dense cache [L, B, Hkv, max_seq, hd],
# one position a step (``decode_step``, ``generate``). The serving step
# below and the benchmark's float32 reference are held to it.
# ---------------------------------------------------------------------------

def init_kv_cache(cfg: LlamaConfig, batch: int):
    shape = (cfg.num_layers, batch, cfg.num_kv_heads, cfg.max_seq,
             cfg.head_dim)
    return {
        "k": jnp.zeros(shape, cfg.dtype),
        "v": jnp.zeros(shape, cfg.dtype),
    }


def _gqa_cache_attention(q, k_cache, v_cache, mask, cfg: LlamaConfig):
    """Grouped-query attention of q against a full cache, without
    materializing the repeated KV heads.

    q: [B, H, C, hd]; k_cache/v_cache: [B, Hkv, S, hd]; mask broadcastable
    to [B, Hkv, G, C, S]. Returns [B, C, D].
    """
    b, h, c, hd = q.shape
    hkv = cfg.num_kv_heads
    g = h // hkv
    scale = 1.0 / math.sqrt(hd)
    qg = q.reshape(b, hkv, g, c, hd)
    # bf16 operands + fp32 accumulation: an explicit .astype(f32) here
    # would materialize an fp32 copy of the whole KV cache every step —
    # at decode time the cache read IS the bandwidth bill.
    scores = jnp.einsum("bkgcd,bksd->bkgcs", qg, k_cache,
                        preferred_element_type=jnp.float32) * scale
    scores = jnp.where(mask, scores, -1e30)
    probs = jax.nn.softmax(scores, axis=-1)
    o = jnp.einsum("bkgcs,bksd->bkgcd", probs.astype(v_cache.dtype),
                   v_cache)
    return o.reshape(b, h, c, hd).transpose(0, 2, 1, 3).reshape(
        b, c, cfg.d_model)


def _cache_layer_step(x, p, cfg: LlamaConfig, positions, kv_mask,
                      write_kv):
    """One transformer block of the dense-cache reference; ``write_kv``
    lands the new K/V in the layer's cache.

    x: [B, T, D]. Returns (x, k_cache, v_cache).
    """
    b, t, _ = x.shape
    h, hd, hkv = cfg.num_heads, cfg.head_dim, cfg.num_kv_heads
    y = rms_norm(x, p["attn_norm"])
    q = (y @ p["wq"].astype(y.dtype)).reshape(b, t, h, hd).transpose(
        0, 2, 1, 3)
    k_new = (y @ p["wk"].astype(y.dtype)).reshape(
        b, t, hkv, hd).transpose(0, 2, 1, 3)
    v_new = (y @ p["wv"].astype(y.dtype)).reshape(
        b, t, hkv, hd).transpose(0, 2, 1, 3)
    q = rope(q, positions, cfg.rope_theta)
    k_new = rope(k_new, positions, cfg.rope_theta)
    k_cache, v_cache = write_kv(k_new, v_new)
    o = _gqa_cache_attention(q, k_cache, v_cache, kv_mask, cfg)
    x = x + o @ p["wo"].astype(o.dtype)
    y = rms_norm(x, p["ffn_norm"])
    gate = jax.nn.silu(y @ p["w_gate"].astype(y.dtype))
    up = y @ p["w_up"].astype(y.dtype)
    x = x + (gate * up) @ p["w_down"].astype(y.dtype)
    return x, k_cache, v_cache


def _lm_head(x, params, cfg: LlamaConfig):
    """[N, D] hidden states -> [N, vocab] fp32 logits."""
    with jax.named_scope("lm_head"):
        x = rms_norm(x, params["final_norm"])
        return jnp.einsum("bd,vd->bv", x, params["wte"].astype(cfg.dtype),
                          preferred_element_type=jnp.float32)


def decode_step(params, cache, tokens, pos, cfg: LlamaConfig):
    """One decode step: tokens [B] at position ``pos`` (scalar int array).

    Returns (logits [B, vocab], new_cache). Static shapes; masked attention
    over the cache prefix.
    """
    x = params["wte"][tokens].astype(cfg.dtype)[:, None, :]  # [B,1,D]
    positions = jnp.full((1,), pos)
    kv_mask = jnp.arange(cfg.max_seq)[None, None, None, None, :] <= pos

    def layer_step(x, inputs):
        p, k_cache, v_cache = inputs

        def write(kn, vn):
            return (jax.lax.dynamic_update_slice_in_dim(k_cache, kn, pos, 2),
                    jax.lax.dynamic_update_slice_in_dim(v_cache, vn, pos, 2))

        x, k2, v2 = _cache_layer_step(x, p, cfg, positions, kv_mask, write)
        return x, (k2, v2)

    x, (new_k, new_v) = jax.lax.scan(
        layer_step, x, (params["blocks"], cache["k"], cache["v"])
    )
    return _lm_head(x[:, 0], params, cfg), {"k": new_k, "v": new_v}


def generate(params, prompt_tokens, cfg: LlamaConfig, max_new: int = 32,
             temperature: float = 0.0, key=None):
    """Greedy/sampled generation, one ``decode_step`` a token, the
    prompt's too: the reference the engine's tokens are held to."""
    if temperature > 0 and key is None:
        raise ValueError("temperature > 0 requires a PRNG key")
    b, s = prompt_tokens.shape
    cache = init_kv_cache(cfg, b)
    decode = jax.jit(partial(decode_step, cfg=cfg))
    tokens = prompt_tokens
    logits = None
    for i in range(s):
        logits, cache = decode(params, cache, tokens[:, i], jnp.asarray(i))
    out = [tokens]
    cur = None
    for j in range(max_new):
        if temperature > 0:
            key, sub = jax.random.split(key)
            cur = jax.random.categorical(sub, logits / temperature, axis=-1)
        else:
            cur = jnp.argmax(logits, axis=-1)
        out.append(cur[:, None])
        logits, cache = decode(params, cache, cur, jnp.asarray(s + j))
    return jnp.concatenate(out, axis=1)


# ---------------------------------------------------------------------------
# What this family offers the serving engine through ``models/serving.py``:
# one step over the page pool of ``models/step.py`` (its layout, its scratch
# page, its kernel, its sharding and the rows of a step are that module's),
# so prompt-prefix pages can be SHARED between slots (radix/prefix cache,
# refcounted by the engine) and freed pages return to a pool instead of
# dying with a slot. PagedAttention (vLLM) / RadixAttention (SGLang). Under
# a tp mesh the q/k/v lanes of every intermediate shard like the pool's,
# and the rotary step's lane shifts run per shard in a shard_map.
#
# Scope names: the step carries ``jax.named_scope`` names — metadata on
# the HLO (``op_name``), no operation added, moved or changed — so a
# device trace can say what a step was made of under names the program
# chose. ``layers`` is round the loop over the layers; inside it every
# operation sits in ``qkv`` (norm, projections, rope), ``kv_write`` and
# ``kv_gather`` (the reference's scatter and gather; nothing on the TPU,
# where the kernel under ``attn`` does both), ``attn`` (attention and its
# output projection) or ``mlp``. What a trace shows under ``layers`` and
# none of those is the loop's own work, which should be nothing. Outside:
# ``embed``, ``lm_head``; ``prefill_lane`` is round the prompt chunk's
# half of the step. benchmark/trace/program.py reads them.
# ---------------------------------------------------------------------------

def init_cache(cfg: LlamaConfig, num_pages: int, page_size: int):
    return step.init_pool(cfg.num_layers, cfg, num_pages, page_size)


def paged_step(params, cache, tables, tokens, pos, chunk, cfg: LlamaConfig,
               page_size: int, rules=None):
    """One continuous-batching step over the paged cache: every slot's
    row decodes one token at its own position and, when ``chunk`` is
    given, one C-token prompt chunk rides the same weight matmuls; only
    attention and the K/V landing sites split.

    tables [B, P] int32, tokens [B] int32, pos [B] int32 (the position
    the new token is written at). chunk: None, or (pre_tokens [C],
    pre_slot, pre_p0, pre_n_valid): the chunk goes into ``pre_slot``'s
    pages from position pre_p0, straddling page boundaries freely.
    ``step.step_rows`` says which of these rows are in the step. The
    caller guarantees pre_slot is not a live decode row this step, so the
    two groups of rows touch disjoint pages. A chunk alone is a chunk
    with every decode row parked.

    The q / k / v projections read a layer of the stacked weights where
    it lies, as ``wo`` and the MLP's do: their outputs stay [.., T,
    heads * hd], the rotary step runs on those flat lanes
    (:func:`rope_lanes`, which says what a reshape to [.., heads, hd]
    before it costs on the TPU: a transposed copy of the layer's weight,
    every layer of every step), K and V go to the kernel as they are and
    q is cut into heads only at the attention call.

    Returns (logits [B, vocab] fp32, the logits [vocab] of chunk index
    pre_n_valid - 1 or None, new cache)."""
    rows = step.step_rows(tables, tokens, pos, chunk, cfg.max_seq)
    b, c, packed = rows.b, rows.c, rows.packed()
    # the rows alone [B, 1, ..]; with a chunk one sequence [1, B + C, ..]
    lay = (lambda a: a[:, None]) if chunk is None else (lambda a: a[None])
    with jax.named_scope("embed"):
        x = lay(params["wte"][packed].astype(cfg.dtype))
    positions = pos
    if chunk is not None:
        _, _, pre_p0, pre_n_valid = chunk
        positions = jnp.concatenate([pos, pre_p0 + jnp.arange(c)])
    h, hd, hkv = cfg.num_heads, cfg.head_dim, cfg.num_kv_heads
    angles_q, angles_k = (
        rope_lane_tables(lay(positions), n, hd, cfg.rope_theta)
        for n in (h, hkv))

    q_axes, kv_axes = (None, None, "qkv"), (None, None, "kv")
    mesh = current_mesh()
    q_shard, kv_shard = (mesh_axes_for(axes[2], rules) or None
                         for axes in (q_axes, kv_axes))

    def layer_step(x, kv, p, layer):
        with jax.named_scope("qkv"):   # token-major, heads side by side
            y = rms_norm(x, p["attn_norm"])
            q = constrain(y @ p["wq"].astype(y.dtype), q_axes, rules)
            k_new = constrain(y @ p["wk"].astype(y.dtype), kv_axes, rules)
            v_new = constrain(y @ p["wv"].astype(y.dtype), kv_axes, rules)
            q = rope_lanes(q, angles_q, mesh, q_shard)
            k_new = rope_lanes(k_new, angles_k, mesh, kv_shard)
            if chunk is not None:
                with jax.named_scope("prefill_lane"):
                    qp = q[:, b:].reshape(1, c, h, hd)
                    kp, vp = k_new[:, b:], v_new[:, b:]      # [1,C,Hkv*hd]
                q, k_new, v_new = (a[0, :b][:, None]
                                   for a in (q, k_new, v_new))
            q = q.reshape(b, 1, h, hd)
        # Decode rows, then the chunk: each writes its own tokens before
        # it attends, so in-chunk causality holds.
        o, kv = step.write_and_attend(q, k_new, v_new, kv, layer,
                                      rows.decode, cfg, page_size, rules)
        if chunk is not None:
            with jax.named_scope("prefill_lane"):
                op, kv = step.write_and_attend(qp, kp, vp, kv, layer,
                                               rows.chunk, cfg, page_size,
                                               rules)
        with jax.named_scope("attn"):
            if chunk is not None:
                o = jnp.concatenate([o[:, 0][None], op], axis=1)
            x = x + o @ p["wo"].astype(o.dtype)
        with jax.named_scope("mlp"):
            y = rms_norm(x, p["ffn_norm"])
            gate = jax.nn.silu(y @ p["w_gate"].astype(y.dtype))
            up = y @ p["w_up"].astype(y.dtype)
            hidden = constrain(gate * up, (None, None, "mlp"), rules)
            return x + hidden @ p["w_down"].astype(y.dtype), kv

    with jax.named_scope("layers"):  # the pool is the carry, never scanned
        (x, kv), _ = jax.lax.scan(
            lambda carry, inp: (layer_step(*carry, *inp), None),
            (x, cache["kv"]),
            (params["blocks"], jnp.arange(cfg.num_layers, dtype=jnp.int32)))
    if chunk is None:
        return _lm_head(x[:, 0], params, cfg), None, {"kv": kv}
    # (this family's engine never sends an empty chunk: the index is
    # ``pre_n_valid - 1`` as it stands, not ``rows.last``)
    logits = _lm_head(jnp.concatenate(
        [x[0, :b], x[0, b + pre_n_valid - 1][None]], axis=0), params, cfg)
    return logits[:b], logits[b], {"kv": kv}


def check_shardable(cfg: LlamaConfig, tp: int) -> None:
    """ValueError unless tp divides every axis the "tp" rules shard."""
    if tp > 1 and (cfg.num_kv_heads % tp or cfg.num_heads % tp
                   or cfg.d_mlp % tp or cfg.vocab_size % tp):
        raise ValueError(
            f"tp={tp} must divide num_kv_heads "
            f"({cfg.num_kv_heads}), num_heads ({cfg.num_heads}), "
            f"d_mlp ({cfg.d_mlp}) and vocab ({cfg.vocab_size})")


serving.register(serving.ServingModel(
    config_type=LlamaConfig, configs=CONFIGS, init_params=init_params,
    param_axes=param_axes, check_shardable=check_shardable,
    init_cache=init_cache, cache_axes={"kv": step.PAGED_KV_AXES},
    step=paged_step, **step.PAGE_FUNCTIONS))
