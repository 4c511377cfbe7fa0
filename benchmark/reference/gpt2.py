"""Plain float32 reference for GPT-2 (Radford et al. 2019; the published
``GPT2LMHeadModel``): learned position embeddings, pre-LayerNorm blocks,
causal multi-head attention, a GELU (tanh form, ``gelu_new``) MLP of four
times the width, a final LayerNorm and a head tied to the embedding; the
loss is the mean next-token cross-entropy.

Straightforward ``jax.numpy``: no kernel, no recomputation, no chunked
loss, one sequence at a time, a Python loop over the layers with each
layer's weights upcast as it is used. Departure from the published model:
no dropout (the configuration file says so under ``reduced``). It reads
the program's parameter tree (stacked ``[L, ...]`` arrays) and nothing else
of the program.
"""

from __future__ import annotations

import math

import jax
import jax.numpy as jnp

F32 = jnp.float32


def _layer_norm(x, scale, bias, eps):
    mean = jnp.mean(x, axis=-1, keepdims=True)
    var = jnp.mean((x - mean) ** 2, axis=-1, keepdims=True)
    return (x - mean) * jax.lax.rsqrt(var + eps) * scale + bias


def _gelu_new(x):
    return 0.5 * x * (1.0 + jnp.tanh(
        math.sqrt(2.0 / math.pi) * (x + 0.044715 * x ** 3)))


def _layer(x, w, heads: int, eps: float):
    """One block on one sequence. x: [S, d] float32."""
    w = jax.tree.map(lambda a: a.astype(F32), w)
    s, d = x.shape
    hd = d // heads
    h = _layer_norm(x, w["ln1_scale"], w["ln1_bias"], eps)
    qkv = h @ w["qkv_w"] + w["qkv_b"]
    q, k, v = (t.reshape(s, heads, hd) for t in jnp.split(qkv, 3, axis=-1))
    scores = jnp.einsum("qhd,khd->hqk", q, k) / math.sqrt(hd)
    causal = jnp.tril(jnp.ones((s, s), bool))
    probs = jax.nn.softmax(jnp.where(causal[None], scores, -jnp.inf), -1)
    o = jnp.einsum("hqk,khd->qhd", probs, v).reshape(s, d)
    x = x + o @ w["proj_w"] + w["proj_b"]
    h = _layer_norm(x, w["ln2_scale"], w["ln2_bias"], eps)
    h = _gelu_new(h @ w["mlp_in_w"] + w["mlp_in_b"])
    return x + h @ w["mlp_out_w"] + w["mlp_out_b"]


def _nll(x, scale, bias, wte, targets, eps):
    logits = _layer_norm(x, scale.astype(F32), bias.astype(F32),
                         eps) @ wte.astype(F32).T
    logz = jax.nn.logsumexp(logits, axis=-1)
    gold = jnp.take_along_axis(logits, targets[:, None], axis=-1)[:, 0]
    return jnp.sum(logz - gold)


def loss(params, cfg: dict, tokens) -> float:
    """tokens [B, S+1] -> mean next-token cross-entropy in float32, at the
    highest matmul precision the device has."""
    heads, eps = cfg["n_head"], float(cfg["layer_norm_epsilon"])
    tokens = jnp.asarray(tokens)
    with jax.default_matmul_precision("highest"):
        layer = jax.jit(lambda x, w: _layer(x, w, heads, eps))
        nll = jax.jit(lambda x, s, b, e, t: _nll(x, s, b, e, t, eps))
        rows = [(row[:-1], row[1:]) for row in tokens]
        xs = [params["wte"][inp].astype(F32)
              + params["wpe"][:inp.shape[0]].astype(F32) for inp, _ in rows]
        for l in range(cfg["n_layer"]):
            w = jax.tree.map(lambda a: a[l], params["blocks"])
            xs = [layer(x, w) for x in xs]
        total = sum(float(nll(x, params["lnf_scale"], params["lnf_bias"],
                              params["wte"], targets))
                    for x, (_, targets) in zip(xs, rows))
    return total / (tokens.shape[0] * (tokens.shape[1] - 1))
