"""Plain float32 reference for a Llama-architecture decoder (the class
SmolLM2 is published in): RMSNorm, rotary embeddings, grouped-query causal
attention, SwiGLU, tied or untied head. Straightforward ``jax.numpy``: no
kernel, no cache, no batching, one sequence at a time, a Python loop over
the layers with each layer's weights upcast as it is used.

It follows Touvron et al. 2023 (LLaMA) as implemented by the published
``LlamaForCausalLM``. Departures, each because the program under test
computes it so and the comparison is of arithmetic, not of checkpoints:
  * rotary pairs are interleaved (x[2i], x[2i+1]) where the published
    class pairs (x[i], x[i + d/2]): with seeded random weights the two
    differ by a fixed permutation of the columns of wq and wk;
  * ``rms_norm_eps`` is the configuration file's (what the program
    computes), named in the file's ``reduced`` where it is not the
    published one.
It reads the program's parameter tree (stacked ``[L, ...]`` arrays) and
nothing else of the program.
"""

from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
import numpy as np

F32 = jnp.float32


def _rms_norm(x, scale, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True)
                             + eps) * scale


def _rope(x, theta):
    """x: [S, H, D]; position p rotates pair i by p * theta**(-2i/D)."""
    s, _, d = x.shape
    inv = theta ** (-jnp.arange(0, d, 2, dtype=F32) / d)
    ang = jnp.arange(s, dtype=F32)[:, None] * inv[None, :]  # [S, D/2]
    cos, sin = jnp.cos(ang)[:, None, :], jnp.sin(ang)[:, None, :]
    x1, x2 = x[..., 0::2], x[..., 1::2]
    return jnp.stack([x1 * cos - x2 * sin, x2 * cos + x1 * sin],
                     axis=-1).reshape(x.shape)


def _layer(x, w, heads: int, kv_heads: int, theta: float, eps: float):
    """One decoder layer on one sequence. x: [S, d] float32."""
    w = jax.tree.map(lambda a: a.astype(F32), w)
    s, d = x.shape
    hd = d // heads
    h = _rms_norm(x, w["attn_norm"], eps)
    q = _rope((h @ w["wq"]).reshape(s, heads, hd), theta)
    k = _rope((h @ w["wk"]).reshape(s, kv_heads, hd), theta)
    v = (h @ w["wv"]).reshape(s, kv_heads, hd)
    k = jnp.repeat(k, heads // kv_heads, axis=1)
    v = jnp.repeat(v, heads // kv_heads, axis=1)
    scores = jnp.einsum("qhd,khd->hqk", q, k) / math.sqrt(hd)
    causal = jnp.tril(jnp.ones((s, s), bool))
    probs = jax.nn.softmax(jnp.where(causal[None], scores, -jnp.inf), -1)
    x = x + jnp.einsum("hqk,khd->qhd", probs, v).reshape(s, d) @ w["wo"]
    h = _rms_norm(x, w["ffn_norm"], eps)
    return x + (jax.nn.silu(h @ w["w_gate"]) * (h @ w["w_up"])) @ w["w_down"]


def _head(x, final_norm, wte, eps):
    return _rms_norm(x, final_norm.astype(F32), eps) @ wte.astype(F32).T


@functools.lru_cache(maxsize=None)
def _compiled(heads: int, kv: int, theta: float, eps: float):
    """The layer and the head, jitted once per geometry (a new lambda per
    call would compile again for every sequence)."""
    return (jax.jit(lambda x, w: _layer(x, w, heads, kv, theta, eps)),
            jax.jit(lambda x, n, e: _head(x, n, e, eps)))


def logits(params, cfg: dict, tokens) -> jax.Array:
    """tokens [S] -> float32 logits [S, vocab], at the highest matmul
    precision the device has (a TPU otherwise multiplies float32 in
    bfloat16 passes)."""
    heads, kv = cfg["num_attention_heads"], cfg["num_key_value_heads"]
    theta, eps = float(cfg["rope_theta"]), float(cfg["rms_norm_eps"])
    layer, head = _compiled(heads, kv, theta, eps)
    with jax.default_matmul_precision("highest"):
        x = params["wte"][jnp.asarray(tokens)].astype(F32)
        for l in range(cfg["num_hidden_layers"]):
            x = layer(x, jax.tree.map(lambda a: a[l], params["blocks"]))
        return head(x, params["final_norm"], params["wte"])


def check_generated(params, cfg: dict, samples: list) -> dict:
    """For each ``{"prompt", "tokens"}``: feed prompt + tokens[:-1] and
    measure, at every generated position, the largest reference logit
    minus the reference logit of the token the system produced (0 where
    the system chose the reference's own argmax)."""
    longest = max(len(s["prompt"]) + len(s["tokens"]) for s in samples)
    pad_to = -(-longest // 128) * 128  # one compiled shape for all samples
    gaps, top_gaps = [], []
    for s in samples:
        seq = list(s["prompt"]) + list(s["tokens"])[:-1]
        n0, n = len(s["prompt"]), len(s["tokens"])
        toks = np.zeros((pad_to,), np.int32)
        toks[:len(seq)] = seq  # causal: padding after a position is unseen
        lg = logits(params, cfg, toks)[n0 - 1:n0 - 1 + n]
        chosen = jnp.take_along_axis(
            lg, jnp.asarray(s["tokens"], jnp.int32)[:, None], axis=1)[:, 0]
        top2 = jax.lax.top_k(lg, 2)[0]
        gaps.extend(np.asarray(top2[:, 0] - chosen, np.float64).tolist())
        top_gaps.extend(np.asarray(top2[:, 0] - top2[:, 1],
                                   np.float64).tolist())
    return {"n": len(gaps), "max_gap": max(gaps),
            "mean_gap": float(np.mean(gaps)),
            "argmax_share": float(np.mean([g == 0.0 for g in gaps])),
            "median_top2_gap": float(np.median(top_gaps)),
            "finite": bool(np.all(np.isfinite(gaps)))}
