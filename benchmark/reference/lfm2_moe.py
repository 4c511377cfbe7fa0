"""Plain float32 reference for the LFM2-MoE decoder (LiquidAI
LFM2-24B-A2B, ``model_type: lfm2_moe``): gated short convolutions among
grouped-query attention layers, a dense SwiGLU FFN in the leading layers,
sigmoid-routed experts in the rest. Straightforward ``jax.numpy``: no
kernel, no cache, no batching, one sequence at a time, a Python loop over
the layers, each layer's weights upcast as it is used and every expert
computed densely on every token, then masked by the routing.

The layer, ``u`` the RMS-normed input (``norm_eps``):

    h = x + Op(RMSNorm_op(x));  y = h + FFN(RMSNorm_ffn(h))

    conv:  [B, C, X] = split3(W_in u);  z = B * X
           c_t = sum_{j < L} k[:, j] * z_{t - (L - 1) + j}   (zeros before 0)
           out = W_out (C * c)
    attn:  q, k, v = W_q u, W_k u, W_v u; RMSNorm over each q head and each
           k head (one [head_dim] weight each); THEN rotary on q and k;
           causal softmax(q k^T / sqrt(head_dim)) v; W_o
    dense: W2(silu(W1 u) * W3 u)               (layers < num_dense_layers)
    moe:   s = sigmoid(W_g u);  sel = top_k(s + expert_bias)
           w = s[sel] / (sum s[sel] + 1e-6) * routed_scaling_factor
           out = sum_{e in sel} w_e * W2_e(silu(W1_e u) * W3_e u)

after the last layer one RMSNorm, then the head, tied to the embedding.

Departures from the published class, each because the program under test
computes it so and the comparison is of arithmetic, not of checkpoints:
  * rotary pairs are interleaved (x[2i], x[2i+1]) where the published
    class pairs (x[i], x[i + d/2]): with seeded random weights the two
    differ by one fixed permutation of the columns of W_q and W_k and of
    the entries of the two head-norm weights;
  * the head is tied to the embedding (the family's default; the catalog
    row does not say).
It reads the program's parameter tree (``params["layers"][i]``; an
expert layer keeps W1 and W3 side by side in ``w_gate_up[e, :, :f]`` and
``[e, :, f:]``, the conv kernel's tap j in ``conv_k[j]``) and nothing else
of the program.
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


def _conv(u, w, taps: int):
    s = u.shape[0]
    gate_b, gate_c, x = jnp.split(u @ w["w_in"], 3, axis=-1)
    z = jnp.pad(gate_b * x, ((taps - 1, 0), (0, 0)))
    c = sum(w["conv_k"][j] * z[j:j + s] for j in range(taps))
    return (gate_c * c) @ w["w_out"]


def _attention(u, w, heads: int, kv_heads: int, theta: float, eps: float):
    s, d = u.shape
    hd = d // heads
    q = _rms_norm((u @ w["wq"]).reshape(s, heads, hd), w["q_norm"], eps)
    k = _rms_norm((u @ w["wk"]).reshape(s, kv_heads, hd), w["k_norm"], eps)
    v = (u @ w["wv"]).reshape(s, kv_heads, hd)
    q, k = _rope(q, theta), _rope(k, theta)
    k = jnp.repeat(k, heads // kv_heads, axis=1)
    v = jnp.repeat(v, heads // kv_heads, axis=1)
    scores = jnp.einsum("qhd,khd->hqk", q, k) / math.sqrt(hd)
    causal = jnp.tril(jnp.ones((s, s), bool))
    probs = jax.nn.softmax(jnp.where(causal[None], scores, -jnp.inf), -1)
    return jnp.einsum("hqk,khd->qhd", probs, v).reshape(s, d) @ w["wo"]


def routing(u, w, top_k: int, use_bias: bool, norm_topk: bool,
            scaling: float):
    """u [S, d] -> dense weights [S, E]: w_e where e was picked, else 0."""
    scores = jax.nn.sigmoid(u @ w["router"])
    pick_by = scores + w["expert_bias"] if use_bias else scores
    _, sel = jax.lax.top_k(pick_by, top_k)
    picked = jnp.take_along_axis(scores, sel, axis=-1)
    if norm_topk:
        picked = picked / (picked.sum(axis=-1, keepdims=True) + 1e-6)
    rows = jnp.arange(u.shape[0])[:, None]
    return jnp.zeros_like(scores).at[rows, sel].set(picked * scaling)


def _experts(u, w, dense_weights):
    """Every expert on every token, one expert at a time (each upcast as
    it is used), weighed by the routing's dense weights."""
    f = w["w_down"].shape[1]

    def one(acc, xs):
        w13, w2, we = xs
        hidden = u @ w13.astype(F32)
        y = (jax.nn.silu(hidden[:, :f]) * hidden[:, f:]) @ w2.astype(F32)
        return acc + we[:, None] * y, None

    out, _ = jax.lax.scan(one, jnp.zeros_like(u),
                          (w["w_gate_up"], w["w_down"], dense_weights.T))
    return out


def _layer(x, w, op: str, ffn: str, geo: tuple):
    """One layer on one sequence. x: [S, d] float32; the experts' two
    stacked weights stay as stored until :func:`_experts` takes one."""
    (heads, kv_heads, theta, eps, taps, top_k, use_bias, norm_topk,
     scaling) = geo
    big = ("w_gate_up", "w_down") if ffn == "moe" else ()
    w = {k: a if k in big else a.astype(F32) for k, a in w.items()}
    u = _rms_norm(x, w["op_norm"], eps)
    x = x + (_conv(u, w, taps) if op == "conv"
             else _attention(u, w, heads, kv_heads, theta, eps))
    u = _rms_norm(x, w["ffn_norm"], eps)
    if ffn == "dense":
        return x + (jax.nn.silu(u @ w["w_gate"]) * (u @ w["w_up"])) \
            @ w["w_down"]
    return x + _experts(u, w, routing(u, w, top_k, use_bias, norm_topk,
                                      scaling))


def _head(x, final_norm, wte, eps):
    return _rms_norm(x, final_norm.astype(F32), eps) @ wte.astype(F32).T


@functools.lru_cache(maxsize=None)
def _compiled(op: str, ffn: str, geo: tuple):
    """A kind of layer, jitted once per geometry."""
    return jax.jit(lambda x, w: _layer(x, w, op, ffn, geo))


@functools.lru_cache(maxsize=None)
def _compiled_head(eps: float):
    return jax.jit(lambda x, n, e: _head(x, n, e, eps))


def geometry(cfg: dict) -> tuple:
    return (cfg["num_attention_heads"], cfg["num_key_value_heads"],
            float(cfg["rope_parameters"]["rope_theta"]),
            float(cfg["norm_eps"]), int(cfg["conv_L_cache"]),
            int(cfg["num_experts_per_tok"]), bool(cfg["use_expert_bias"]),
            bool(cfg["norm_topk_prob"]),
            float(cfg["routed_scaling_factor"]))


def logits(params, cfg: dict, tokens, round_experts_to=None) -> jax.Array:
    """tokens [S] -> float32 logits [S, vocab], a layer at a time, at the
    highest matmul precision the device has (a TPU otherwise multiplies
    float32 in bfloat16 passes). ``round_experts_to``: a dtype the
    experts' weights are rounded to as each layer is used — what a
    precision below the stated one would give, for setting the bound of
    the comparison (``tools/lfm2_precision_probe.py``); None in every
    comparison that decides ``correct``."""
    geo = geometry(cfg)
    with jax.default_matmul_precision("highest"):
        x = params["wte"][jnp.asarray(tokens)].astype(F32)
        for i, (op, w) in enumerate(zip(cfg["layer_types"],
                                        params["layers"])):
            ffn = "dense" if i < cfg["num_dense_layers"] else "moe"
            if ffn == "moe" and round_experts_to is not None:
                w = dict(w, **{k: w[k].astype(round_experts_to).astype(
                    w[k].dtype) for k in ("w_gate_up", "w_down")})
            x = _compiled(op, ffn, geo)(x, w)
        return _compiled_head(geo[3])(x, params["final_norm"],
                                      params["wte"])


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
