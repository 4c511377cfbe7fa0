"""Plain float32 reference for the Solar-Open2 decoder (upstage
Solar-Open2-250B, ``model_type: solar_open2``): Kimi-Delta-Attention (KDA)
layers among gated grouped-query attention layers without a positional
term, and in every layer sigmoid-routed experts beside a shared expert.
Straightforward ``jax.numpy``: no kernel, no cache, no batching, one
sequence at a time, a Python loop over the layers, each layer's weights
upcast as it is used; the recurrence is a ``lax.scan`` over tokens, the
attention a dense causal softmax, the experts a loop over the held ones,
each computed on every token and masked by the routing.

The layer, ``u`` the RMS-normed input (``rms_norm_eps``):

    h = x + Op(RMSNorm_op(x));  y = h + FFN(RMSNorm_ffn(h))

    kda:  q, k, v = SiLU(conv4(W_q u)), SiLU(conv4(W_k u)), SiLU(conv4(W_v u))
            (causal, depthwise, zeros before position 0), per head of 128:
          q = q / sqrt(sum q^2 + 1e-6) / sqrt(128);  k = k / sqrt(sum k^2 + 1e-6)
          g = -exp(A_log_h) * softplus(W_f2 (W_f1 u) + dt_bias)   [128 a head]
          b = 2 sigmoid(w_b u)                                    [1 a head]
          S_t = (I - b k k^T) Diag(exp g) S_{t-1} + b k v^T;  o_t = S_t^T q
          out = W_o [RMSNorm_head(o) * sigmoid(W_g2 (W_g1 u))]
    gqa:  q, k, v = W_q u, W_k u, W_v u (no norm, no rotary);
          causal softmax(q k^T / sqrt(128)) v;  out = W_o [o * sigmoid(W_gate u)]
    ffn:  s = sigmoid(W_r u);  sel = top_8(s + expert_bias)
          w = s[sel] / (sum s[sel] + 1e-6) * routed_scaling_factor
          out = sum_{e in sel, e held} w_e W2_e(silu(W1_e u) * W3_e u)
                + W2_s(silu(W1_s u) * W3_s u)

after the last layer one RMSNorm, then the untied head.

Departures from the published model, each because the configuration
states it (``benchmark/configs/solar-open2-250b.json``) and the program
under test computes it so:
  * THE SHARE. The parameter tree holds ``n_routed_experts`` of the
    published experts of every layer, the run that starts at
    ``experts_held_first``; the router scores and picks over all
    ``n_routed_experts_published``, and a pick outside the run adds
    nothing (the other chips of the deployment would add it). The
    embedding and the head hold ``vocab_size`` rows of the published
    vocabulary: a smaller vocabulary, ids 0 .. vocab_size - 1.
  * what the catalog row does not give is the configuration's
    ``assumed``: sigmoid scores with a selection-only bias, the low-rank
    width, the element-wise GQA gate, no q / k head norm in the GQA
    layers, the L2 norm's epsilon, the state in float32.
It reads the program's parameter tree (``params["layers"][i]``: a KDA
layer keeps W_q, W_k, W_v side by side in ``w_qkv`` and their taps in
``conv_k[j]``; an expert's W1 and W3 side by side in ``w_gate_up[e, :,
:f]`` and ``[e, :, f:]``) and nothing else of the program.
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


def _conv(z, taps_k):
    """z [S, ch], taps_k [L, ch]: c_t = sum_j k[j] z_{t - (L-1) + j}."""
    s, taps = z.shape[0], taps_k.shape[0]
    z = jnp.pad(z, ((taps - 1, 0), (0, 0)))
    return sum(taps_k[j] * z[j:j + s] for j in range(taps))


def recurrence(q, k, v, g, b, s0=None, state_dtype=None, keep=None):
    """The delta rule, token by token. q, k, g [S, H, dk]; v [S, H, dv];
    b [S, H] -> (o [S, H, dv], kept [len(keep), H, dk, dv]): the states
    after ``keep[i]`` tokens (``keep`` None: after the last token).
    ``state_dtype``: the state rounded to that dtype after every token
    (what a lower precision would carry; None in every comparison that
    decides ``correct``). It is rounded by ``lax.reduce_precision``: a
    float32 -> bfloat16 -> float32 pair of casts is one the TPU compiler
    takes out (``xla_allow_excess_precision``), and the probe that used
    the casts read a fault that was never computed."""
    n, h, dk, dv = q.shape[0], q.shape[1], q.shape[2], v.shape[2]
    keep = jnp.asarray([n] if keep is None else keep, jnp.int32)

    def token(carry, xs):
        s, kept = carry
        t, qt, kt, vt, gt, bt = xs
        s = s * jnp.exp(gt)[:, :, None]
        ks = jnp.einsum("hkv,hk->hv", s, kt)
        s = s + kt[:, :, None] * (bt[:, None] * (vt - ks))[:, None, :]
        if state_dtype is not None:
            info = jnp.finfo(state_dtype)
            s = jax.lax.reduce_precision(s, info.nexp, info.nmant)
        kept = jnp.where((keep == t + 1)[:, None, None, None], s, kept)
        return (s, kept), jnp.einsum("hkv,hk->hv", s, qt)

    if s0 is None:
        s0 = jnp.zeros((h, dk, dv), F32)
    kept = jnp.broadcast_to(s0, keep.shape + s0.shape)  # 0 tokens: s0
    (_, kept), o = jax.lax.scan(
        token, (s0, kept), (jnp.arange(n), q, k, v, g, b))
    return o, kept


def _kda(u, w, heads: int, eps: float, state_dtype=None, stale=False,
         keep=None):
    """-> (the operator's output [S, d], the states :func:`recurrence`
    kept)."""
    s = u.shape[0]
    hd = w["w_f2"].shape[1] // heads
    q, k, v = (x.reshape(s, heads, hd) for x in jnp.split(
        jax.nn.silu(_conv(u @ w["w_qkv"], w["conv_k"])), 3, axis=-1))
    q = q * jax.lax.rsqrt(jnp.sum(q * q, -1, keepdims=True) + 1e-6) \
        / math.sqrt(hd)
    k = k * jax.lax.rsqrt(jnp.sum(k * k, -1, keepdims=True) + 1e-6)
    g = -jnp.exp(w["a_log"])[:, None] * jax.nn.softplus(
        ((u @ w["w_f1"]) @ w["w_f2"] + w["dt_bias"]).reshape(s, heads, hd))
    b = 2.0 * jax.nn.sigmoid(u @ w["w_b"])
    s0 = None
    if stale:
        # a slot that was not reset at admission: the state another
        # sequence of the same length left (this one's tokens, reversed)
        s0 = recurrence(q[::-1], k[::-1], v[::-1], g[::-1], b[::-1])[1][0]
    o, kept = recurrence(q, k, v, g, b, s0, state_dtype, keep)
    gate = jax.nn.sigmoid((u @ w["w_g1"]) @ w["w_g2"])
    return (_rms_norm(o, w["o_norm"], eps).reshape(s, -1) * gate) \
        @ w["wo"], kept


def _gqa(u, w, heads: int, kv_heads: int):
    s = u.shape[0]
    hd = w["wq"].shape[1] // heads
    q = (u @ w["wq"]).reshape(s, heads, hd)
    k = jnp.repeat((u @ w["wk"]).reshape(s, kv_heads, hd),
                   heads // kv_heads, axis=1)
    v = jnp.repeat((u @ w["wv"]).reshape(s, kv_heads, hd),
                   heads // kv_heads, axis=1)
    scores = jnp.einsum("qhd,khd->hqk", q, k) / math.sqrt(hd)
    causal = jnp.tril(jnp.ones((s, s), bool))
    probs = jax.nn.softmax(jnp.where(causal[None], scores, -jnp.inf), -1)
    o = jnp.einsum("hqk,khd->qhd", probs, v).reshape(s, heads * hd)
    return (o * jax.nn.sigmoid(u @ w["w_gate"])) @ w["wo"]


def router_scores(u, w):
    """u [S, d] -> the router's score of every published expert [S, E]."""
    return jax.nn.sigmoid(u @ w["router"])


def routing(u, w, top_k: int, norm_topk: bool, scaling: float):
    """u [S, d] -> dense weights [S, E] over every published expert: w_e
    where e was picked, else 0."""
    scores = router_scores(u, w)
    _, sel = jax.lax.top_k(scores + w["expert_bias"], top_k)
    picked = jnp.take_along_axis(scores, sel, axis=-1)
    if norm_topk:
        picked = picked / (picked.sum(axis=-1, keepdims=True) + 1e-6)
    rows = jnp.arange(u.shape[0])[:, None]
    return jnp.zeros_like(scores).at[rows, sel].set(picked * scaling)


def held_experts(u, w, dense_weights):
    """The held experts on every token, one at a time (each upcast as it
    is used), weighed by their columns of the routing's dense weights."""
    f = w["w_down"].shape[1]

    def one(acc, xs):
        w13, w2, we = xs
        hidden = u @ w13.astype(F32)
        y = (jax.nn.silu(hidden[:, :f]) * hidden[:, f:]) @ w2.astype(F32)
        return acc + we[:, None] * y, None

    out, _ = jax.lax.scan(one, jnp.zeros_like(u),
                          (w["w_gate_up"], w["w_down"], dense_weights.T))
    return out


def shared_expert(u, w):
    f = w["shared_down"].shape[0]
    hidden = u @ w["shared_gate_up"]
    return (jax.nn.silu(hidden[:, :f]) * hidden[:, f:]) @ w["shared_down"]


def ffn(u, w, geo: tuple):
    """The layer's FFN as this share computes it: the held routed experts'
    part and the shared expert."""
    top_k, norm_topk, scaling, first = geo
    dense = routing(u, w, top_k, norm_topk, scaling)
    held = w["w_gate_up"].shape[0]
    return held_experts(u, w, dense[:, first:first + held]) \
        + shared_expert(u, w)


def upcast(w):
    """A layer's weights in float32, but the experts' two stacked ones,
    which stay as stored until :func:`held_experts` takes one."""
    big = ("w_gate_up", "w_down")
    return {k: a if k in big else a.astype(F32) for k, a in w.items()}


def mixed(x, w, op: str, geo: tuple, state_dtype=None, stale=False,
          keep=None):
    """x + Op(RMSNorm_op(x)) on one sequence x [S, d] float32, ``w``
    upcast -> (h [S, d], a KDA layer's kept states or None)."""
    heads, kv_heads, kda_heads, eps = geo[:4]
    u = _rms_norm(x, w["op_norm"], eps)
    if op == "kda":
        out, kept = _kda(u, w, kda_heads, eps, state_dtype, stale, keep)
        return x + out, kept
    return x + _gqa(u, w, heads, kv_heads), None


def ffn_input(h, w, geo: tuple):
    return _rms_norm(h, w["ffn_norm"], geo[3])


def _layer(x, w, op: str, geo: tuple, state_dtype=None, stale=False,
           keep=None):
    """One layer on one sequence -> (y [S, d], the kept states or None)."""
    w = upcast(w)
    h, kept = mixed(x, w, op, geo, state_dtype, stale, keep)
    return h + ffn(ffn_input(h, w, geo), w, geo[4:]), kept


@functools.lru_cache(maxsize=None)
def _compiled(op: str, geo: tuple, state_dtype, stale: bool):
    """A kind of layer, jitted once per geometry; ``keep`` [2] int32."""
    return jax.jit(lambda x, w, keep: _layer(x, w, op, geo, state_dtype,
                                             stale, keep))


@functools.lru_cache(maxsize=None)
def _compiled_head(eps: float):
    return jax.jit(lambda x, n, e: _rms_norm(x, n.astype(F32), eps)
                   @ e.astype(F32).T)


def layer_types(cfg: dict) -> list:
    return ["gqa" if i in cfg["gqa_layers"] else "kda"
            for i in range(cfg["num_hidden_layers"])]


def geometry(cfg: dict) -> tuple:
    return (cfg["num_attention_heads"], cfg["num_key_value_heads"],
            cfg["linear_attn_config"]["num_heads"],
            float(cfg["rms_norm_eps"]), int(cfg["num_experts_per_tok"]),
            bool(cfg["norm_topk_prob"]), float(cfg["routed_scaling_factor"]),
            int(cfg.get("experts_held_first", 0)))


def logits(params, cfg: dict, tokens, round_experts_to=None,
           state_dtype=None, stale_state=False,
           precision: str = "highest", states_after=None):
    """tokens [S] -> float32 logits [S, held vocabulary], a layer at a
    time, at the highest matmul precision the device has (a TPU otherwise
    multiplies float32 in bfloat16 passes). The options are what a fault
    would give, for setting the limits of the comparison
    (``tools/solar_precision_probe.py``): the experts' weights rounded to
    a lower dtype, the KDA state rounded after every token, the state not
    zero at the sequence's start, the products in the device's default
    precision; all off in every comparison that decides ``correct``.
    ``states_after`` n: -> (logits, [KDA layers, 2, H, dk, dv]: every KDA
    layer's state after n - 1 and after n tokens)."""
    geo = geometry(cfg)
    n = len(tokens) if states_after is None else states_after
    keep, states = jnp.asarray([n - 1, n], jnp.int32), []
    with jax.default_matmul_precision(precision):
        x = params["wte"][jnp.asarray(tokens)].astype(F32)
        for op, w in zip(layer_types(cfg), params["layers"]):
            if round_experts_to is not None:
                w = dict(w, **{k: w[k].astype(round_experts_to).astype(
                    w[k].dtype) for k in ("w_gate_up", "w_down")})
            x, kept = _compiled(op, geo, state_dtype, bool(stale_state))(
                x, w, keep)
            if kept is not None:
                states.append(kept)
        out = _compiled_head(geo[3])(x, params["final_norm"],
                                     params["lm_head"])
    if states_after is None:
        return out
    return out, (jnp.stack(states) if states else jnp.zeros((0, 2), F32))


def mantissa_bits(x) -> int:
    """The mantissa bits a float32 array uses: 23 less the trailing zero
    bits all its elements share. Values computed in float32 use all 23;
    values that passed through bfloat16 use 7, through float16 10."""
    used = int(np.bitwise_or.reduce(
        np.asarray(x, np.float32).view(np.uint32).ravel() & 0x7FFFFF))
    return 0 if used == 0 else 24 - (used & -used).bit_length()


def state_error(got, want) -> float:
    """got, want [KDA layers, H, dk, dv]: the mean over layers of
    |got - want| / |want| (Frobenius norms over a layer's heads)."""
    got, want = np.asarray(got, np.float64), np.asarray(want, np.float64)
    axes = tuple(range(1, want.ndim))
    return float(np.mean(np.sqrt(((got - want) ** 2).sum(axes)
                                 / (want ** 2).sum(axes))))


def check_generated(params, cfg: dict, samples: list) -> dict:
    """For each ``{"prompt", "tokens"}``: feed prompt + tokens[:-1] and
    measure, at every generated position, the largest reference logit
    minus the reference logit of the token the system produced (0 where
    the system chose the reference's own argmax).

    A sample may carry ``"state"``: the KDA state [KDA layers, H, dk, dv]
    its slot held when the request had ended. It is held to the
    reference's state after the same tokens (:func:`state_error`) — after
    prompt + tokens or after prompt + tokens[:-1], whichever is nearer: a
    pipelined engine may or may not have fed the last token back before
    it learnt that the request was over — and to the float32 the
    configuration states for it (:func:`mantissa_bits`)."""
    longest = max(len(s["prompt"]) + len(s["tokens"]) for s in samples)
    pad_to = -(-longest // 128) * 128  # one compiled shape for all samples
    gaps, top_gaps, state_errs, state_bits = [], [], [], []
    for s in samples:
        seq = list(s["prompt"]) + list(s["tokens"])
        n0, n = len(s["prompt"]), len(s["tokens"])
        toks = np.zeros((pad_to,), np.int32)
        toks[:len(seq)] = seq  # causal: what follows a position is unseen
        lg, states = logits(params, cfg, toks, states_after=len(seq))
        lg = lg[n0 - 1:n0 - 1 + n]
        chosen = jnp.take_along_axis(
            lg, jnp.asarray(s["tokens"], jnp.int32)[:, None], axis=1)[:, 0]
        top2 = jax.lax.top_k(lg, 2)[0]
        gaps.extend(np.asarray(top2[:, 0] - chosen, np.float64).tolist())
        top_gaps.extend(np.asarray(top2[:, 0] - top2[:, 1],
                                   np.float64).tolist())
        if s.get("state") is not None and states.size:
            state_errs.append(min(state_error(s["state"], states[:, i])
                                  for i in (0, 1)))
            state_bits.append(mantissa_bits(s["state"]))
    out = {"n": len(gaps), "max_gap": max(gaps),
           "mean_gap": float(np.mean(gaps)),
           "argmax_share": float(np.mean([g == 0.0 for g in gaps])),
           "median_top2_gap": float(np.median(top_gaps)),
           "finite": bool(np.all(np.isfinite(gaps)))}
    if state_errs:
        out.update(state_err=max(state_errs), state_bits=min(state_bits))
    return out
