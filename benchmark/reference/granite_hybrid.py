"""Plain float32 reference for the Granite-4.0-H decoder (ibm-granite
granite-4.0-h-micro, ``model_type: granitemoehybrid`` with no experts):
Mamba-2 layers among grouped-query attention layers without a positional
term, a dense SwiGLU in every layer, and the four published multipliers.
Straightforward ``jax.numpy``: no kernel, no cache, no batching, one
sequence at a time, a Python loop over the layers, each layer's weights
upcast as it is used; the recurrence is a ``lax.scan`` over tokens on the
state ``[heads, head_dim, N]`` as the equations index it, the attention a
dense causal softmax, the head computed in blocks of the vocabulary.

    h = embedding_multiplier * E[token]
    h = h + residual_multiplier * Mixer(RMSNorm_op(h))
    h = h + residual_multiplier * W_down[silu(g) * u],  [g | u] = W_gate_up RMSNorm_ffn(h)
    logits = (E RMSNorm(h)) / logits_scaling

    mamba:     [z | xBC | dt] = W_in u     (d_inner | d_inner + 2 N | heads)
               xBC = silu(conv4(xBC) + b_conv)  (causal, depthwise, zeros
                 before position 0);  [x | B | C] = xBC  (d_inner | N | N)
               dt = softplus(dt + dt_bias);  a = exp(-exp(A_log) dt)   [1 a head]
               per head p:  S_p = a_p S_p + (dt_p x_p) B^T;  y_p = S_p C + D_p x_p
               out = W_out [RMSNorm_{d_inner}(y * silu(z)) * w]
    attention: q, k, v = W_q u, W_k u, W_v u (no norm, no rotary);
               causal softmax(attention_multiplier q k^T) v;  out = W_o o

Departures from the published model: none in the equations. What the
catalog row does not give is the configuration's ``assumed``
(``benchmark/configs/granite-4.0-h-micro.json``): the orders of the two
splits, the head size, the gate before ONE norm over all d_inner
channels, no limit on the step size, the state in float32.
It reads the program's parameter tree (``params["mamba"]`` and
``params["attention"]``: every layer of a kind stacked along a leading
axis, in the order of ``layer_types``) and nothing else of the program.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

# how a held state is compared is the same for any matrix state a slot
# keeps: the mantissa bits its float32 values use, and its distance from
# the reference's, a mean over the layers
from benchmark.reference.solar_open2 import (mantissa_bits,  # noqa: F401
                                             state_error)

F32 = jnp.float32
VOCAB_BLOCKS = 8


def _rms_norm(x, scale, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True)
                             + eps) * scale


def _conv(z, taps_k):
    """z [S, ch], taps_k [L, ch]: c_t = sum_j k[j] z_{t - (L-1) + j}."""
    s, taps = z.shape[0], taps_k.shape[0]
    z = jnp.pad(z, ((taps - 1, 0), (0, 0)))
    return sum(taps_k[j] * z[j:j + s] for j in range(taps))


def recurrence(x, a, b, c, s0=None, state_dtype=None, keep=None):
    """The state-space recurrence, token by token. x [S, H, P] (the input
    times its step size); a [S, H]; b, c [S, N] -> (y [S, H, P], kept
    [len(keep), H, P, N]): the states after ``keep[i]`` tokens (``keep``
    None: after the last token). ``state_dtype``: the state rounded to
    that dtype after every token (what a lower precision would carry; None
    in every comparison that decides ``correct``), by
    ``lax.reduce_precision``: a pair of casts is one the TPU compiler
    takes out."""
    n_tok, h, p = x.shape
    keep = jnp.asarray([n_tok] if keep is None else keep, jnp.int32)

    def token(carry, xs):
        s, kept = carry
        t, xt, at, bt, ct = xs
        s = s * at[:, None, None] + xt[:, :, None] * bt[None, None, :]
        if state_dtype is not None:
            info = jnp.finfo(state_dtype)
            s = jax.lax.reduce_precision(s, info.nexp, info.nmant)
        kept = jnp.where((keep == t + 1)[:, None, None, None], s, kept)
        return (s, kept), jnp.sum(s * ct[None, None, :], axis=-1)

    if s0 is None:
        s0 = jnp.zeros((h, p, b.shape[-1]), F32)
    kept = jnp.broadcast_to(s0, keep.shape + s0.shape)  # 0 tokens: s0
    (_, kept), y = jax.lax.scan(token, (s0, kept),
                                (jnp.arange(n_tok), x, a, b, c))
    return y, kept


def _mamba(u, w, geo: dict, fault: dict, keep=None):
    """-> (the mixer's output [S, d], the states :func:`recurrence`
    kept)."""
    s, h, n = u.shape[0], geo["ssm_heads"], geo["ssm_state"]
    di = w["y_norm"].shape[0]
    proj = u @ w["w_in"]     # [z | xBC]; the dt columns are ``w_dt^T``
    z, xbc, dt = proj[:, :di], proj[:, di:], u @ w["w_dt"].T
    xbc = jax.nn.silu(_conv(xbc, w["conv_k"]) + w["conv_b"])
    x = xbc[:, :di].reshape(s, h, di // h)
    b, c = xbc[:, di:di + n], xbc[:, di + n:]
    dt = jax.nn.softplus(dt + w["dt_bias"])
    a = jnp.exp(-jnp.exp(w["a_log"]) * dt)
    dx = x * dt[:, :, None]
    s0 = None
    if fault.get("stale_state"):
        # a slot that was not reset at admission: the state another
        # sequence of the same length left (this one's tokens, reversed)
        s0 = recurrence(dx[::-1], a[::-1], b[::-1], c[::-1])[1][0]
    y, kept = recurrence(dx, a, b, c, s0, fault.get("state_dtype"), keep)
    if not fault.get("drop_skip"):
        y = y + w["d_skip"][:, None] * x
    y = _rms_norm(y.reshape(s, di) * jax.nn.silu(z), w["y_norm"],
                  geo["eps"])
    return y @ w["w_out"], kept


def _attention(u, w, geo: dict, fault: dict):
    s, heads, kv_heads = u.shape[0], geo["heads"], geo["kv_heads"]
    hd = w["wq"].shape[1] // heads
    q = (u @ w["wq"]).reshape(s, heads, hd)
    k = jnp.repeat((u @ w["wk"]).reshape(s, kv_heads, hd),
                   heads // kv_heads, axis=1)
    v = jnp.repeat((u @ w["wv"]).reshape(s, kv_heads, hd),
                   heads // kv_heads, axis=1)
    scale = fault.get("attention_scale") or geo["attention_multiplier"]
    scores = jnp.einsum("qhd,khd->hqk", q, k) * scale
    causal = jnp.tril(jnp.ones((s, s), bool))
    probs = jax.nn.softmax(jnp.where(causal[None], scores, -jnp.inf), -1)
    return jnp.einsum("hqk,khd->qhd", probs, v).reshape(s, heads * hd) \
        @ w["wo"]


def _layer(x, w, kind: str, geo: dict, fault: dict, keep=None):
    """One layer on one sequence x [S, d] float32 -> (y [S, d], a mamba
    layer's kept states or None)."""
    w = {k: a.astype(F32) for k, a in w.items()}
    u, kept = _rms_norm(x, w["op_norm"], geo["eps"]), None
    if kind == "mamba":
        out, kept = _mamba(u, w, geo, fault, keep)
    else:
        out = _attention(u, w, geo, fault)
    x = x + geo["residual_multiplier"] * out
    f = w["w_down"].shape[0]
    hidden = _rms_norm(x, w["ffn_norm"], geo["eps"]) @ w["w_gate_up"]
    out = (jax.nn.silu(hidden[:, :f]) * hidden[:, f:]) @ w["w_down"]
    return x + geo["residual_multiplier"] * out, kept


def _frozen(d: dict) -> tuple:
    return tuple(sorted(d.items()))


@functools.lru_cache(maxsize=None)
def _compiled(kind: str, geo: tuple, fault: tuple):
    """A kind of layer, jitted once per geometry; ``keep`` [2] int32."""
    return jax.jit(lambda x, w, keep: _layer(x, w, kind, dict(geo),
                                             dict(fault), keep))


@functools.lru_cache(maxsize=None)
def _compiled_head(eps: float, scaling: float):
    def head(x, norm, table):
        """[R, d] -> [R, vocab], a block of the vocabulary at a time."""
        x = _rms_norm(x, norm.astype(F32), eps)
        blocks = table.reshape(VOCAB_BLOCKS, -1, table.shape[1])
        out = jax.lax.map(lambda e: x @ e.astype(F32).T, blocks)
        return jnp.moveaxis(out, 0, 1).reshape(x.shape[0], -1) / scaling

    return jax.jit(head)


def geometry(cfg: dict) -> dict:
    return {"heads": cfg["num_attention_heads"],
            "kv_heads": cfg["num_key_value_heads"],
            "ssm_heads": cfg["mamba_n_heads"],
            "ssm_state": cfg["mamba_d_state"],
            "eps": float(cfg["rms_norm_eps"]),
            "embedding_multiplier": float(cfg["embedding_multiplier"]),
            "residual_multiplier": float(cfg["residual_multiplier"]),
            "attention_multiplier": float(cfg["attention_multiplier"]),
            "logits_scaling": float(cfg["logits_scaling"])}


def layers_of(params, cfg: dict):
    """(kind, that layer's weights) in the order of ``layer_types``."""
    seen = {"mamba": 0, "attention": 0}
    for kind in cfg["layer_types"]:
        i = seen[kind]
        seen[kind] += 1
        yield kind, {k: a[i] for k, a in params[kind].items()}


def logits(params, cfg: dict, tokens, rows=None, states_after=None,
           precision: str = "highest", **fault):
    """tokens [S] -> float32 logits [S, vocab] (of positions ``rows``, if
    given), a layer at a time, at the highest matmul precision the device
    has (a TPU otherwise multiplies float32 in bfloat16 passes).
    ``fault`` is what a fault would give, for setting the limits of the
    comparison (``tools/granite_precision_probe.py``): ``state_dtype``
    (the state rounded after every token), ``stale_state`` (not zero at
    the sequence's start), ``drop_skip`` (no ``D x``),
    ``attention_scale`` (another than the published), ``round_weights_to``
    (every weight matrix through a lower dtype), and ``precision`` the
    products' (the device's default is what an engine in bfloat16 has
    besides); all off in every comparison that decides ``correct``.
    ``states_after`` n: -> (logits, [mamba layers, 2, H, P, N]: every
    mamba layer's state after n - 1 and after n tokens)."""
    geo = geometry(cfg)
    round_to = fault.pop("round_weights_to", None)
    n = len(tokens) if states_after is None else states_after
    keep, states = jnp.asarray([n - 1, n], jnp.int32), []
    if cfg["vocab_size"] % VOCAB_BLOCKS:
        raise ValueError("the head's blocks do not divide the vocabulary")
    with jax.default_matmul_precision(precision):
        x = params["wte"][jnp.asarray(tokens)].astype(F32) \
            * geo["embedding_multiplier"]
        for kind, w in layers_of(params, cfg):
            if round_to is not None:
                # op by op, outside any compiled function: inside one the
                # TPU compiler takes a pair of casts out
                w = {k: a.astype(round_to).astype(a.dtype) if a.ndim == 2
                     and k != "conv_k" else a for k, a in w.items()}
            x, kept = _compiled(kind, _frozen(geo), _frozen(fault))(
                x, w, keep)
            if kept is not None:
                states.append(kept)
        if rows is not None:
            x = x[jnp.asarray(rows)]
        out = _compiled_head(geo["eps"], geo["logits_scaling"])(
            x, params["final_norm"], params["wte"])
    if states_after is None:
        return out
    return out, (jnp.stack(states) if states else jnp.zeros((0, 2), F32))


def check_generated(params, cfg: dict, samples: list, **fault) -> dict:
    """For each ``{"prompt", "tokens"}``: feed prompt + tokens[:-1] and
    measure, at every generated position, the largest reference logit
    minus the reference logit of the token the system produced (0 where
    the system chose the reference's own argmax). ``logit_std`` is the
    spread a gap is read against: the standard deviation of a position's
    logits over the vocabulary, a mean over positions (the head is the
    embedding, whose scale the configuration's ``assumed`` weights set).

    A sample may carry ``"state"``: the state [mamba layers, H, P, N] its
    slot held when the request had ended. It is held to the reference's
    state after the same tokens (:func:`state_error`) — after prompt +
    tokens or after prompt + tokens[:-1], whichever is nearer: a
    pipelined engine may or may not have fed the last token back before
    it learnt that the request was over — and to the float32 the
    configuration states for it (:func:`mantissa_bits`)."""
    longest = max(len(s["prompt"]) + len(s["tokens"]) for s in samples)
    pad_to = -(-longest // 128) * 128  # one compiled shape for all samples
    most = max(len(s["tokens"]) for s in samples)
    gaps, stds, top_gaps, state_errs, state_bits = [], [], [], [], []
    for s in samples:
        seq = list(s["prompt"]) + list(s["tokens"])
        n0, n = len(s["prompt"]), len(s["tokens"])
        toks = np.zeros((pad_to,), np.int32)
        toks[:len(seq)] = seq  # causal: what follows a position is unseen
        rows = np.minimum(n0 - 1 + np.arange(most), pad_to - 1)
        lg, states = logits(params, cfg, toks, rows=rows,
                            states_after=len(seq), **fault)
        lg = lg[:n]
        chosen = jnp.take_along_axis(
            lg, jnp.asarray(s["tokens"], jnp.int32)[:, None], axis=1)[:, 0]
        top2 = jax.lax.top_k(lg, 2)[0]
        gaps.extend(np.asarray(top2[:, 0] - chosen, np.float64).tolist())
        stds.extend(np.asarray(jnp.std(lg, axis=1), np.float64).tolist())
        top_gaps.extend(np.asarray(top2[:, 0] - top2[:, 1],
                                   np.float64).tolist())
        if s.get("state") is not None and states.size:
            state_errs.append(min(state_error(s["state"], states[:, i])
                                  for i in (0, 1)))
            state_bits.append(mantissa_bits(s["state"]))
    out = {"n": len(gaps), "max_gap": max(gaps),
           "mean_gap": float(np.mean(gaps)),
           "logit_std": float(np.mean(stds)),
           "argmax_share": float(np.mean([g == 0.0 for g in gaps])),
           "median_top2_gap": float(np.median(top_gaps)),
           "finite": bool(np.all(np.isfinite(gaps)))}
    if state_errs:
        out.update(state_err=max(state_errs), state_bits=min(state_bits))
    return out
