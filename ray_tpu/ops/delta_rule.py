"""The gated delta rule's recurrence over a state a sequence keeps, in
place: a Pallas TPU kernel and the plain recurrence it is held to.

Per head, with ``S [dk, dv]`` float32 the state, and for each token a
decay ``a = exp(g)`` in (0, 1) for every key channel, a key ``k`` and a
query ``q`` (``[dk]``), a value ``v`` (``[dv]``) and a step ``b``:

    S <- Diag(a) S;  S <- S + k (b (v - S^T k))^T;  o = S^T q

which is ``S_t = (I - b k k^T) Diag(a) S_{t-1} + b k v^T`` (Kimi Delta
Attention; with ``b`` up to 2 the transition has negative eigenvalues).

The serving engine keeps every layer's states in ONE array, ``state [L,
slots, H, dk, dv]``, in the cache tree beside the KV pages. The kernel
takes the WHOLE array and a layer index, aliased to its output, and
streams each active sequence's heads through VMEM once: a block of heads
is read, carried through the sequence's tokens with ``S`` resident (one
token for a decode row, the chunk's tokens for the prefill lane), and
written back where it came from. Nothing slices a layer or a slot out of
the array and no XLA operation touches it. A sequence that is not in the
step (``n_tok`` 0: a parked decode row, an empty chunk) is neither read
nor written: the active sequences are visited first and the grid steps
left over are aimed at the block the last active one ended on, which the
pipeline then neither fetches again nor writes back early.

A token's ``dk`` vectors (a, k, q) reach the kernel with ``dk`` along
sublanes, a head a lane, so that a head's column is one lane slice and
multiplies ``S [dk, dv]`` by a lane broadcast; its ``dv`` vectors (v, b)
and the output with ``dv`` along lanes, a head a sublane row. The sums
over ``dk`` are sublane sums. Everything is float32 on the VPU: the
state is carried over thousands of tokens and a bfloat16 product in the
recurrence compounds.

Off the TPU callers get :func:`delta_rule_reference` (:func:`recurrence`,
a ``lax.scan`` over tokens, from and to the slots' states); the tests run
the kernel interpreted against it.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from .attention import _on_tpu

_LANES = 128
# heads a block: [hb, 128, 128] float32 is hb x 64 KiB, in and out, each
# double-buffered (8 MiB at 32). The kernel's own time hardly depends on
# it (1.644 / 1.633 / 1.635 ms a layer of 128 rows at 16 / 32 / 64 on a
# v5e: ~187 cycles a head of VPU work against 150 of HBM); what XLA lays
# out round it does (3 x hb columns padded to 128 lanes): the scope took
# 13.3 / 12.5 / 12.3 ms a step (PERF.md Findings PR 39).
_HEAD_BLOCK = 32
# tokens of a sequence a grid step carries the state through
_TOKEN_BLOCK = 8
_VMEM_LIMIT = 48 * 1024 * 1024


def use_kernel() -> bool:
    return _on_tpu()


def recurrence(s0, n_tok, q, k, v, g, beta):
    """The recurrence as a ``lax.scan`` over tokens, from the states ``s0
    [R, H, dk, dv]``: -> (o [R, T, H, dv] float32, the states after each
    sequence's ``n_tok``-th token)."""
    live = jnp.arange(q.shape[1])[None, :] < n_tok[:, None]      # [R, T]

    def token(s, xs):
        qt, kt, vt, gt, bt, on = xs                              # [R, H, ..]
        s1 = s * jnp.exp(gt)[..., None]
        ks = jnp.einsum("rhkv,rhk->rhv", s1, kt,
                        precision=jax.lax.Precision.HIGHEST)
        s2 = s1 + kt[..., None] * (bt[..., None] * (vt - ks))[..., None, :]
        o = jnp.einsum("rhkv,rhk->rhv", s2, qt,
                       precision=jax.lax.Precision.HIGHEST)
        return jnp.where(on[:, None, None, None], s2, s), o

    f32 = lambda x: jnp.moveaxis(x.astype(jnp.float32), 1, 0)   # noqa: E731
    s, o = jax.lax.scan(token, s0, (f32(q), f32(k), f32(v), f32(g),
                                    f32(beta), live.T))
    return jnp.moveaxis(o, 0, 1), s


def delta_rule_reference(state, layer, slot_of, n_tok, q, k, v, g, beta):
    """:func:`recurrence` from and to the slots' states of ``layer``; the
    contract of :func:`delta_rule`. A sequence that is not in the step
    writes its slot's state back as it read it; two sequences never share
    a slot."""
    o, s = recurrence(state[layer, slot_of], n_tok, q, k, v, g, beta)
    return o, state.at[layer, slot_of].set(s)


def _kernel(meta_ref, s_ref, cols_ref, rows_ref, o_ref, so_ref, *, hb: int,
            tb: int, blocks: int):
    from jax.experimental import pallas as pl

    i, tt = pl.program_id(0), pl.program_id(2)
    n_active = meta_ref[1]

    def token(t, src):
        """Token t of the block through every head of the block: the
        heads' chains are independent, so the straight-line code of one
        token lets the scheduler interleave them (a loop over tokens a
        head runs one dependent chain at a time: ~200 cycles a token
        where this takes ~50)."""
        tile = cols_ref[0, 0, t]                                 # [dk, W]
        for h in range(hb):
            a = tile[:, h:h + 1]
            kk = tile[:, hb + h:hb + h + 1]
            qq = tile[:, 2 * hb + h:2 * hb + h + 1]
            v = rows_ref[0, 0, t, 0, h:h + 1, :]                 # [1, dv]
            b = rows_ref[0, 0, t, 1, h:h + 1, :]
            s = src[0, 0, h] * a                                 # [dk, dv]
            ks = jnp.sum(s * kk, axis=0, keepdims=True)
            s = s + kk * (b * (v - ks))
            so_ref[0, 0, h] = s
            o_ref[0, 0, t, h:h + 1, :] = jnp.sum(s * qq, axis=0,
                                                 keepdims=True)

    active = i < n_active
    if tb == 1 and blocks == 1:
        # a decode row: read where the state came in, write where it goes
        pl.when(active)(lambda: token(0, s_ref))
    else:
        # a chunk: the state moves to the output block once and is
        # carried there, token by token, through the sequence's blocks
        @pl.when(active & (tt == 0))
        def _():
            so_ref[...] = s_ref[...]

        @pl.when(active)
        def _():
            def step(t, carry):
                token(t, so_ref)
                return carry

            jax.lax.fori_loop(0, tb, step, 0)

    # no sequence in the step: every grid step is aimed at one block, and
    # what is written back at the end must be what was there
    @pl.when((n_active == 0) & (i == 0) & (pl.program_id(1) == 0)
             & (tt == 0))
    def _():
        so_ref[...] = s_ref[...]
        o_ref[...] = jnp.zeros_like(o_ref)


def delta_rule(state, layer, slot_of, n_tok, q, k, v, g, beta,
               interpret: bool = False, head_block: int = None,
               token_block: int = None):
    """R sequences of T tokens through the recurrence, each from and to
    its slot's state of ``layer`` -> (o [R, T, H, dv] float32, state).

    state [L, slots, H, dk, dv] float32 (donate it: it is updated in
    place); slot_of [R] int32, distinct; n_tok [R] int32: the first
    ``n_tok`` of a sequence's T tokens are real, and a sequence with none
    is not in the step: its state is neither read nor written and its
    ``o`` is unspecified (as are the ``o`` of tokens past ``n_tok``).
    q, k [R, T, H, dk]; v [R, T, H, dv]; g [R, T, H, dk] the log decay
    (<= 0); beta [R, T, H]."""
    if not (interpret or use_kernel()):
        return delta_rule_reference(state, layer, slot_of, n_tok, q, k, v,
                                    g, beta)
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    r, t, h, dk = q.shape
    dv = v.shape[-1]
    hb = min(head_block or _HEAD_BLOCK, h)
    tb = min(token_block or _TOKEN_BLOCK, t)
    if h % hb or t % tb:
        raise ValueError(f"delta_rule cannot tile {h} heads by {hb} or {t} "
                         f"tokens by {tb}")
    if not interpret and (dk % 8 or dv % _LANES or hb % 8):
        raise ValueError(
            f"delta_rule: dk {dk} must fill sublanes, dv {dv} lanes and "
            f"the head block {hb} sublane rows")
    jn, tn = h // hb, t // tb
    width = -(-3 * hb // _LANES) * _LANES
    f32 = jnp.float32
    live = jnp.arange(t)[None, :] < n_tok[:, None]               # [R, T]
    # a token past n_tok leaves the state as it is: S * 1 + k * 0
    a = jnp.where(live[..., None, None], jnp.exp(g.astype(f32)), 1.0)
    beta = jnp.where(live[..., None], beta.astype(f32), 0.0)

    def columns(x):  # [R, T, H, dk] -> [R, J, T, dk, hb]
        return x.astype(f32).reshape(r, t, jn, hb, dk).transpose(
            0, 2, 1, 4, 3)

    cols = jnp.concatenate([columns(a), columns(k), columns(q)], axis=-1)
    if width > 3 * hb:
        cols = jnp.pad(cols, ((0, 0),) * 4 + ((0, width - 3 * hb),))

    def lanes(x):  # [R, T, H, dv] -> [R, J, T, hb, dv]
        return x.astype(f32).reshape(r, t, jn, hb, dv).transpose(
            0, 2, 1, 3, 4)

    rows = jnp.stack([lanes(v), lanes(jnp.broadcast_to(
        beta[..., None], (r, t, h, dv)))], axis=3)       # [R, J, T, 2, hb, dv]
    # active sequences first, in their own order
    on = n_tok > 0
    order = jnp.argsort(~on, stable=True).astype(jnp.int32)
    meta = jnp.concatenate([
        jnp.stack([jnp.asarray(layer, jnp.int32),
                   on.sum().astype(jnp.int32)]),
        order, slot_of.astype(jnp.int32)])

    def where(i, j, tt, meta_ref):
        n_active = meta_ref[1]
        act = i < n_active
        seq = meta_ref[2 + jnp.where(act, i, jnp.maximum(n_active - 1, 0))]
        return (act, seq, jnp.where(act, j, jn - 1),
                jnp.where(act, tt, tn - 1))

    def state_at(i, j, tt, meta_ref):
        _, seq, jj, _ = where(i, j, tt, meta_ref)
        return meta_ref[0], meta_ref[2 + r + seq], jj, 0, 0

    def token_at(trailing):
        def at(i, j, tt, meta_ref):
            _, seq, jj, tk = where(i, j, tt, meta_ref)
            return (seq, jj, tk) + (0,) * trailing
        return at

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1, grid=(r, jn, tn),
        in_specs=[
            pl.BlockSpec((1, 1, hb, dk, dv), state_at),
            pl.BlockSpec((1, 1, tb, dk, width), token_at(2)),
            pl.BlockSpec((1, 1, tb, 2, hb, dv), token_at(3)),
        ],
        out_specs=[
            pl.BlockSpec((1, 1, tb, hb, dv), token_at(2)),
            pl.BlockSpec((1, 1, hb, dk, dv), state_at),
        ])
    o, state = pl.pallas_call(
        functools.partial(_kernel, hb=hb, tb=tb, blocks=tn),
        grid_spec=grid_spec,
        out_shape=[jax.ShapeDtypeStruct((r, jn, t, hb, dv), f32),
                   jax.ShapeDtypeStruct(state.shape, state.dtype)],
        # operand 0 is the scalar-prefetched meta; the state is operand 1
        input_output_aliases={1: 1},
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary", "arbitrary", "arbitrary"),
            vmem_limit_bytes=_VMEM_LIMIT),
        interpret=interpret, name="delta_rule",
    )(meta, state, cols, rows)
    # [R, J, T, hb, dv] -> [R, T, H, dv]
    return o.transpose(0, 2, 1, 3, 4).reshape(r, t, h, dv), state
