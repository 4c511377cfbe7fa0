"""The gated delta rule's recurrence over a state a sequence keeps, in
place: a Pallas TPU kernel and the plain recurrence it is held to.

Per head, with ``S [dk, dv]`` float32 the state, and for each token a
decay ``a = exp(g)`` in (0, 1) for every key channel, a key ``k`` and a
query ``q`` (``[dk]``), a value ``v`` (``[dv]``) and a step ``b``:

    S <- Diag(a) S;  S <- S + k (b (v - S^T k))^T;  o = S^T q

which is ``S_t = (I - b k k^T) Diag(a) S_{t-1} + b k v^T`` (Kimi Delta
Attention; with ``b`` up to 2 the transition has negative eigenvalues).

The serving engine keeps every layer's states in ONE array, ``state [L,
slots, H, dk, dv]``, in the cache tree beside the KV pages. One call a
layer takes the WHOLE array where it lies in HBM and a layer index,
aliased to its output, and a step's rows as the layer computes them: the
decode rows, one token of slot i each, then one slot's chunk. The kernel
moves the states itself: the block of heads of each active decode row is
copied into VMEM, carried through the row's token in place and copied
back, a row a grid step (a burst of one: a row's 64 heads are the 4 MiB
``ops/slot_stream.py`` asks of a burst), and the chunk's state stays in
VMEM from the first step to the last while its tokens are carried through
it beside the decode rows (their stream leaves the vector units half
idle). Nothing
slices a layer or a slot out of the array and no XLA operation touches
it. A row that is not in the step (a parked decode row, an empty chunk,
the chunk's tokens past its last real one) starts no DMA: its state is
neither read nor written.

A row's ``dk`` vectors (g, k, q) arrive ``[H, dk]``, ``dk`` along lanes;
the kernel takes ``exp`` and transposes a block's tiles on the XLU, so
that ``dk`` lies along sublanes, a head a lane: a head's column is then
one lane slice and multiplies ``S [dk, dv]`` by a lane broadcast. Its
``dv`` vectors (v, b) and the output have ``dv`` along lanes, a head a
sublane row. The sums over ``dk`` are sublane sums. Everything is float32
on the VPU: the state is carried over thousands of tokens and a bfloat16
product in the recurrence compounds.

Off the TPU callers get :func:`delta_rule_reference` (:func:`recurrence`,
a ``lax.scan`` over tokens, from and to the slots' states); the tests run
the kernel interpreted against it.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from .attention import _on_tpu
from .slot_stream import (burst_rows, one_row, plan_valid,  # noqa: F401
                          row_maps, rows_block, step_plan, stream_geometry,
                          stream_rows)

_LANES = 128
# heads a block: a slot's 64 heads are 4 MiB contiguous, one DMA a row each
# way. On a v5e a call of 128 rows takes 1.63 ms at 64 heads a block and
# 1.72 at 32 (PERF.md Findings PR 41: the stream binds, and a grid step's
# fixed costs are exposed beside it); three blocks of states, the chunk's,
# and every row's o twice are 29 MiB of VMEM.
_HEAD_BLOCK = 64
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


def delta_rule_reference(state, layer, plan, q, k, v, g, beta):
    """:func:`recurrence` from and to the slots' states of ``layer``; the
    contract of :func:`delta_rule`. A row that is not in the step writes
    its slot's state back as it read it."""
    b = plan.shape[0] - 3
    xs = (q, k, v, g, beta)
    o, s = recurrence(state[layer, :b], plan_valid(plan).astype(jnp.int32),
                      *(x[:b, None] for x in xs))
    o, state = o[:, 0], state.at[layer, :b].set(s)
    if q.shape[0] > b:
        oc, s = recurrence(state[layer, plan[1]][None], plan[2][None],
                           *(x[None, b:] for x in xs))
        o = jnp.concatenate([o, oc[0]], axis=0)
        state = state.at[layer, plan[1]].set(s[0])
    return o, state


def _prepare(q_ref, k_ref, g_ref, b_ref, row, first_head, cols_ref,
             beta_ref):
    """A row's per-head vectors as :func:`_through` reads them: a (the
    decay), k and q with dk along sublanes, a head a lane (the row's
    ``[hb, dk]`` tiles transposed on the XLU, 128 rows at a time), and b a
    head broadcast along lanes."""
    from jax.experimental import pallas as pl

    f32 = jnp.float32
    hb, dv = beta_ref.shape
    kk = k_ref[0].astype(f32)                                    # [hb, dk]
    stack = [jnp.exp(g_ref[0].astype(f32)), kk, q_ref[0].astype(f32)]
    width = cols_ref.shape[1]
    if width > 3 * hb:
        stack.append(jnp.zeros((width - 3 * hb, kk.shape[1]), f32))
    stack = jnp.concatenate(stack, axis=0)                       # [W, dk]
    for w in range(0, width, _LANES):
        cols_ref[:, w:w + _LANES] = stack[w:w + _LANES].T
    # the row's beta [1, H] -> this block's heads down a column
    heads = b_ref.shape[1]
    mine = (jax.lax.broadcasted_iota(jnp.int32, (hb, heads), 0) + first_head
            == jax.lax.broadcasted_iota(jnp.int32, (hb, heads), 1))
    beta = jnp.sum(jnp.where(mine, b_ref[pl.ds(row, 1), :].astype(f32), 0.0),
                   axis=1, keepdims=True)                        # [hb, 1]
    beta_ref[...] = jnp.broadcast_to(beta, (hb, dv))


def _through(state, cols_ref, v_ref, beta_ref, o_ref, row, lo: int, hi: int):
    """The row's token through heads lo .. hi of the block, in place in
    ``state [hb, dk, dv]``: the heads' chains are independent, so one
    straight-line body lets the scheduler interleave them (a loop over
    tokens a head runs one dependent chain at a time: ~200 cycles a token
    where this takes ~95)."""
    from jax.experimental import pallas as pl

    hb = beta_ref.shape[0]
    tiles = [cols_ref[:, w:w + _LANES]
             for w in range(0, cols_ref.shape[1], _LANES)]

    def col(at):
        return tiles[at // _LANES][:, at % _LANES:at % _LANES + 1]

    for h in range(lo, hi):
        a, kk, qq = col(h), col(hb + h), col(2 * hb + h)         # [dk, 1]
        v = v_ref[0, h:h + 1, :].astype(jnp.float32)             # [1, dv]
        s1 = state[h] * a                                        # [dk, dv]
        ks = jnp.sum(s1 * kk, axis=0, keepdims=True)
        s2 = s1 + kk * (beta_ref[h:h + 1, :] * (v - ks))
        state[h] = s2
        o_ref[pl.ds(row, 1), h:h + 1, :] = jnp.sum(
            s2 * qq, axis=0, keepdims=True)[None]


def _kernel(plan_ref, layer_ref, *refs, hb: int, b: int, c: int, burst: int,
            **geo):
    """``slot_stream.stream_rows`` with the delta rule's arithmetic: a
    block of ``hb`` heads a grid step, of each row of a burst."""
    refs = list(refs)
    rows = range(burst)
    s_hbm = refs.pop(0)
    # q, k, v, g: a row of the burst each, then the chunk's tokens of the step
    dec = [[refs.pop(0) for _ in range(4)] for _ in rows] if b else None
    chk = [refs.pop(0) for _ in range(4)] if c else None
    b_ref, o_ref, so_hbm = (refs.pop(0) for _ in range(3))
    sbuf = [refs.pop(0) for _ in rows]
    cbuf = refs.pop(0)
    cols_d = [refs.pop(0) for _ in rows]
    cols_c = refs.pop(0)
    beta_d = [refs.pop(0) for _ in rows]
    beta_c = refs.pop(0)
    rsem, wsem = ([refs.pop(0) for _ in rows] for _ in range(2))
    csem, = refs

    def prepare_d(row, j, k):
        q, kk, _, g = dec[k]
        _prepare(q, kk, g, b_ref, row, j * hb, cols_d[k], beta_d[k])

    def prepare_c(row, j, i):
        q, kk, _, g = (one_row(x, i) for x in chk)
        _prepare(q, kk, g, b_ref, row, j * hb, cols_c, beta_c)

    stream_rows(
        plan_ref, layer_ref, s_hbm, so_hbm, sbuf, cbuf, rsem, wsem, csem,
        ub=hb, b=b, c=c, burst=burst, **geo,
        prepare_d=prepare_d, prepare_c=prepare_c,
        through_d=lambda state, row, lo, hi, k: _through(
            state, cols_d[k], dec[k][2], beta_d[k], o_ref, row, lo, hi),
        through_c=lambda state, row, lo, hi, i: _through(
            state, cols_c, one_row(chk[2], i), beta_c, o_ref, row, lo, hi))


def delta_rule(state, layer, plan, q, k, v, g, beta, interpret: bool = False,
               head_block: int = None, burst: int = None):
    """A step's N = B + C rows through the recurrence, each from and to
    its slot's state of ``layer`` -> (o [N, H, dv] float32, state).

    Rows ``[:B]`` are one token of slot i each, rows ``[B:]`` one slot's
    chunk in order; ``plan`` (:func:`step_plan`, B = its length - 3) says
    which decode rows are in the step, and the chunk's slot and how many
    of its C tokens are real. state [L, slots, H, dk, dv] float32 (donate
    it: it is updated in place). A decode row that is not in the step,
    and an empty chunk, are neither read nor written, and their ``o`` is
    unspecified (as are the ``o`` of the chunk's tokens past its last
    real one); the chunk's slot is no active decode row's. q, k, g [N, H,
    dk] (g the log decay, <= 0); v [N, H, dv]; beta [N, H]: the rows as
    the layer computes them, in any float dtype. ``head_block`` and
    ``burst`` are the tests' and the probes': heads a grid step, and
    decode rows a burst where ``slot_stream.burst_rows`` is not to say.

    The state never leaves HBM but by the kernel's own DMAs: a grid step
    takes the next burst of active decode rows' blocks of heads into VMEM
    (one row where a block is 4 MiB: ``ops/slot_stream.py``), carries
    each through its row's token and sends it back, and beside it carries
    a share of the chunk's heads through one of its tokens, the chunk's
    state resident from the first step to the last."""
    if not (interpret or use_kernel()):
        return delta_rule_reference(state, layer, plan, q, k, v, g, beta)
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    n, h, dk = q.shape
    dv = v.shape[-1]
    b = plan.shape[0] - 3
    c = n - b
    hb = min(head_block or _HEAD_BLOCK, h)
    if h % hb:
        raise ValueError(f"delta_rule cannot tile {h} heads by {hb}")
    if not interpret and (dk % _LANES or dv % _LANES or hb % 8):
        raise ValueError(
            f"delta_rule: dk {dk} and dv {dv} must fill lanes and the "
            f"head block {hb} sublane rows")
    burst = burst or burst_rows(hb * dk * dv * state.dtype.itemsize, b)
    tokens, stride, parts, steps = stream_geometry(b, c, hb, burst)
    decode_rows, chunk_row = row_maps(b, c, hb, burst, tokens, stride)
    width = -(-3 * hb // _LANES) * _LANES

    hbm = pl.BlockSpec(memory_space=pl.ANY)
    rows = [pl.BlockSpec((1, hb, x.shape[-1]), row)
            for row in decode_rows[:burst * bool(b)] for x in (q, k, v, g)] + (
        [pl.BlockSpec(rows_block(tokens, hb, x.shape[-1]), chunk_row)
         for x in (q, k, v, g)] if c else [])
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2, grid=(h // hb, steps),
        in_specs=[hbm] + rows + [
            # every row's beta, fetched once: a row is a sublane
            pl.BlockSpec((n, h), lambda j, s, *_: (0, 0))],
        out_specs=[
            # every row's o, written back once a block of heads
            pl.BlockSpec((n, hb, dv), lambda j, s, *_: (0, j, 0)),
            hbm],
        scratch_shapes=[
            # the decode rows' states, a ring over bursts a row of a burst
            *[pltpu.VMEM((3, hb, dk, dv), jnp.float32)] * burst,
            pltpu.VMEM((hb, dk, dv), jnp.float32),       # the chunk's
            *[pltpu.VMEM((dk, width), jnp.float32)] * (burst + 1),
            *[pltpu.VMEM((hb, dv), jnp.float32)] * (burst + 1),
            *[pltpu.SemaphoreType.DMA((3,))] * (2 * burst),
            pltpu.SemaphoreType.DMA((2,))])
    operands = (q, k, v, g) * (burst * bool(b) + bool(c))
    return pl.pallas_call(
        functools.partial(_kernel, hb=hb, b=b, c=c, burst=burst,
                          tokens=tokens, stride=stride, parts=parts,
                          steps=steps),
        grid_spec=grid_spec,
        out_shape=[jax.ShapeDtypeStruct((n, h, dv), jnp.float32),
                   jax.ShapeDtypeStruct(state.shape, state.dtype)],
        # operands 0 and 1 are scalar-prefetched; the state is operand 2
        input_output_aliases={2: 1},
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary", "arbitrary"),
            # the state output is written by the kernel's DMAs alone
            vmem_limit_bytes=_VMEM_LIMIT, has_side_effects=True),
        interpret=interpret, name="delta_rule",
    )(plan, jnp.reshape(layer, (1,)).astype(jnp.int32), state, *operands,
      beta)
