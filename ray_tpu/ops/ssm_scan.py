"""A selective state-space recurrence (Mamba-2) over a state a sequence
keeps, in place: a Pallas TPU kernel and the plain recurrence it is held
to.

Per head ``p`` of a layer, with ``S_p [P, N]`` float32 the state, and for
each token a decay ``a_p`` in (0, 1) (ONE number a head), an input ``x_p
[P]`` already scaled by the token's step size, and ``B``, ``C`` ``[N]``
shared by all heads:

    S_p <- a_p S_p + x_p B^T;  y_p = S_p C

(no correction by ``S^T k`` and no ``beta``, which ``ops/delta_rule.py``
has; the skip ``D x`` is elementwise on the rows and the caller's). Every
one of a layer's ``H x P`` CHANNELS thus keeps ``N`` numbers that no other
channel reads: ``S[n, w] <- a[w] S[n, w] + B[n] x[w]``, ``y[w] = sum_n
S[n, w] C[n]``, with ``a`` a head's number repeated over its channels.

That is how the state is laid out: ``state [L, slots, G, N, W]``, the
channels in G blocks of W (W = 128 lanes: two heads of 64), N along
sublanes. A block ``[N, W]`` is multiplied by a row vector (``a``, ``x``:
a sublane broadcast, free), by ``B`` and ``C`` as columns (one 128 x 128
transpose and two lane broadcasts a ROW, shared by all its blocks, since
the model has one group), and the sum over N is a sum of vregs and one
sublane fold: no lane reduction anywhere. With P along sublanes (``[H,
P, N]``) every head would need eight lane reductions a token, 512 a row.
``heads_view`` gives the state as ``[.., H, P, N]``.

The engine keeps every layer's states in that ONE array beside the KV
pages; one call a layer takes the whole array where it lies in HBM,
aliased to its output, a layer index and a step's rows as the layer
computes them, and moves the states with its own DMAs, a burst of decode
rows a grid step: ``ops/slot_stream.py``, shared with the delta rule,
says how. A prompt chunk's tokens are carried through the chunk's
resident state one after the other, several a grid step, beside the
decode rows' stream: the recurrence itself, in float32, and not its
chunked matmul form (PERF.md Findings PR 43 says what that costs).

Off the TPU callers get :func:`ssm_scan_reference` (:func:`recurrence`, a
``lax.scan`` over tokens); the tests run the kernel interpreted against
it.
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
# blocks of 128 channels a grid step: a slot's 32 blocks (64 heads of 64)
# are 2 MiB contiguous, one DMA a row each way and two rows a burst
# (``slot_stream.BURST_BYTES``); three bursts, the chunk's block and every
# row's y twice are ~25 MiB of VMEM at 64 rows and a lane of 256.
_BLOCKS = 32
_VMEM_LIMIT = 48 * 1024 * 1024


def use_kernel() -> bool:
    return _on_tpu()


def blocks_of(channels: int):
    """(G, W): how ``channels`` = heads x head_dim channels are laid out."""
    w = min(_LANES, channels)
    if channels % w:
        raise ValueError(f"{channels} channels do not fill blocks of {w}")
    return channels // w, w


def heads_view(state, heads: int):
    """state [.., G, N, W] -> [.., heads, P, N], as the equations index
    it."""
    *lead, g, n, w = state.shape
    return jnp.moveaxis(state, -2, -1).reshape(*lead, heads,
                                               g * w // heads, n)


def recurrence(s0, n_tok, x, a, bc):
    """The recurrence as a ``lax.scan`` over tokens, from the states ``s0
    [R, G, N, W]``: x, a [R, T, G, W], bc [R, T, 2, N] (B, then C) -> (y
    [R, T, G, W] float32, the states after each sequence's ``n_tok``-th
    token)."""
    live = jnp.arange(x.shape[1])[None, :] < n_tok[:, None]      # [R, T]

    def token(s, xs):
        xt, at, bct, on = xs
        s1 = s * at[:, :, None, :] \
            + bct[:, 0][:, None, :, None] * xt[:, :, None, :]
        y = jnp.sum(s1 * bct[:, 1][:, None, :, None], axis=2)
        return jnp.where(on[:, None, None, None], s1, s), y

    f32 = lambda v: jnp.moveaxis(v.astype(jnp.float32), 1, 0)   # noqa: E731
    s, y = jax.lax.scan(token, s0, (f32(x), f32(a), f32(bc), live.T))
    return jnp.moveaxis(y, 0, 1), s


def ssm_scan_reference(state, layer, plan, x, a, bc):
    """:func:`recurrence` from and to the slots' states of ``layer``; the
    contract of :func:`ssm_scan`. A row that is not in the step writes
    its slot's state back as it read it."""
    b = plan.shape[0] - 3
    xs = (x, a, bc)
    y, s = recurrence(state[layer, :b], plan_valid(plan).astype(jnp.int32),
                      *(v[:b, None] for v in xs))
    y, state = y[:, 0], state.at[layer, :b].set(s)
    if x.shape[0] > b:
        yc, s = recurrence(state[layer, plan[1]][None], plan[2][None],
                           *(v[None, b:] for v in xs))
        y = jnp.concatenate([y, yc[0]], axis=0)
        state = state.at[layer, plan[1]].set(s[0])
    return y, state


def _prepare(bc_ref, b_tile, c_tile):
    """A row's B and C ``[N]``, which arrive along lanes, as the tiles
    :func:`_through` multiplies a block ``[N, W]`` by: N down the
    sublanes (one transpose on the XLU), the same in every lane."""
    n, w = b_tile.shape
    rows = jnp.concatenate(
        [bc_ref[0].astype(jnp.float32),
         jnp.zeros((_LANES - 2, n), jnp.float32)], axis=0)      # [128, N]
    cols = rows.T                                                # [N, 128]
    b_tile[...] = jnp.broadcast_to(cols[:, 0:1], (n, w))
    c_tile[...] = jnp.broadcast_to(cols[:, 1:2], (n, w))


def _through(state, x_ref, a_ref, b_tile, c_tile, y_ref, row, lo: int,
             hi: int):
    """The row's token through blocks lo .. hi, in place in ``state [gb,
    N, W]``: the blocks are independent, so one straight-line body lets
    the scheduler interleave them."""
    from jax.experimental import pallas as pl

    for g in range(lo, hi):
        s1 = state[g] * a_ref[0, g:g + 1, :].astype(jnp.float32) \
            + b_tile[...] * x_ref[0, g:g + 1, :].astype(jnp.float32)
        state[g] = s1
        y_ref[pl.ds(row, 1), g:g + 1, :] = jnp.sum(
            s1 * c_tile[...], axis=0, keepdims=True)[None]


def _kernel(plan_ref, layer_ref, *refs, gb: int, b: int, c: int, burst: int,
            **geo):
    """``slot_stream.stream_rows`` with this recurrence's arithmetic: a
    block of ``gb`` blocks of channels a grid step, of each row of a
    burst."""
    refs = list(refs)
    rows = range(burst)
    s_hbm = refs.pop(0)
    # x, a, bc: a row of the burst each, then the chunk's tokens of the step
    dec = [[refs.pop(0) for _ in range(3)] for _ in rows] if b else None
    chk = [refs.pop(0) for _ in range(3)] if c else None
    y_ref, so_hbm = refs.pop(0), refs.pop(0)
    sbuf = [refs.pop(0) for _ in rows]
    cbuf = refs.pop(0)
    tiles = [[refs.pop(0), refs.pop(0)] for _ in rows]       # B, C a row
    bcc, ccc = refs.pop(0), refs.pop(0)
    rsem, wsem = ([refs.pop(0) for _ in rows] for _ in range(2))
    csem, = refs
    stream_rows(
        plan_ref, layer_ref, s_hbm, so_hbm, sbuf, cbuf, rsem, wsem, csem,
        ub=gb, b=b, c=c, burst=burst, **geo,
        prepare_d=lambda row, j, k: _prepare(dec[k][2], *tiles[k]),
        through_d=lambda state, row, lo, hi, k: _through(
            state, dec[k][0], dec[k][1], *tiles[k], y_ref, row, lo, hi),
        prepare_c=lambda row, j, i: _prepare(one_row(chk[2], i), bcc, ccc),
        through_c=lambda state, row, lo, hi, i: _through(
            state, one_row(chk[0], i), one_row(chk[1], i), bcc, ccc, y_ref,
            row, lo, hi))


def ssm_scan(state, layer, plan, x, a, bc, interpret: bool = False,
             blocks: int = None, burst: int = None):
    """A step's R = B + C rows through the recurrence, each from and to
    its slot's state of ``layer`` -> (y [R, G, W] float32, state).

    Rows ``[:B]`` are one token of slot i each, rows ``[B:]`` one slot's
    chunk in order; ``plan`` (``slot_stream.step_plan``, B = its length -
    3) says which decode rows are in the step, and the chunk's slot and
    how many of its C tokens are real. state [L, slots, G, N, W] float32
    (donate it: it is updated in place). A decode row that is not in the
    step, and an empty chunk, are neither read nor written, and their
    ``y`` is unspecified (as are the ``y`` of the chunk's tokens past its
    last real one) and may be NaN: a caller whose later operations mix a
    step's rows masks them; the chunk's slot is no active decode row's. x [R, G,
    W]: the rows' inputs times their step sizes; a [R, G, W]: the decay,
    a head's number in each of its channels; bc [R, 2, N]: B, then C; in
    any float dtype. ``blocks`` and ``burst`` are the tests' and the
    probes': blocks of channels a grid step, and decode rows a burst
    where ``slot_stream.burst_rows`` is not to say."""
    if not (interpret or use_kernel()):
        return ssm_scan_reference(state, layer, plan, x, a, bc)
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    r, g, w = x.shape
    n = bc.shape[-1]
    b = plan.shape[0] - 3
    c = r - b
    gb = min(blocks or _BLOCKS, g)
    if g % gb:
        raise ValueError(f"ssm_scan cannot tile {g} blocks by {gb}")
    if n != _LANES or (not interpret and w % _LANES):
        raise ValueError(
            f"ssm_scan: the state size {n} must be {_LANES} and a block's "
            f"{w} channels must fill lanes")
    burst = burst or burst_rows(gb * n * w * state.dtype.itemsize, b)
    tokens, stride, parts, steps = stream_geometry(b, c, gb, burst)
    decode_rows, chunk_row = row_maps(b, c, gb, burst, tokens, stride)

    def specs(row, rows):
        block = functools.partial(rows_block, rows)
        return [pl.BlockSpec(block(gb, w), row), pl.BlockSpec(block(gb, w), row),
                pl.BlockSpec(block(2, n), lambda j, s, *refs: row(
                    0, s, *refs))]

    hbm = pl.BlockSpec(memory_space=pl.ANY)
    # the decode rows' states, a ring over bursts a row of a burst
    ring, tile = pltpu.VMEM((3, gb, n, w), jnp.float32), pltpu.VMEM(
        (n, w), jnp.float32)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2, grid=(g // gb, steps),
        in_specs=[hbm] + [spec for row in decode_rows[:burst * bool(b)]
                          for spec in specs(row, 1)]
        + (specs(chunk_row, tokens) if c else []),
        out_specs=[
            # every row's y, written back once a block of channels
            pl.BlockSpec((r, gb, w), lambda j, s, *_: (0, j, 0)),
            hbm],
        scratch_shapes=[
            *[ring] * burst,
            pltpu.VMEM((gb, n, w), jnp.float32),         # the chunk's
            *[tile] * (2 * burst + 2),
            *[pltpu.SemaphoreType.DMA((3,))] * (2 * burst),
            pltpu.SemaphoreType.DMA((2,))])
    operands = (x, a, bc) * (burst * bool(b) + bool(c))
    return pl.pallas_call(
        functools.partial(_kernel, gb=gb, b=b, c=c, burst=burst,
                          tokens=tokens, stride=stride, parts=parts,
                          steps=steps),
        grid_spec=grid_spec,
        out_shape=[jax.ShapeDtypeStruct((r, g, w), jnp.float32),
                   jax.ShapeDtypeStruct(state.shape, state.dtype)],
        # operands 0 and 1 are scalar-prefetched; the state is operand 2
        input_output_aliases={2: 1},
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary", "arbitrary"),
            # the state output is written by the kernel's DMAs alone
            vmem_limit_bytes=_VMEM_LIMIT, has_side_effects=True),
        interpret=interpret, name="ssm_scan",
    )(plan, jnp.reshape(layer, (1,)).astype(jnp.int32), state, *operands)
