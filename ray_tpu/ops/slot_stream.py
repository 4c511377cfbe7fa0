"""What the recurrences over a state a slot keeps have in common
(``ops/delta_rule.py``, ``ops/ssm_scan.py``): the plan of a step's rows,
and the part of their Pallas kernels that moves the states.

The serving engine keeps every layer's states in ONE array ``state [L,
slots, U, ..]`` (U units a slot: heads, or blocks of channels) in the
cache tree beside the KV pages. A kernel takes the whole array where it
lies in HBM (``pl.ANY``), aliased to its output, a layer index, and a
step's rows as the layer computes them: the decode rows, one token of
slot i each, then one slot's chunk. :func:`stream_rows` is the kernel's
body but for the arithmetic: over a grid ``(U // ub, steps)`` the block of
``ub`` units of each active decode row is copied into VMEM, handed to the
caller's ``through_d`` for the row's token and copied back, a row a grid
step, while the chunk's block stays in VMEM from the first step to the
last and ``through_c`` carries its tokens through it beside the decode
rows (their stream leaves the vector units half idle). A row that is not
in the step (a parked decode row, an empty chunk, the chunk's tokens past
its last real one) starts no DMA: its state is neither read nor written.
"""

from __future__ import annotations

import jax.numpy as jnp


def step_plan(valid, chunk_at=None):
    """What of a step's rows is in the step, as the kernels read it: an
    int32 vector ``[active decode rows, the chunk's slot, its live
    tokens, the decode rows with the active ones first]``. Built once a
    step, on the device, from ``valid [B]`` bool and ``chunk_at`` = None
    or (slot, n_valid); every layer's call takes the same one."""
    slot, n_valid = (0, 0) if chunk_at is None else chunk_at
    order = jnp.argsort(~valid, stable=True)
    return jnp.concatenate([
        jnp.stack([valid.sum(), slot, n_valid]).astype(jnp.int32),
        order.astype(jnp.int32)])


def plan_valid(plan):
    """``valid [B]`` bool as :func:`step_plan` was given it."""
    b = plan.shape[0] - 3
    return jnp.zeros((b,), bool).at[plan[3:]].set(jnp.arange(b) < plan[0])


def stream_geometry(b: int, c: int, ub: int):
    """-> (stride, parts, steps): a chunk token every ``stride`` grid
    steps, its units over ``parts`` of them (the decode rows' stream has
    vector time to spare, and the chunk's tokens take it in shares small
    enough to hide there), ``steps`` grid steps a block of units."""
    stride = max(1, b // c) if c else 1
    parts = stride if ub % stride == 0 else 1
    return stride, parts, max(b, c * stride)


def row_maps(b: int, c: int, stride: int):
    """Index maps ``(j, s, plan_ref, layer_ref) -> block`` of a ``[N, U,
    ..]`` operand holding a step's rows, a block ``(1, ub, ..)``: the
    s-th active decode row, and the chunk's token of grid step s."""

    def decode_row(j, s, plan_ref, layer_ref):
        at = jnp.clip(jnp.minimum(s, plan_ref[0] - 1), 0, b - 1)
        return plan_ref[3 + at], j, 0

    def chunk_row(j, s, plan_ref, layer_ref):
        return b + jnp.clip(jnp.minimum(s // stride, plan_ref[2] - 1), 0,
                            c - 1), j, 0

    return decode_row, chunk_row


def stream_rows(plan_ref, layer_ref, s_hbm, so_hbm, sbuf, cbuf, rsem, wsem,
                csem, *, ub: int, b: int, c: int, stride: int, parts: int,
                steps: int, prepare_d, through_d, prepare_c, through_c):
    """One grid step ``(j, s)`` of a kernel over ``state [L, slots, U,
    ..]`` (``s_hbm``, aliased to ``so_hbm``): block j of ``ub`` units of
    the s-th active decode row through the row's token, and a share of
    the chunk's block through one of its tokens.

    sbuf [3, ub, ..], cbuf [ub, ..] VMEM; rsem, wsem DMA semaphores [3],
    csem [2]. ``prepare_d(row, j)`` lays out what ``through_d(block_ref,
    row, lo, hi)`` reads to carry units lo .. hi of the block through
    decode row ``row``'s token, in place; ``prepare_c`` / ``through_c``
    the same for the chunk's token at row ``row`` of the step's rows
    (called only if the step has decode rows / a chunk)."""
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    j, s = pl.program_id(0), pl.program_id(1)
    layer, units = layer_ref[0], pl.ds(j * ub, ub)
    n_active, slot_c, n_valid = plan_ref[0], plan_ref[1], plan_ref[2]

    def row_of(i):
        """The i-th active decode row, which is its slot."""
        return plan_ref[3 + jnp.clip(i, 0, b - 1)] if b else jnp.int32(0)

    def read(i):
        return pltpu.make_async_copy(s_hbm.at[layer, row_of(i), units],
                                     sbuf.at[i % 3], rsem.at[i % 3])

    def write(i):
        return pltpu.make_async_copy(sbuf.at[i % 3],
                                     so_hbm.at[layer, row_of(i), units],
                                     wsem.at[i % 3])

    chunk_in = pltpu.make_async_copy(s_hbm.at[layer, slot_c, units], cbuf,
                                     csem.at[0])
    chunk_out = pltpu.make_async_copy(cbuf, so_hbm.at[layer, slot_c, units],
                                      csem.at[1])

    # this step's decode row (the s-th active one) and its share of the
    # chunk: units group r of token t
    has_d = s < n_active
    t, r = s // stride, s % stride
    has_c = (t < n_valid) & (r < parts)
    row_d, row_c = row_of(s), b + t
    group = ub // parts
    halves = [(0, -(-ub // 2)), (-(-ub // 2), ub)]
    chunk_halves = [(0, -(-group // 2)), (-(-group // 2), group)]

    def if_row(i, dma):
        """``dma(i)`` if i is an active decode row."""
        pl.when((i >= 0) & (i < n_active))(lambda: dma(i))

    @pl.when(s == 0)
    def _():
        pl.when(n_valid > 0)(chunk_in.start)

        @pl.when(n_active > 0)
        def _():
            read(0).start()
            read(0).wait()

        pl.when(n_valid > 0)(chunk_in.wait)

    # Two streams a step, a read then a write, never both at once: HBM
    # gives the two together 80 % of its rate and one after the other
    # 85 % (PERF.md Findings PR 41). The next row's state comes in while
    # this row's vectors are laid out and half of its units (and of the
    # chunk's share) are carried through their token; the last row's
    # goes back during the other half. (A write left in flight across
    # the step's boundary runs beside the next rows' fetches: slower.)
    if_row(s + 1, lambda i: read(i).start())
    if b:
        pl.when(has_d)(lambda: prepare_d(row_d, j))
    if c:
        pl.when(has_c & (r == 0))(lambda: prepare_c(row_c, j))
    for phase, ((lo, hi), (clo, chi)) in enumerate(zip(halves, chunk_halves)):
        if phase == 1:
            if_row(s + 1, lambda i: read(i).wait())
            if_row(s - 1, lambda i: write(i).start())
        if b and hi > lo:
            pl.when(has_d)(lambda lo=lo, hi=hi: through_d(
                sbuf.at[s % 3], row_d, lo, hi))
        if c and chi > clo:
            for g in range(parts):
                pl.when(has_c & (r == g))(
                    lambda g=g, clo=clo, chi=chi: through_c(
                        cbuf, row_c, g * group + clo, g * group + chi))
    if_row(s - 1, lambda i: write(i).wait())

    # the chunk's state goes back after its last token's last units
    pl.when(has_c & (t == n_valid - 1) & (r == parts - 1))(chunk_out.start)

    @pl.when(s == steps - 1)
    def _():
        @pl.when(has_d)
        def _():
            write(s).start()
            write(s).wait()

        pl.when(n_valid > 0)(chunk_out.wait)
