"""What the recurrences over a state a slot keeps have in common
(``ops/delta_rule.py``, ``ops/ssm_scan.py``): the plan of a step's rows,
and the part of their Pallas kernels that moves the states.

The serving engine keeps every layer's states in ONE array ``state [L,
slots, U, ..]`` (U units a slot: heads, or blocks of channels) in the
cache tree beside the KV pages. A kernel takes the whole array where it
lies in HBM (``pl.ANY``), aliased to its output, a layer index, and a
step's rows as the layer computes them: the decode rows, one token of
slot i each, then one slot's chunk. :func:`stream_rows` is the kernel's
body but for the arithmetic: over a grid ``(U // ub, steps)`` the blocks
of ``ub`` units of a BURST of active decode rows are copied into VMEM,
handed to the caller's ``through_d`` for each row's token and copied
back, a burst a grid step, while the chunk's block stays in VMEM from the
first step to the last and ``through_c`` carries its tokens through it
beside the decode rows (their stream leaves the vector units half idle).

A burst is the fewest rows whose blocks are :data:`BURST_BYTES` together
(:func:`burst_rows`: it follows the bytes of a row's block, nothing
else): their reads are started back to back and waited for together, and
so are their writes a step later, because this HBM moves more of its
nominal rate the larger a burst is. A row that is not in the step (a
parked decode row, the rows a part-filled last burst lacks, an empty
chunk, the chunk's tokens past its last real one) starts no DMA: its
state is neither read nor written.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

# The fewest bytes a burst moves each way. On a v5e, a read stream then a
# write stream a grid step and nothing computed (the probes under
# ``benchmark/tools/``, PERF.md Findings PR 44), of 819 GB/s: 64 rows of 2
# MiB one a step 75.7 %, two 78.6 %, four 79.7 %, eight 80.4 %; 128 rows of
# 4 MiB one a step 80.3 %, two 81.9 %, four 82.6 % (hand-made DMAs of 4 / 8
# / 16 MiB, Findings PR 41: 82 / 84 / 85 %). Past 4 MiB a burst gains under
# a point and a half for twice the VMEM, and a lane of 256 tokens beside
# four rows no longer hides (0.498 ms a call against 0.485).
BURST_BYTES = 4 * 1024 * 1024


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


def burst_rows(block_bytes: int, b: int) -> int:
    """How many of ``b`` decode rows a grid step moves: the fewest whose
    blocks of ``block_bytes`` make :data:`BURST_BYTES`."""
    return max(1, min(-(-BURST_BYTES // block_bytes), b))


def stream_geometry(b: int, c: int, ub: int, burst: int):
    """-> (tokens, stride, parts, steps) for ``b`` decode rows in bursts
    of ``burst`` and a chunk of ``c`` tokens: ``steps`` grid steps a block
    of units, the bursts in the first of them. Where the chunk has more
    tokens than there are bursts, ``tokens`` of them a grid step, carried
    one after the other through the resident state (a count that divides
    ``c``: a step's tokens are one block of the rows); else one every
    ``stride`` grid steps, its units over ``parts`` of them (the bursts'
    stream has vector time to spare, and the chunk's tokens take it in
    shares small enough to hide there)."""
    bursts = -(-b // burst)
    if c > bursts > 0:
        tokens = next(k for k in range(-(-c // bursts), c + 1) if c % k == 0)
        return tokens, 1, 1, max(bursts, c // tokens)
    stride = max(1, bursts // c) if c else 1
    parts = stride if ub % stride == 0 else 1
    return 1, stride, parts, max(bursts, c * stride)


def burst_row(s, k: int, burst: int):
    """Which of the active decode rows, counted from 0, is row ``k`` of
    grid step ``s``'s burst (of a burst of one, the step's: no
    arithmetic is traced that a row a grid step did not need)."""
    return s * burst + k if burst > 1 else s


def chunk_tokens(s, tokens: int, stride: int):
    """-> (t, r): grid step ``s`` carries the chunk's tokens ``t .. t +
    tokens - 1`` and, where that is one token over ``stride`` steps,
    share ``r`` of its units."""
    return (s * tokens, 0) if tokens > 1 else (s // stride, s % stride)


def row_maps(b: int, c: int, ub: int, burst: int, tokens: int, stride: int):
    """Index maps ``(j, s, plan_ref, layer_ref) -> block`` of a ``[N, U,
    ..]`` operand holding a step's rows: one a row of grid step s's
    burst, a block ``(1, ub, ..)``, and the chunk's tokens of grid step
    s, a block :func:`rows_block` of ``tokens`` rows."""

    def decode_row(k):
        def index(j, s, plan_ref, layer_ref):
            at = jnp.clip(jnp.minimum(burst_row(s, k, burst),
                                      plan_ref[0] - 1), 0, b - 1)
            return plan_ref[3 + at], j, 0
        return index

    def chunk_row(j, s, plan_ref, layer_ref):
        if tokens == 1:
            return b + jnp.clip(jnp.minimum(s // stride, plan_ref[2] - 1), 0,
                                c - 1), j, 0
        block = jnp.clip(jnp.minimum(s, (plan_ref[2] - 1) // tokens), 0,
                         c // tokens - 1)
        return b + block * tokens, j * ub, 0

    return [decode_row(k) for k in range(burst)], chunk_row


def rows_block(rows: int, *dims: int):
    """The block of a rows operand that holds ``rows`` of a grid step's
    rows: one row, or several from a row that no block size need divide
    (every dimension is then indexed by element)."""
    from jax.experimental import pallas as pl

    if rows == 1:
        return (1,) + dims
    return tuple(pl.Element(n) for n in (rows,) + dims)


def one_row(ref, i):
    """Row ``i`` of a block of rows, as a block of one row."""
    from jax.experimental import pallas as pl

    return ref if ref.shape[0] == 1 else ref.at[pl.ds(i, 1)]


def stream_rows(plan_ref, layer_ref, s_hbm, so_hbm, sbuf, cbuf, rsem, wsem,
                csem, *, ub: int, b: int, c: int, burst: int, tokens: int,
                stride: int, parts: int, steps: int, prepare_d, through_d,
                prepare_c, through_c):
    """One grid step ``(j, s)`` of a kernel over ``state [L, slots, U,
    ..]`` (``s_hbm``, aliased to ``so_hbm``): block j of ``ub`` units of
    each row of the s-th burst of active decode rows through the row's
    token, and the chunk's block through its tokens of the step.

    sbuf, rsem, wsem: a list of ``burst``, one a row of a burst, of VMEM
    [3, ub, ..] and DMA semaphores [3] (a ring over bursts); cbuf [ub,
    ..] VMEM; csem [2]. ``prepare_d(row, j, k)`` lays out what
    ``through_d(block_ref, row, lo, hi, k)`` reads to carry units lo ..
    hi of the block through decode row ``row``'s token, in place, the row
    being the k-th of its burst; ``prepare_c(row, j, i)`` /
    ``through_c(block_ref, row, lo, hi, i)`` the same for the chunk's
    token at row ``row`` of the step's rows, the i-th of the grid step's
    (called only if the step has decode rows / a chunk)."""
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    j, s = pl.program_id(0), pl.program_id(1)
    layer, units = layer_ref[0], pl.ds(j * ub, ub)
    n_active, slot_c, n_valid = plan_ref[0], plan_ref[1], plan_ref[2]
    lanes = range(burst)

    def row_of(i):
        """The i-th active decode row, which is its slot."""
        return plan_ref[3 + jnp.clip(i, 0, b - 1)] if b else jnp.int32(0)

    def read(at, k):
        return pltpu.make_async_copy(
            s_hbm.at[layer, row_of(burst_row(at, k, burst)), units],
            sbuf[k].at[at % 3], rsem[k].at[at % 3])

    def write(at, k):
        return pltpu.make_async_copy(
            sbuf[k].at[at % 3],
            so_hbm.at[layer, row_of(burst_row(at, k, burst)), units],
            wsem[k].at[at % 3])

    chunk_in = pltpu.make_async_copy(s_hbm.at[layer, slot_c, units], cbuf,
                                     csem.at[0])
    chunk_out = pltpu.make_async_copy(cbuf, so_hbm.at[layer, slot_c, units],
                                      csem.at[1])

    # this step's decode rows (the s-th burst of the active ones) and its
    # share of the chunk: units group r of token t, or tokens t ..
    has_d = [burst_row(s, k, burst) < n_active for k in lanes]
    t, r = chunk_tokens(s, tokens, stride)
    has_c = (t < n_valid) & (r < parts)
    rows_d, row_c = [row_of(burst_row(s, k, burst)) for k in lanes], b + t
    group = ub // parts
    halves = [(0, -(-ub // 2)), (-(-ub // 2), ub)]
    chunk_halves = [(0, -(-group // 2)), (-(-group // 2), group)]
    token_halves = [(0, -(-tokens // 2)), (-(-tokens // 2), tokens)]
    # a burst's rows, each in two halves of its units: what is carried
    # through while the next burst comes in, and while the last goes back
    work = [(k, lo, hi) for k in lanes for lo, hi in halves if hi > lo]
    work = [work[:-(-len(work) // 2)], work[-(-len(work) // 2):]]

    def burst_at(at, dma):
        """``dma(at, k)`` for every row k the burst ``at`` has."""
        for k in lanes:
            i = burst_row(at, k, burst)
            pl.when((i >= 0) & (i < n_active))(lambda k=k: dma(at, k))

    def whole_burst(at, has, dma):
        """The burst ``at`` moved with nothing beside it: ``dma(at, k)``
        started for the rows it has (``has[k]``: a burst fills from its
        first row), then waited for."""
        def row(k):
            dma(at, k).start()
            if k + 1 < burst:
                pl.when(has[k + 1])(lambda: row(k + 1))
            dma(at, k).wait()

        pl.when(has[0])(lambda: row(0))

    @pl.when(s == 0)
    def _():
        pl.when(n_valid > 0)(chunk_in.start)
        whole_burst(0, [n_active > k for k in lanes], read)
        pl.when(n_valid > 0)(chunk_in.wait)

    # Two streams a step, a burst of reads then a burst of writes, never
    # both at once: HBM gives the two together 80 % of its rate and one
    # after the other up to 85 %, the more the larger a burst (BURST_BYTES;
    # PERF.md Findings PR 41 and PR 44). The next burst's states come in
    # while this burst's vectors are laid out and half of its rows' units
    # (and of the chunk's share) are carried through their tokens; the
    # last burst's go back during the other half. (A write left in flight
    # across the step's boundary runs beside the next rows' fetches:
    # slower.)
    burst_at(s + 1, lambda at, k: read(at, k).start())
    if b:
        for k in lanes:
            pl.when(has_d[k])(lambda k=k: prepare_d(rows_d[k], j, k))
    if c and tokens == 1:
        pl.when(has_c & (r == 0))(lambda: prepare_c(row_c, j, 0))
    for phase, ((clo, chi), (tlo, thi)) in enumerate(
            zip(chunk_halves, token_halves)):
        if phase == 1:
            burst_at(s + 1, lambda at, k: read(at, k).wait())
            burst_at(s - 1, lambda at, k: write(at, k).start())
        if b:
            for k, lo, hi in work[phase]:
                pl.when(has_d[k])(lambda k=k, lo=lo, hi=hi: through_d(
                    sbuf[k].at[s % 3], rows_d[k], lo, hi, k))
        if c and tokens == 1 and chi > clo:
            for g in range(parts):
                pl.when(has_c & (r == g))(
                    lambda g=g, clo=clo, chi=chi: through_c(
                        cbuf, row_c, g * group + clo, g * group + chi, 0))
        if c and tokens > 1 and thi > tlo:
            def token(i, _):
                @pl.when(t + i < n_valid)
                def _():
                    prepare_c(row_c + i, j, i)
                    through_c(cbuf, row_c + i, 0, ub, i)

            jax.lax.fori_loop(tlo, thi, token, None)
    burst_at(s - 1, lambda at, k: write(at, k).wait())

    # the chunk's state goes back after its last token's last units
    if tokens == 1:
        pl.when(has_c & (t == n_valid - 1) & (r == parts - 1))(
            chunk_out.start)
    else:
        pl.when(has_c & (n_valid - 1 < t + tokens))(chunk_out.start)

    @pl.when(s == steps - 1)
    def _():
        whole_burst(s, has_d, write)
        pl.when(n_valid > 0)(chunk_out.wait)
