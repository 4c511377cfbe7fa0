"""Paged attention over the un-sliced KV pool: a Pallas TPU kernel and the
plain reference it is held to.

The serving engine keeps every layer's K and V pages in ONE array,
``pool [L, 2, NP, ps, F]`` (0 = K, 1 = V; ``F = Hkv * hd``, a token's KV
heads side by side in the lane axis), and a row reaches its pages through
a page-table row. The kernel takes the WHOLE pool and a layer index: the
pool stays in HBM (``memory_space=ANY``), the layer, the tables and the
rows' lengths are scalar-prefetched, and for each row only the pages
``0 .. ceil(len / ps) - 1`` of its table row are DMA'd into VMEM, several
pages a block, double-buffered across blocks and rows. The rows' new K/V
land in place first, through the same pool aliased to an output: the page
a new token falls in is read into VMEM, the token's row replaced, and the
page written back, so a row attends to its own token. Nothing slices a
layer out of the pool and no XLA operation touches it, so the layer loop
that calls this carries the pool and XLA has nothing pool-shaped to lay
out again (a scatter left beside the kernel asked for another layout of
the whole pool, and XLA copied it there and back every layer).

Attention is the online softmax of ``ops/attention.py``: bf16 operands,
float32 scores and accumulation, the probabilities cast to the pool's
dtype before the PV product, every live position in the softmax. Heads
are handled a 128-lane chunk of ``F`` at a time: the kernel lays the
queries of the chunk's KV heads out block-diagonally (a head's ``hd``
values in its own lanes, zeros in its neighbour's), so one MXU product of
``[rows, 128] x [128, tokens]`` gives every head of the chunk its scores
and no head is ever cut out of a lane row. Queries come and outputs go
with a token's heads side by side, as a pool row has them, so XLA does
nothing round the call but hand over what the projections made. The query length is a static
shape: 1 for decode rows, the chunk length for the prefill lane, with the
in-chunk causal mask; one kernel serves both.

On the TPU the kernel runs compiled or raises for a shape it cannot tile;
off the TPU callers take :func:`paged_attention_reference` (gather and
einsum), and the tests run the kernel interpreted against it.
"""

from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
import numpy as np

from .attention import _NEG_INF, _VMEM_LIMIT, _on_tpu

_LANES = 128
# Both KV block buffers (K and V, two slots) may take this much VMEM.
_KV_VMEM_BUDGET = 8 * 1024 * 1024
_MAX_BLOCK_TOKENS = 256
# Lane chunks unrolled inside the kernel's rolled loop over chunks.
_CHUNK_UNROLL = 4
# Pages a row's new tokens may fall in (``window``) up to which the loop
# over them is unrolled: a chunk of up to 112 tokens at page_size 16.
_WINDOW_UNROLL = 8


def use_kernel() -> bool:
    """The engine's programs call the kernel on the TPU and the reference
    everywhere else (CPU tests, interpret-mode parity)."""
    return _on_tpu()


def _geometry(q, pool, interpret: bool = True):
    """Static tiling of one call, or ValueError for a shape the kernel
    cannot tile: (KV heads, group, chunk lanes, chunks, pages a block).
    Compiled, a pool row must fill whole 128-lane rows
    (or divide one: _padded_to_lanes); interpreted (the CPU tests' small
    models) it may be narrower."""
    _, _, h, hd = q.shape
    ps, f = pool.shape[3], pool.shape[4]
    if f % hd or h % (f // hd):
        raise ValueError(
            f"paged attention: pool lanes {f} / query heads {h} do not "
            f"divide by head_dim {hd}")
    hkv = f // hd
    wc = min(_LANES, f)
    sublanes = 8 * 4 // pool.dtype.itemsize
    if f % wc or wc % hd or ps % sublanes or (wc < _LANES
                                              and not interpret):
        raise ValueError(
            f"paged attention cannot tile head_dim {hd} x {hkv} KV heads "
            f"(lanes {f}) with page_size {ps} for {pool.dtype}: needs "
            f"head_dim dividing {wc}, lanes a multiple of {wc}, page_size "
            f"a multiple of {sublanes}")
    per_page = 2 * 2 * ps * f * pool.dtype.itemsize  # K and V, two slots
    pb = max(1, min(_KV_VMEM_BUDGET // per_page, _MAX_BLOCK_TOKENS // ps))
    return hkv, h // hkv, wc, f // wc, pb


def _kernel(layer_ref, rows_ref, *refs, scale: float, pages_per_block: int,
            page_size: int, head_dim: int, window: int, one_token: bool):
    # scalar prefetch: the layer, and a row's page table followed by its
    # first position and its live length (row_meta). Then inputs (tok_ref
    # only where a row has several query tokens), outputs, scratch.
    if one_token:
        q_ref, kn_ref, vn_ref, pool_in_ref = refs[:4]
        tok_ref, refs = None, refs[4:]
    else:
        q_ref, tok_ref, kn_ref, vn_ref, pool_in_ref = refs[:5]
        refs = refs[5:]
    (o_ref, pool_ref,
     kv_buf, sems, stage, wsem, qbd_ref, m_ref, l_ref, acc_ref) = refs
    # pool_in_ref is pool_ref's alias: the same HBM. Everything goes
    # through the output, which is what sees the new rows.
    del pool_in_ref
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    rows, pb, ps = q_ref.shape[0], pages_per_block, page_size
    t_new, f = kn_ref.shape[1], kn_ref.shape[2]
    chunks, rows_q, wc = qbd_ref.shape
    hpc = wc // head_dim                 # KV heads in a 128-lane chunk
    tq = rows_q // hpc                   # query rows a head (padded)
    bt = pb * ps
    layer = layer_ref[0]
    n_tab = rows_ref.shape[1] - 2

    def start_of(r):
        return rows_ref[r, n_tab]

    def len_of(r):
        return rows_ref[r, n_tab + 1]

    # which head's lanes: head j of a chunk owns lanes [j * hd, (j+1) * hd)
    lane_head = jax.lax.broadcasted_iota(jnp.int32, (tq, wc), 1) // head_dim

    # -- the rows' new K/V into their pages, in place ---------------------
    # Row r's new tokens are positions [start, start + n_new): rows
    # [s, s + n_new) of its ``window`` pages from logical page
    # start // ps on, s = start % ps.
    # (Loops over rows, pages and chunks are rolled: unrolled, lowering
    # the kernel took ten seconds of every process's start. The window
    # of a short chunk stays unrolled, as it always was; a 256-token
    # lane's 17 pages, unrolled in each of the four passes below, were
    # most of that kernel's compile.)
    def for_written_pages(act):
        def row(r, _):
            n_new = jnp.maximum(len_of(r) - start_of(r), 0)
            s = start_of(r) % ps

            def page_of_window(w, _=0):
                @pl.when(jnp.logical_and(n_new > 0, w * ps < s + n_new))
                def _():
                    page = rows_ref[r, start_of(r) // ps + w]
                    act(pool_ref.at[layer, :, page], r, w, s, n_new)
                return 0

            if window <= _WINDOW_UNROLL:
                for w in range(window):
                    page_of_window(w)
            else:
                jax.lax.fori_loop(0, window, page_of_window, 0)
            return 0

        jax.lax.fori_loop(0, rows, row, 0)

    for_written_pages(lambda hbm, r, w, s, n: pltpu.make_async_copy(
        hbm, stage.at[r * window + w], wsem).start())
    for_written_pages(lambda hbm, r, w, s, n: pltpu.make_async_copy(
        hbm, stage.at[r * window + w], wsem).wait())

    def patch(hbm, r, w, s, n_new):
        # window row i of page w holds token i - s, if that is a new one
        i = w * ps + jax.lax.broadcasted_iota(jnp.int32, (ps, f), 0)
        fresh = jnp.logical_and(i >= s, i < s + n_new)
        if t_new > 1:
            # rows of the page <- tokens, by an exact 0/1 product
            place = (w * ps + jax.lax.broadcasted_iota(
                jnp.int32, (ps, t_new), 0) - s
                == jax.lax.broadcasted_iota(jnp.int32, (ps, t_new), 1))
            place = place.astype(stage.dtype)
        for kv, ref in enumerate((kn_ref, vn_ref)):
            old = stage[r * window + w, kv].astype(jnp.float32)
            if t_new > 1:
                new = jax.lax.dot_general(
                    place, ref[r], (((1,), (0,)), ((), ())),
                    precision=(jax.lax.Precision.HIGHEST
                               if stage.dtype == jnp.float32 else None),
                    preferred_element_type=jnp.float32)
            else:
                new = jnp.broadcast_to(ref[r].astype(jnp.float32), (ps, f))
            stage[r * window + w, kv] = jnp.where(fresh, new, old).astype(
                stage.dtype)
        pltpu.make_async_copy(stage.at[r * window + w], hbm, wsem).start()

    for_written_pages(patch)
    for_written_pages(lambda hbm, r, w, s, n: pltpu.make_async_copy(
        stage.at[r * window + w], hbm, wsem).wait())

    # -- attention over each row's live pages -----------------------------
    def num_blocks(r):
        return (len_of(r) + bt - 1) // bt

    def page_copy(r, pidx, slot, i):
        return pltpu.make_async_copy(
            pool_ref.at[layer, :, rows_ref[r, pidx]],
            kv_buf.at[slot, :, pl.ds(pl.multiple_of(i * ps, ps), ps)],
            sems.at[slot])

    def for_live_pages(r, j, slot, act):
        """``act`` on the copy of every page of block j that row r has."""
        npages = (len_of(r) + ps - 1) // ps

        def page(i, _):
            act(page_copy(r, j * pb + i, slot, i))
            return 0

        jax.lax.fori_loop(0, jnp.clip(npages - j * pb, 0, pb), page, 0)

    def next_row(r):
        """First row at or after r that has anything to read (rows if
        none)."""
        return jax.lax.while_loop(
            lambda x: jnp.logical_and(
                x < rows, num_blocks(jnp.minimum(x, rows - 1)) == 0),
            lambda x: x + 1, r)

    # A page never fetched must still read as finite numbers: masked
    # positions weigh 0, and 0 x NaN is NaN.
    kv_buf[...] = jnp.zeros(kv_buf.shape, kv_buf.dtype)
    first = next_row(0)

    @pl.when(first < rows)
    def _():
        for_live_pages(first, 0, 0, lambda c: c.start())

    def for_chunks(body):
        """body(c, lanes) for every 128-lane chunk: a rolled loop over
        groups of ``_CHUNK_UNROLL``, the group unrolled so that the MXU
        work of one chunk overlaps the vector work of the next."""
        unroll = math.gcd(chunks, _CHUNK_UNROLL)

        def group(gi, _):
            for u in range(unroll):
                c = gi * unroll + u
                body(c, pl.ds(pl.multiple_of(c * wc, wc), wc))
            return 0

        if chunks == unroll:
            group(0, 0)
        else:
            jax.lax.fori_loop(0, chunks // unroll, group, 0)

    def attend_block(r, j, slot):
        # a query at token t reads positions <= start + t and < length
        lim = start_of(r) if one_token else start_of(r) + tok_ref[...]
        lim = jnp.minimum(lim, len_of(r) - 1)
        k_pos = j * bt + jax.lax.broadcasted_iota(
            jnp.int32, (rows_q, bt), 1)
        mask = k_pos <= lim

        def chunk(c, lanes):
            k = kv_buf[slot, 0, :, lanes]                  # [bt, wc]
            v = kv_buf[slot, 1, :, lanes]
            s = jax.lax.dot_general(
                qbd_ref[c], k, (((1,), (1,)), ((), ())),
                preferred_element_type=jnp.float32) * scale
            s = jnp.where(mask, s, _NEG_INF)
            m_prev = m_ref[c]
            m_new = jnp.maximum(m_prev, jnp.max(s, axis=-1, keepdims=True))
            p = jnp.where(mask, jnp.exp(s - m_new), 0.0)
            alpha = jnp.exp(m_prev - m_new)
            l_ref[c] = l_ref[c] * alpha + jnp.sum(p, axis=-1, keepdims=True)
            acc_ref[c] = acc_ref[c] * alpha + jax.lax.dot_general(
                p.astype(v.dtype), v, (((1,), (0,)), ((), ())),
                preferred_element_type=jnp.float32)
            m_ref[c] = m_new

        for_chunks(chunk)

    def row_body(r, done):
        n = num_blocks(r)

        @pl.when(n == 0)
        def _():  # an empty or parked slot: nothing to read, zeros out
            o_ref[r] = jnp.zeros(o_ref.shape[1:], o_ref.dtype)

        @pl.when(n > 0)
        def _():
            m_ref[...] = jnp.full(m_ref.shape, _NEG_INF, jnp.float32)
            l_ref[...] = jnp.zeros(l_ref.shape, jnp.float32)
            acc_ref[...] = jnp.zeros(acc_ref.shape, jnp.float32)
            # The row's queries, block-diagonal a chunk: rows [j * tq,
            # (j + 1) * tq) are the chunk's head j, its hd values in its
            # own lanes and zeros in its neighbours', so one product with
            # a [tokens, 128] slab of K scores every head of the chunk.
            def chunk(c, lanes):
                qc = q_ref[r, :, lanes].astype(jnp.float32)
                if qc.shape[0] != tq:                      # one query row
                    qc = jnp.broadcast_to(qc, (tq, wc))
                qbd_ref[c] = jnp.concatenate(
                    [jnp.where(lane_head == jh, qc, 0.0)
                     for jh in range(hpc)], axis=0).astype(qbd_ref.dtype)

            for_chunks(chunk)

        def block_body(j, done):
            slot = done % 2
            more = j + 1 < n

            @pl.when(more)
            def _():
                for_live_pages(r, j + 1, 1 - slot, lambda c: c.start())

            @pl.when(jnp.logical_not(more))
            def _():
                r2 = next_row(r + 1)

                @pl.when(r2 < rows)
                def _():
                    for_live_pages(r2, 0, 1 - slot, lambda c: c.start())

            for_live_pages(r, j, slot, lambda c: c.wait())
            attend_block(r, j, slot)
            return done + 1

        done = jax.lax.fori_loop(0, n, block_body, done)

        @pl.when(n > 0)
        def _():
            # each head's rows keep its own lanes: back to a token's
            # heads side by side
            t_out = o_ref.shape[1]

            def chunk(c, lanes):
                l = l_ref[c]
                o = acc_ref[c] / jnp.where(l == 0.0, 1.0, l)
                o = sum(jnp.where(lane_head == jh,
                                  o[jh * tq:(jh + 1) * tq], 0.0)
                        for jh in range(hpc))
                o_ref[r, :, lanes] = o[:t_out].astype(o_ref.dtype)

            for_chunks(chunk)

        return done

    jax.lax.fori_loop(0, rows, row_body, 0)


@functools.lru_cache(maxsize=None)
def _kernel_for(scale, pages_per_block, page_size, head_dim, window,
                one_token):
    """One kernel object a static configuration, so that JAX traces the
    body once for every call site that shares it (both engine programs
    call the decode rows' kernel) and not once a site."""
    return functools.partial(
        _kernel, scale=scale, pages_per_block=pages_per_block,
        page_size=page_size, head_dim=head_dim, window=window,
        one_token=one_token)


def row_meta(tables, q_start, lengths):
    """What the kernel is told of each row, as ONE int32 array [R, P + 2]:
    its page-table row, the position of its first new token, and how many
    positions are live once the new tokens are in. It does not depend on
    the layer: a loop over layers builds it once, outside."""
    return jnp.concatenate(
        [tables, q_start[:, None], lengths[:, None]], axis=1).astype(
            jnp.int32)


def _padded_to_lanes(q, k_new, v_new, pool, layer, rows, interpret: bool):
    """A pool row narrower than a lane row (a tp shard left with one KV
    head of 64): Mosaic cannot cut such pages out of HBM. The layer's
    slice of the shard is padded to 128 lanes with KV heads of zeros,
    their query heads zero too, the kernel runs on that, and the slice
    goes back. This does copy a layer of the pool a layer, which is what
    the kernel exists to avoid, so it is only for pools this narrow:
    llama-1b at tp=4 keeps 4 MiB a layer on a chip."""
    r, t, h, hd = q.shape
    f = pool.shape[4]
    hkv, hkv_p = f // hd, _LANES // hd
    g = h // hkv

    def widen(x):
        return jnp.pad(x, [(0, 0)] * (x.ndim - 1) + [(0, _LANES - f)])

    q_p = jnp.pad(q.reshape(r, t, hkv, g, hd),
                  ((0, 0), (0, 0), (0, hkv_p - hkv), (0, 0), (0, 0)))
    o, slab = _paged_attention_local(
        q_p.reshape(r, t, hkv_p * g, hd), widen(k_new), widen(v_new),
        widen(jax.lax.dynamic_index_in_dim(pool, layer, 0, keepdims=True)),
        jnp.zeros((), jnp.int32), rows, interpret)
    pool = jax.lax.dynamic_update_index_in_dim(
        pool, slab[0, ..., :f], layer, 0)
    return o[:, :, :h], pool


def _paged_attention_local(q, k_new, v_new, pool, layer, rows,
                           interpret: bool):
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    r, t, h, hd = q.shape
    ps, f = pool.shape[3], pool.shape[4]
    if not interpret and f < _LANES and _LANES % f == 0 and f % hd == 0:
        return _padded_to_lanes(q, k_new, v_new, pool, layer, rows,
                                interpret)
    hkv, g, wc, chunks, pb = _geometry(q, pool, interpret)
    hpc = wc // hd                       # KV heads in a lane chunk
    # Queries with a token's heads side by side, as a pool row has them:
    # the G query heads of a KV head become G rows of that head's lanes.
    t_q = t * g
    q_tok = q.reshape(r, t, hkv, g, hd).transpose(0, 1, 3, 2, 4).reshape(
        r, t_q, f)
    tq_p = 16 if t_q == 1 else -(-t_q // 16) * 16
    if t_q > 1:
        q_tok = jnp.pad(q_tok, ((0, 0), (0, tq_p - t_q), (0, 0)))
    rows_q = hpc * tq_p
    # the token of each query row (a constant of the shapes): row
    # j * tq_p + i is head j of a chunk at token i // G
    tok = (np.arange(rows_q, dtype=np.int32) % tq_p // g)[:, None]
    window = (t + ps - 2) // ps + 1
    t_pad = t if t == 1 else -(-t // 16) * 16
    k_new, v_new = (jnp.pad(x.astype(pool.dtype),
                            ((0, 0), (0, t_pad - t), (0, 0)))
                    for x in (k_new, v_new))

    bt = pb * ps
    kernel = _kernel_for(1.0 / math.sqrt(hd), pb, ps, hd, window, t_q == 1)
    vmem = functools.partial(pl.BlockSpec, memory_space=pltpu.VMEM)
    hbm = pl.BlockSpec(memory_space=pl.ANY)
    inputs = [q_tok] + ([] if t_q == 1 else [jnp.asarray(tok)]) + [
        k_new, v_new, pool]
    o_tok, pool = pl.pallas_call(
        kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=2, grid=(1,),
            in_specs=[vmem()] * (len(inputs) - 1) + [hbm],
            out_specs=[vmem(), hbm],
            scratch_shapes=[
                pltpu.VMEM((2, 2, bt, f), pool.dtype),
                pltpu.SemaphoreType.DMA((2,)),
                pltpu.VMEM((r * window, 2, ps, f), pool.dtype),
                pltpu.SemaphoreType.DMA(()),
                pltpu.VMEM((chunks, rows_q, wc), q.dtype),
                pltpu.VMEM((chunks, rows_q, 1), jnp.float32),
                pltpu.VMEM((chunks, rows_q, 1), jnp.float32),
                pltpu.VMEM((chunks, rows_q, wc), jnp.float32),
            ]),
        out_shape=[jax.ShapeDtypeStruct((r, q_tok.shape[1], f), q.dtype),
                   jax.ShapeDtypeStruct(pool.shape, pool.dtype)],
        input_output_aliases={1 + len(inputs): 1},  # the pool, in place
        interpret=interpret,
        compiler_params=None if interpret else pltpu.CompilerParams(
            vmem_limit_bytes=_VMEM_LIMIT),
        name="paged_attention",
    )(jnp.reshape(layer, (1,)).astype(jnp.int32), rows, *inputs)
    o = o_tok[:, :t_q].reshape(r, t, g, hkv, hd).transpose(0, 1, 3, 2, 4)
    return o.reshape(r, t, h, hd), pool


def paged_attention(q, k_new, v_new, pool, layer, rows, *, mesh=None,
                    heads_axis=None, interpret=None):
    """Land each row's new K/V in its pages, then attend each row's
    queries over that row's live pages. Returns (o, pool).

    q [R, T, H, hd]: T query tokens a row (static), query head ``h``
    reading KV head ``h // (H // Hkv)``; k_new / v_new [R, T, Hkv * hd]:
    their keys and values. pool [L, 2, NP, ps, Hkv * hd], updated in
    place (donate it); layer: int32 scalar; rows: :func:`row_meta` of
    tables [R, P] int32 physical page ids, q_start [R], the position of a
    row's first token, and lengths [R], how many positions of the row are
    live once the new tokens are in. Tokens ``t < lengths[r] - q_start[r]`` of row r are written, at
    positions ``q_start[r] + t``, to page ``tables[r, position // ps]``;
    the others (a parked row, a chunk's tail) nowhere. Query t attends
    positions ``<= q_start[r] + t`` and ``< lengths[r]``; a row of length
    0 reads no page and returns zeros. o is [R, T, H, hd].

    ``mesh`` / ``heads_axis``: GSPMD cannot partition a Mosaic kernel,
    so under a mesh the kernel runs per shard in a ``shard_map``, the
    pool's lane axis, k_new / v_new and q's head axis split over
    ``heads_axis`` (whole KV heads a shard), tables and lengths
    replicated.
    """
    if interpret is None:
        interpret = not _on_tpu()
    fn = functools.partial(_paged_attention_local, interpret=interpret)
    if (mesh is not None and mesh.size > 1 and heads_axis is not None
            and not jax.sharding.get_abstract_mesh().manual_axes):
        from jax.sharding import PartitionSpec as P

        rep, heads = P(), P(None, None, heads_axis, None)
        lanes = P(None, None, heads_axis)
        pool_spec = P(None, None, None, None, heads_axis)
        fn = jax.shard_map(
            fn, mesh=mesh,
            in_specs=(heads, lanes, lanes, pool_spec, rep, rep),
            out_specs=(heads, pool_spec), check_vma=False)
    return fn(q, k_new, v_new, pool, layer, rows)


def page_slots(tables, rows, pos, valid, page_size: int):
    """Where tokens land: the token of table row rows[n] at ``pos[n]``
    goes to physical page tables[rows[n], pos[n] // ps] at offset
    pos[n] % ps; an invalid one (parked row, overshoot, chunk tail) to
    the scratch page, so it can never corrupt a live page. Returns
    (phys, off)."""
    lpage = jnp.minimum(pos // page_size, tables.shape[1] - 1)
    return (jnp.where(valid, tables[rows, lpage], 0),
            jnp.where(valid, pos % page_size, 0))


def write_token_kv(pool, layer, kn, vn, phys, off):
    """New K/V rows into the pool by one scatter: kn / vn [N, Hkv * hd]
    land at pool[layer, :, phys[n], off[n]]."""
    return pool.at[layer, :, phys, off].set(jnp.stack([kn, vn], axis=1))


def paged_attention_reference(q, k_new, v_new, pool, layer, rows):
    """The same contract in plain XLA: scatter the new rows (the invalid
    ones to the scratch page 0, which the kernel leaves alone), gather
    every table entry's page of ``layer``, mask, einsum. What the
    kernel is tested against; the engine's programs run the same three
    steps off the TPU."""
    r, t, h, hd = q.shape
    ps, f = pool.shape[3], pool.shape[4]
    hkv = f // hd
    tables, q_start, lengths = rows[:, :-2], rows[:, -2], rows[:, -1]
    pos = q_start[:, None] + jnp.arange(t)[None, :]            # [R, T]
    phys, off = page_slots(
        tables, jnp.arange(r)[:, None], pos, pos < lengths[:, None], ps)
    pool = write_token_kv(pool, layer, k_new.reshape(r * t, f),
                          v_new.reshape(r * t, f), phys.reshape(-1),
                          off.reshape(-1))
    kv = gather_pages(jax.lax.dynamic_index_in_dim(
        pool, layer, 0, keepdims=False), tables, hkv)
    k_pos = jnp.arange(tables.shape[1] * ps)[None, None, :]
    mask = (k_pos <= pos[..., None]) & (k_pos < lengths[:, None, None])
    o = gqa_attention(q.transpose(0, 2, 1, 3), kv,
                      mask[:, None, None, :, :], hkv)
    o = jnp.where((lengths > 0)[:, None, None], o, 0)
    return o.reshape(r, t, h, hd), pool


def gather_pages(kv_l, tables, hkv: int):
    """ONE fused gather: one layer's pages [2, NP, ps, F] by tables [B, P]
    -> seq-major [2, B, P*ps, Hkv, hd] (0 = K, 1 = V). Logical page l's
    offset o lands at sequence position l * ps + o, so positions and
    masks are those of a dense cache; a page row is contiguous, so the
    reshape is free."""
    b, p = tables.shape
    g = kv_l[:, tables]  # [2, B, P, ps, F]
    return g.reshape(2, b, p * g.shape[3], hkv, g.shape[4] // hkv)


def gqa_attention(q, kv, mask, hkv: int):
    """Grouped-query attention of q against a fused SEQ-MAJOR cache
    view, without materializing the repeated KV heads.

    q: [B, H, C, hd]; kv: [2, B, S, Hkv, hd]; mask broadcastable to
    [B, Hkv, G, C, S]. bf16 operands and float32 accumulation: an
    explicit float32 cast would materialize a float32 copy of the
    gathered cache. Returns [B, C, H * hd]."""
    b, h, c, hd = q.shape
    g = h // hkv
    qg = q.reshape(b, hkv, g, c, hd)
    scores = jnp.einsum("bkgcd,bskd->bkgcs", qg, kv[0],
                        preferred_element_type=jnp.float32) * (
                            1.0 / math.sqrt(hd))
    scores = jnp.where(mask, scores, _NEG_INF)
    probs = jax.nn.softmax(scores, axis=-1)
    o = jnp.einsum("bkgcs,bskd->bkgcd", probs.astype(kv.dtype), kv[1])
    return o.reshape(b, h, c, hd).transpose(0, 2, 1, 3).reshape(
        b, c, h * hd)
