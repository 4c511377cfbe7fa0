"""Attention ops: Pallas flash attention (TPU) + reference implementation.

The reference framework has no attention kernels (model code is user-space
there); this framework ships them because long-context SP/ring attention is
first-class (SURVEY §5.7). The standard online-softmax flash algorithm, in
the form a TPU v5e read fastest (every figure: PERF.md, Findings PR 32,
kernel alone at ``[8, 20, 1024, 64]`` bfloat16, causal):

  - grid over (batch, head groups): one program holds the whole sequence
    of q, k and v of its heads in VMEM and loops over heads, then query
    blocks, then key tiles.
  - Head dim 64 half-fills the MXU in all seven products (contraction 64,
    or 64 output lanes), and both kernels run at 75-85 % of that
    half-filled pace. So their time is the score elements they visit, plus
    ~0.3 us for EVERY tile of a rolled loop whatever its size: tiles of
    128 x 128 visit the fewest elements and read 3-4 x slower than tiles
    of 512 x 512.
  - Hence two schedules of one walk (``_walk_tiles``). Up to four query
    blocks are unrolled (STATIC): each block takes the square on its
    diagonal masked by element and everything before it as ONE unmasked
    tile (forward 0.61 -> 0.44 ms a call, backward 1.05 -> 0.82 ms).
    Longer sequences roll both loops over 512-wide tiles: the diagonal's
    tile first, then a loop over the tiles under it. Tiles above the
    diagonal are never visited in either.
  - The backward holds its tiles transposed, [keys, queries], and takes
    lse and delta as lane-major rows: as ``[.., sq, 1]`` columns XLA pads
    each to 128 lanes and copies it into that layout before every call
    (0.12 ms each, outside the kernel).
  - bf16 matmul operands, fp32 accumulation and softmax statistics; a
    power-of-two scale is folded into the query, which is exact.
  - PACKED ROWS (PR 50). A ``[.., seq, 64]`` bfloat16 array is tiled
    (8, 128) in HBM: its 64 lanes are padded to 128, so it takes twice its
    size and every DMA of it moves twice its bytes; and a caller whose
    matmul wrote ``[b, s, heads * 64]`` pays a split, a reshape and a
    transpose to get there. So the kernels also take rows of
    ``128 // head_dim`` heads side by side, ``[b, heads / 2, seq, 128]`` at
    head dim 64 (head ``r * n + j`` in lanes ``[j * 64, (j + 1) * 64)`` of
    row ``r``), which a projection's matmul can write and read directly
    (``models/gpt2.py _packed_attention``). One body for both: a row's
    heads are taken one after another, each over the row's full 128 lanes
    with NO lane shuffle and NO added MXU pass, because a 64-wide head
    half-fills the MXU anyway. For the head in lanes 0-63:
    ``S = (q_row * keep) @ k_row^T`` contracts all 128 lanes with the other
    head's zeroed (``keep`` is a [1, 128] row of ``scale`` and 0: the ONE
    multiply a folded scale cost already); ``p @ v_row`` yields 128 output
    lanes of which the head's 64 are kept by a lane select when the row is
    stored. In the backward ``dO`` is zeroed the same way, so
    ``dV += P^T dO`` and ``dK += dS^T Q``
    add exact zeros to the other head's lanes of accumulators the row's
    heads share, ``dP = V dO^T`` contracts the head's lanes alone, and
    ``dQ = dS K`` is selected like ``o``. What a head costs beyond the
    unpacked kernel: one [block_q, 128] multiply (dO) and two selects a
    query block. lse and delta stay a head's (``[b, heads, ..]``). On a
    row of one head (head dim 128, or the ``[b, h, s, d]`` entry at any
    head dim) the body traces to the kernel it was before, instruction
    for instruction.

  - SAVED IN PLACE (PRs 55 and 57). A loop over layers that owns its
    backward pass keeps each layer's q, k, v, o and lse stacked
    ``[layers, ...]``, and a Mosaic call takes no slice of a buffer as an
    operand and gives none as a result: XLA copies. So both kernels take an
    optional layer number, a prefetched scalar that their block index maps
    read: the backward reads its layer of the stacks where it lies, the
    forward writes its layer of the o and lse stacks (operands aliased to
    its results), lse as the rows of lanes the backward reads
    (``attention_saving`` / ``attention_of_saved``). Without a layer each
    is the call it was, instruction for instruction.

``flash_attention`` is differentiable end-to-end in Pallas: forward kernel
plus a fused dq/dk/dv backward kernel (blockwise recompute from the saved
LSE — no S×S materialization anywhere). An explicit request for the
kernel runs the kernel or raises: a shape it cannot tile is an error, and
on TPU it is compiled, never interpreted.
"""

from __future__ import annotations

import functools
import math
from typing import Optional

import jax
import jax.numpy as jnp

_NEG_INF = -1e30
_LANES = 128  # of a vector register and of an HBM tile's minor dimension


def _on_tpu() -> bool:
    return jax.default_backend() == "tpu"


# ---------------------------------------------------------------------------
# Reference implementation (also the CPU-test path and the backward building
# block). Shapes: q [B, H, Sq, D], k/v [B, H, Sk, D].
# ---------------------------------------------------------------------------

def mha_reference_with_lse(q, k, v, causal: bool = True,
                           scale: Optional[float] = None,
                           q_offset: int = 0):
    """Reference attention returning (o, lse [B,H,Sq] fp32) — the
    mergeable form ring attention's block steps need. ``q_offset``
    shifts causal positions (ring steps). Fully-masked rows produce
    lse ~= -1e30 (finite), so downstream logaddexp merges never see
    inf-inf NaNs."""
    if scale is None:
        scale = 1.0 / math.sqrt(q.shape[-1])
    logits = jnp.einsum(
        "bhqd,bhkd->bhqk", q, k, preferred_element_type=jnp.float32
    ) * scale
    if causal:
        sq, sk = q.shape[2], k.shape[2]
        q_pos = jnp.arange(sq)[:, None] + q_offset
        k_pos = jnp.arange(sk)[None, :]
        logits = jnp.where(q_pos >= k_pos, logits, _NEG_INF)
    m = jnp.max(logits, axis=-1, keepdims=True)
    p = jnp.exp(logits - m)
    l = jnp.sum(p, axis=-1, keepdims=True)
    o = jnp.einsum("bhqk,bhkd->bhqd", (p / l).astype(v.dtype), v,
                   preferred_element_type=jnp.float32).astype(q.dtype)
    return o, (m + jnp.log(l))[..., 0]


def mha_reference(q, k, v, causal: bool = True,
                  scale: Optional[float] = None,
                  q_offset: int = 0):
    """Plain attention; ``q_offset`` shifts causal positions (ring steps)."""
    return mha_reference_with_lse(q, k, v, causal=causal, scale=scale,
                                  q_offset=q_offset)[0]


# ---------------------------------------------------------------------------
# Pallas kernels. One schedule for both: a query block walks the key tiles
# it can see. Under the causal mask those are the tile the diagonal crosses
# (masked by element) and the tiles wholly under it (no mask arithmetic at
# all); a tile wholly above the diagonal is never visited.
# ---------------------------------------------------------------------------

# A sequence of at most this many query blocks gets the STATIC schedule:
# the blocks are unrolled in Python, so each knows its own key extent and
# takes everything under its diagonal as ONE tile. The chip pays ~0.3 us
# for every tile of a rolled loop whatever its size (PERF.md, Findings
# PR 32), so at the training shapes few large tiles beat many small ones.
# Longer sequences roll both loops: their code must not grow with length.
_STATIC_MAX_BLOCKS = 4
# ... and the widest key extent one static tile may span: its float32
# score tile is [block_q, extent].
_STATIC_MAX_SK = 2048


def _is_static(causal: bool, seq_q: int, seq_k: int, block_q: int) -> bool:
    return (seq_q // block_q <= _STATIC_MAX_BLOCKS
            and seq_k <= _STATIC_MAX_SK and (not causal or seq_q == seq_k))


def _rows_minus_cols(block_q: int, width: int):
    """[block_q, width] int32, row - column inside a tile: element (i, j)
    of the tile at (q0, k0) is visible iff i - j >= k0 - q0."""
    return (jax.lax.broadcasted_iota(jnp.int32, (block_q, width), 0)
            - jax.lax.broadcasted_iota(jnp.int32, (block_q, width), 1))


def _folds_exactly(scale: float) -> bool:
    """A power of two (1/8 at head dim 64) scales a floating-point query
    exactly, so it is applied once to the [block_q, d] query and not to
    every float32 score tile."""
    return scale > 0 and math.frexp(scale)[0] == 0.5


def _for_each_block(n: int, body, static: bool):
    """``body(i)`` for every query block: unrolled where ``static`` (``i``
    is then a Python int), else one rolled loop."""
    if static:
        for i in range(n):
            body(i)
    else:
        jax.lax.fori_loop(0, n, lambda i, c: (body(i), c)[1], 0)


def _block_rows(qb, block_q: int, static: bool):
    """(first row, ``pl.ds`` of the rows) of query block ``qb``."""
    from jax.experimental import pallas as pl

    q0 = qb * block_q
    return q0, pl.ds(q0 if static else pl.multiple_of(q0, block_q), block_q)


def _walk_tiles(tile, carry, q0, block_q: int, block_k: int, seq_k: int,
                causal: bool, static: bool):
    """``carry = tile(carry, k0, width, masked)`` over the key tiles the
    query block at row ``q0`` sees. First the one tile that needs care —
    under the causal mask the tile the diagonal crosses, where every row
    sees at least its own position, so a running max starts finite — then
    the tiles wholly visible, unmasked.

    Static (``q0`` a Python int, ``seq_q == seq_k`` if causal): the tile
    on the diagonal is [block_q, block_q] and all keys before it are one
    tile. Rolled: ``block_k``-wide tiles, ``block_q`` dividing ``block_k``
    so that exactly one tile holds the block's diagonal; a block past the
    last key (``seq_q > seq_k``) takes the last tile first, all visible.
    """
    if static:
        if not causal:
            return tile(carry, 0, seq_k, False)
        carry = tile(carry, q0, block_q, True)
        return tile(carry, 0, q0, False) if q0 else carry
    num_kb = seq_k // block_k
    first = jnp.minimum(q0 // block_k, num_kb - 1) if causal else num_kb - 1
    carry = tile(carry, first * block_k, block_k, causal)
    return jax.lax.fori_loop(
        0, first, lambda kb, c: tile(c, kb * block_k, block_k, False), carry)


def _lane_head(width: int, heads_a_row: int):
    """``j -> [1, width] bool``, the lanes of head ``j`` of a packed row;
    None where a row is one head."""
    if heads_a_row == 1:
        return None
    lane = jax.lax.broadcasted_iota(jnp.int32, (1, width), 1)
    per = width // heads_a_row
    return lambda j: (lane >= j * per) & (lane < (j + 1) * per)


def _own_lanes(x, lane_head, j: int, scale: Optional[float] = None):
    """``x`` [rows, D] with every lane outside head ``j`` zeroed, times
    ``scale``: ONE multiply by a [1, D] row, which a folded scale cost an
    unpacked row already. Contracted over all D lanes against a packed
    operand, the zeros drop the other heads' terms; as the [.., D] factor
    of a product whose result is accumulated, they leave the other heads'
    lanes of the accumulator alone."""
    if lane_head is None:
        return x if scale is None else x * scale
    keep = jnp.where(lane_head(j), 1.0 if scale is None else scale, 0.0)
    return x * keep.astype(x.dtype)


def _join_lanes(per_head, lane_head):
    """One [rows, D] row from each head's [rows, D] result, of which only
    that head's lanes mean anything (the others hold another head's
    operand times this head's weights)."""
    out = per_head[0]
    for j in range(1, len(per_head)):
        out = jnp.where(lane_head(j), per_head[j], out)
    return out


def _column_as_lanes(col):
    """``[n, 1] -> [1, n]`` on the VPU: each run of 128 rows is spread
    over the lanes, all but its diagonal zeroed, and summed over the rows
    (``x + 0.0`` is ``x``: the same bits). At the train cells' shapes the
    forward reads 8.8 us a call FASTER with it than with the one-lane
    columns it replaces, where a transpose through the XLU reads 15 us
    slower and a one-hot product on the MXU 59 (PERF.md Findings PR 57)."""
    n = col.shape[0]
    run = math.gcd(n, _LANES)
    diagonal = _rows_minus_cols(run, run) == 0
    return jnp.concatenate(
        [jnp.sum(jnp.where(diagonal, jnp.broadcast_to(
            col[r:r + run], (run, run)), 0.0), axis=0, keepdims=True)
         for r in range(0, n, run)], axis=1)


def _head_of(row, j: int, heads_a_row: int):
    """Index of head ``j`` of ``row`` among the heads (lse, delta)."""
    return row if heads_a_row == 1 else row * heads_a_row + j


def _flash_fwd_kernel(q_ref, k_ref, v_ref, o_ref, lse_ref,
                      *, block_q: int, block_k: int, seq_q: int, seq_k: int,
                      scale: float, causal: bool, static: bool,
                      num_heads: int, heads_a_row: int, lse_lanes: int = 0):
    """``num_heads`` rows of ``heads_a_row`` heads each: see the module's
    docstring for how a head of a packed row is computed. ``lse_lanes``:
    ``lse_ref`` is ``[1, heads, seq_q // lse_lanes, lse_lanes]``, rows of
    lanes as the backward kernel reads them (``_lse_rows``), and not
    ``[1, heads, seq_q, 1]`` columns."""
    from jax.experimental import pallas as pl

    fold = _folds_exactly(scale)
    rel = (_rows_minus_cols(block_q, block_q if static else block_k)
           if causal else None)
    lane_head = _lane_head(q_ref.shape[-1], heads_a_row)

    def head_body(hh, _):
        def one_head(j, q0, rows):
            """(m, l, acc) of head ``j`` of the row; of acc [block_q, D]
            only that head's lanes mean anything."""
            # Matmul operands stay in the input dtype (bf16 on the MXU's
            # fast path); accumulators and softmax statistics are float32.
            q = _own_lanes(q_ref[0, hh, rows, :], lane_head, j,
                           scale if fold else None)

            def tile(carry, k0, width, masked):
                if not static:
                    k0 = pl.multiple_of(k0, block_k)
                k_blk = k_ref[0, hh, pl.ds(k0, width), :]
                v_blk = v_ref[0, hh, pl.ds(k0, width), :]
                s = jax.lax.dot_general(
                    q, k_blk, (((1,), (1,)), ((), ())),
                    preferred_element_type=jnp.float32)  # [block_q, width]
                if not fold:
                    s = s * scale
                if masked:
                    s = jnp.where(rel >= k0 - q0, s, _NEG_INF)
                m_new = jnp.max(s, axis=-1, keepdims=True)
                if carry is not None:
                    m, l, acc = carry
                    m_new = jnp.maximum(m, m_new)
                p = jnp.exp(s - m_new)
                l_new = jnp.sum(p, axis=-1, keepdims=True)
                acc_new = jax.lax.dot_general(
                    p.astype(v_blk.dtype), v_blk, (((1,), (0,)), ((), ())),
                    preferred_element_type=jnp.float32)
                if carry is not None:
                    alpha = jnp.exp(m - m_new)
                    l_new = l * alpha + l_new
                    acc_new = acc * alpha + acc_new
                return m_new, l_new, acc_new

            return _walk_tiles(tile, None, q0, block_q, block_k, seq_k,
                               causal, static)

        def q_block(qb):
            q0, rows = _block_rows(qb, block_q, static)
            heads = [one_head(j, q0, rows) for j in range(heads_a_row)]
            # l >= 1: the row's largest visible score contributes exp(0)
            o = _join_lanes([acc * (1.0 / l) for _, l, acc in heads],
                            lane_head)
            o_ref[0, hh, rows, :] = o.astype(o_ref.dtype)
            for j, (m, l, _) in enumerate(heads):  # [block_q, 1] a head
                lse = m + jnp.log(l)
                head = _head_of(hh, j, heads_a_row)
                if not lse_lanes:
                    lse_ref[0, head, rows, :] = lse
                    continue
                lse = _column_as_lanes(lse)  # [1, block_q]
                for r in range(block_q // lse_lanes):
                    lse_ref[0, head, pl.ds(qb * (block_q // lse_lanes) + r,
                                           1), :] = \
                        lse[:, r * lse_lanes:(r + 1) * lse_lanes]

        _for_each_block(seq_q // block_q, q_block, static)
        return 0

    jax.lax.fori_loop(0, num_heads, head_body, 0)


# Per-program VMEM budget for choosing how many heads to fold into one
# program (v5e/v4 have 128MB VMEM; leave ample headroom for double
# buffering + the score tile + compiler temps).
_VMEM_BUDGET = 48 * 1024 * 1024
_VMEM_LIMIT = 110 * 1024 * 1024


def _pick_head_block(h: int, per_head_bytes: int) -> int:
    """Largest divisor of ``h`` whose folded working set fits the budget."""
    hb = h
    while hb > 1 and (hb * per_head_bytes > _VMEM_BUDGET or h % hb != 0):
        hb -= 1
    while h % hb != 0:
        hb -= 1
    return max(hb, 1)


def _compiler_params(interpret: bool):
    if interpret:
        return None
    from jax.experimental.pallas import tpu as pltpu

    return pltpu.CompilerParams(vmem_limit_bytes=_VMEM_LIMIT)


def _schedule(causal, sq, sk, block_q, block_k):
    """``(static, block_q)`` a kernel asked for ``block_q`` runs at: the
    unrolled schedule where ``_is_static``, else the rolled causal walk,
    which wants ``block_q`` to divide ``block_k``."""
    static = _is_static(causal, sq, sk, block_q)
    if causal and not static and block_k % block_q != 0:
        block_q = block_k
    return static, block_q


def _block_spec(*block):
    """A ``[1, rows, ..]`` block at (batch, head group) of a kernel's own
    operand or result; with a leading None, the same block of layer
    ``layer`` of a ``[layers, ...]`` stack, the layer the prefetched scalar
    that follows the grid's indices."""
    from jax.experimental import pallas as pl

    if block[0] is None:
        return pl.BlockSpec(block, lambda i, g, at: (at[0], i, g, 0, 0))
    return pl.BlockSpec(block, lambda i, g, *_: (i, g, 0, 0))


def _flash_fwd_into_layer_kernel(layer_ref, q_ref, k_ref, v_ref, o_stack,
                                 lse_stack, o_ref, lse_ref, **static):
    """``_flash_fwd_kernel`` behind a prefetched layer number, writing its
    layer of two stacks: the block index maps have read the layer, and the
    stacks are the results' own buffers, of which the body sees its blocks
    (``o_ref``, ``lse_ref``) alone."""
    del layer_ref, o_stack, lse_stack
    _flash_fwd_kernel(q_ref, k_ref, v_ref, o_ref, lse_ref, **static)


def _flash_fwd_pallas(q, k, v, causal: bool, scale: float,
                      block_q: int, block_k: int, interpret: bool,
                      heads_a_row: int = 1, layer=None, stacks=None):
    """ONE pallas call, (q, k, v) -> (o, lse[b, heads, sq]); the blocks
    must divide the sequences. q, k, v and o are ``[b, rows, s, D]``,
    ``heads_a_row`` heads side by side in a row's D lanes.

    ``layer`` (an int32 scalar) and ``stacks``, what a loop over layers
    saves of o and lse, ``[layers, b, rows, sq, D]`` and ``[layers, b,
    heads, sq // n, n]`` (``_lse_rows``' layout): the call writes layer
    ``layer`` of both where it lies and returns the stacks, every other
    layer as it was. The stacks are operands aliased to the results and
    the layer a prefetched scalar that the out blocks' index maps read, so
    the kernel's own DMAs put o and lse where the backward kernel reads
    them (``_flash_bwd_pallas(layer=)``): XLA cannot point a Mosaic call's
    result into a slice of a larger buffer, so stacking o after the call
    was a copy of it a layer and lse, written as ``[sq, 1]`` columns that
    HBM and VMEM pad to 128 lanes, a ``reduce`` to make it dense (109 + 62
    us a layer at the train cells' shapes, PERF.md Findings PR 57). The
    same kernel on the same blocks: the same numbers in another place."""
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    stacked = layer is not None
    b, h, sq, d = q.shape
    sk = k.shape[2]
    lse_lanes = _lse_lanes(causal, sq, sk, block_q, block_k) if stacked else 0
    static, block_q = _schedule(causal, sq, sk, block_q, block_k)
    esize = q.dtype.itemsize
    # whole-sequence q, o, k, v and a head's lse column (a [sq, 1] float32
    # block is padded to 128 lanes in VMEM; rows of lanes are held to the
    # same count, so that stacked or not a program takes the same heads);
    # x2 for double-buffering.
    per_head = 2 * ((2 * sq + 2 * sk) * d * esize
                    + heads_a_row * sq * 128 * 4)
    hb = _pick_head_block(h, per_head)
    heads = h * heads_a_row
    if stacked:
        per_block = (b, heads, sq // lse_lanes, lse_lanes)
        # (a query block here is a whole number of the backward's)
        if (block_q % lse_lanes
                or [s.shape[1:] for s in stacks] != [q.shape, per_block]):
            raise ValueError(
                f"stacks of o {q.shape} and of lse in ``_lse_rows``' layout "
                f"{per_block} a layer, not "
                f"{' and '.join(str(s.shape[1:]) for s in stacks)}")

    spec, at = _block_spec, (None,) if stacked else ()
    lse_block = ((1, hb * heads_a_row, sq // lse_lanes, lse_lanes) if stacked
                 else (1, hb * heads_a_row, sq, 1))
    full_q, full_k = spec(1, hb, sq, d), spec(1, hb, sk, d)
    in_place = pl.BlockSpec(memory_space=pl.ANY)
    o, lse = pl.pallas_call(
        functools.partial(
            _flash_fwd_into_layer_kernel if stacked else _flash_fwd_kernel,
            block_q=block_q, block_k=block_k, seq_q=sq,
            seq_k=sk, scale=scale, causal=causal, static=static,
            num_heads=hb, heads_a_row=heads_a_row, lse_lanes=lse_lanes),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=int(stacked),
            grid=(b, h // hb),
            in_specs=[full_q, full_k, full_k] + [in_place] * (2 * stacked),
            out_specs=[spec(*at, 1, hb, sq, d), spec(*at, *lse_block)]),
        out_shape=[jax.ShapeDtypeStruct(s.shape, s.dtype) for s in stacks]
        if stacked else
        [jax.ShapeDtypeStruct((b, h, sq, d), q.dtype),
         jax.ShapeDtypeStruct((b, heads, sq, 1), jnp.float32)],
        # operands count the prefetched scalar: (layer, q, k, v, o, lse)
        input_output_aliases={4: 0, 5: 1} if stacked else {},
        interpret=interpret,
        compiler_params=_compiler_params(interpret),
    )(*([jnp.reshape(layer, (1,)).astype(jnp.int32)] if stacked else []),
      q, k, v, *(stacks if stacked else ()))
    return (o, lse) if stacked else (o, lse.reshape(b, heads, sq))


def _flash_bwd_fused_kernel(q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref,
                            dq_ref, dk_ref, dv_ref, dk_acc, dv_acc, *,
                            block_q: int, block_k: int, seq_q: int,
                            seq_k: int, scale: float, causal: bool,
                            static: bool, num_heads: int, heads_a_row: int):
    """dq + dk + dv in ONE pallas program (per (batch, head-group)): it
    walks Q blocks, recomputes P per (Q, K) tile from the saved LSE — no
    S x S array anywhere — and accumulates dk/dv into fp32 VMEM scratch
    across the Q loop.

    Tiles are held TRANSPOSED, [keys, queries]: lse and delta then are
    lane-major rows (``[b, h, sq // block_q, block_q]`` operands, dense in
    HBM; as ``[.., sq, 1]`` columns XLA pads each to 128 lanes and copies
    it into that layout before every call), and dv = P^T dO and
    dk = dS^T Q are plain products; only dq = dS K contracts the tile's
    leading axis. With a folded scale the scaled query gives S and carries
    the scale into dk; dq takes it once a block.

    A packed row's heads share dk_acc and dv_acc: head j's q and dO are
    zero outside its lanes, so its products add nothing to the others'."""
    from jax.experimental import pallas as pl

    d = q_ref.shape[-1]
    fold = _folds_exactly(scale)
    # [keys, queries]: query position minus key position inside a tile
    rel = (-_rows_minus_cols(block_q if static else block_k, block_q)
           if causal else None)
    lane_head = _lane_head(d, heads_a_row)

    def head_body(hh, _):
        dk_acc[...] = jnp.zeros((seq_k, d), jnp.float32)
        dv_acc[...] = jnp.zeros((seq_k, d), jnp.float32)

        def one_head(j, qb, q0, rows):
            """dq of head ``j`` of the row, right in that head's lanes."""
            q = _own_lanes(q_ref[0, hh, rows, :], lane_head, j,
                           scale if fold else None)
            do = _own_lanes(do_ref[0, hh, rows, :], lane_head, j)
            head = _head_of(hh, j, heads_a_row)
            lse = lse_ref[0, head, pl.ds(qb, 1), :]  # [1, block_q]
            delta = delta_ref[0, head, pl.ds(qb, 1), :]

            def tile(dq_part, k0, width, masked):
                if not static:
                    k0 = pl.multiple_of(k0, block_k)
                cols = pl.ds(k0, width)
                k_blk = k_ref[0, hh, cols, :]
                v_blk = v_ref[0, hh, cols, :]
                s = jax.lax.dot_general(
                    k_blk, q, (((1,), (1,)), ((), ())),
                    preferred_element_type=jnp.float32)  # [width, block_q]
                if not fold:
                    s = s * scale
                if masked:
                    s = jnp.where(rel >= k0 - q0, s, _NEG_INF)
                p = jnp.exp(s - lse)
                dp = jax.lax.dot_general(
                    v_blk, do, (((1,), (1,)), ((), ())),
                    preferred_element_type=jnp.float32)
                ds = p * (dp - delta)
                if not fold:
                    ds = ds * scale
                ds = ds.astype(q.dtype)
                dv_acc[cols, :] += jax.lax.dot_general(
                    p.astype(do.dtype), do, (((1,), (0,)), ((), ())),
                    preferred_element_type=jnp.float32)
                dk_acc[cols, :] += jax.lax.dot_general(
                    ds, q, (((1,), (0,)), ((), ())),
                    preferred_element_type=jnp.float32)
                return dq_part + jax.lax.dot_general(
                    ds, k_blk, (((0,), (0,)), ((), ())),
                    preferred_element_type=jnp.float32)

            dq = _walk_tiles(tile, jnp.zeros((block_q, d), jnp.float32),
                             q0, block_q, block_k, seq_k, causal, static)
            return dq * scale if fold else dq

        def q_block(qb):
            q0, rows = _block_rows(qb, block_q, static)
            dq = _join_lanes([one_head(j, qb, q0, rows)
                              for j in range(heads_a_row)], lane_head)
            dq_ref[0, hh, rows, :] = dq.astype(dq_ref.dtype)

        _for_each_block(seq_q // block_q, q_block, static)
        dk_ref[0, hh] = dk_acc[...].astype(dk_ref.dtype)
        dv_ref[0, hh] = dv_acc[...].astype(dv_ref.dtype)
        return 0

    jax.lax.fori_loop(0, num_heads, head_body, 0)


def _layer_of(stack, layer):
    return jax.lax.dynamic_index_in_dim(stack, layer, 0, keepdims=False)


def _flash_bwd_of_layer_kernel(layer_ref, *refs, **static):
    """``_flash_bwd_fused_kernel`` behind a prefetched layer number: the
    block index maps have read it, the body has no use for it."""
    del layer_ref
    _flash_bwd_fused_kernel(*refs, **static)


def _flash_bwd_pallas(q, k, v, o, lse, do, causal, scale,
                      block_q, block_k, interpret, heads_a_row: int = 1,
                      layer=None):
    """ONE pallas call, (q, k, v, do, lse, delta) -> (dq, dk, dv); the
    blocks must divide the sequences. Rows as in ``_flash_fwd_pallas``;
    lse is ``[b, heads, sq]``.

    ``layer`` (an int32 scalar): q, k, v, o and lse are what a loop over
    layers saved, stacked ``[layers, ...]`` (lse as ``_lse_rows`` lays it
    out), and the kernel reads layer ``layer`` of q, k, v and lse where it
    lies in its stack, the layer a prefetched scalar that the block index
    maps read. A Mosaic call takes no slice of a buffer as an operand, so
    a slice in front of it is a copy of each a layer (67-69 us each at the
    train cells' shapes, PERF.md Findings PR 55). The kernel is the same
    kernel on the same blocks."""
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    stacked = layer is not None
    b, h, sq, d = q.shape[-4:]
    sk = k.shape[-2]
    static, block_q = _schedule(causal, sq, sk, block_q, block_k)
    # a row of block_q lanes a query block and head: see the kernel
    per_block = (b, h * heads_a_row, sq // block_q, block_q)
    if stacked:
        if lse.shape[1:] != per_block:  # a caller's own stack, mis-tiled
            raise ValueError(f"a stacked lse wants ``_lse_rows``' layout "
                             f"{per_block} a layer, not {lse.shape[1:]}")
        o = _layer_of(o, layer)
    else:
        lse = lse.reshape(per_block)
    delta = do.astype(jnp.float32) * o.astype(jnp.float32)
    if heads_a_row == 1:
        delta = jnp.sum(delta, axis=-1)
    else:  # [b, rows, heads a row, sq]: each head's lanes summed apart
        hd = d // heads_a_row
        delta = jnp.stack([jnp.sum(delta[..., j * hd:(j + 1) * hd], axis=-1)
                           for j in range(heads_a_row)], axis=2)
    esize = q.dtype.itemsize
    # Full-seq q/k/v/do in, dq/dk/dv out, double-buffered, plus fp32
    # compiler temps for the tile chain — empirically ~5.5MB/head at
    # seq 1024/d 64, so budget ~40*sq*d bytes per folded head.
    per_head = 5 * (7 * sq * d * esize + 8 * sq) + 8 * sk * d
    hb = _pick_head_block(h, per_head)

    def spec(*block, saved=False):
        """A block of the call's own operand, or (``saved``) of layer
        ``layer`` of a stack."""
        return _block_spec(*((None,) if stacked and saved else ()), *block)

    q_rows = (1, hb * heads_a_row) + per_block[2:]
    full_q, full_k = spec(1, hb, sq, d), spec(1, hb, sk, d)
    saved_q = spec(1, hb, sq, d, saved=True)
    saved_k = spec(1, hb, sk, d, saved=True)
    return pl.pallas_call(
        functools.partial(
            _flash_bwd_of_layer_kernel if stacked
            else _flash_bwd_fused_kernel, block_q=block_q, block_k=block_k,
            seq_q=sq, seq_k=sk, scale=scale, causal=causal, static=static,
            num_heads=hb, heads_a_row=heads_a_row),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=int(stacked),
            grid=(b, h // hb),
            in_specs=[saved_q, saved_k, saved_k, full_q,
                      spec(*q_rows, saved=True), spec(*q_rows)],
            out_specs=[full_q, full_k, full_k],
            scratch_shapes=[pltpu.VMEM((sk, d), jnp.float32),
                            pltpu.VMEM((sk, d), jnp.float32)]),
        out_shape=[jax.ShapeDtypeStruct((b, h, sq, d), q.dtype),
                   jax.ShapeDtypeStruct((b, h, sk, d), k.dtype),
                   jax.ShapeDtypeStruct((b, h, sk, d), v.dtype)],
        interpret=interpret,
        compiler_params=_compiler_params(interpret),
    )(*([jnp.reshape(layer, (1,)).astype(jnp.int32)] if stacked else []),
      q, k, v, do, lse, delta.reshape(per_block))


# ---------------------------------------------------------------------------
# Differentiable wrapper: pallas forward, blockwise-recompute backward.
# ---------------------------------------------------------------------------

def _fwd(q, k, v, causal, scale, block_q, block_k, heads_a_row=1, **into):
    """The forward at the callers' blocks: 512-row query blocks read
    fastest on the chip in both schedules (PERF.md, Findings PR 32)."""
    return _flash_fwd_pallas(q, k, v, causal, scale, block_q, block_k,
                             interpret=not _on_tpu(),
                             heads_a_row=heads_a_row, **into)


# The backward's query block where the static schedule can take it: four
# blocks of 256 visit 0.655 M score elements a head at seq 1024 against
# 0.786 M for two of 512, and read 15 % faster on the chip (ibid.).
_BWD_STATIC_BLOCK_Q = 256


def _bwd_block_q(causal, sq: int, sk: int, block_q: int) -> int:
    want = _BWD_STATIC_BLOCK_Q
    if block_q % want == 0 and _is_static(causal, sq, sk, want):
        return want
    return block_q


def _bwd(q, k, v, o, lse, do, causal, scale, block_q, block_k,
         heads_a_row, layer=None):
    block_q = _bwd_block_q(causal, q.shape[-2], k.shape[-2], block_q)
    return _flash_bwd_pallas(q, k, v, o, lse, do, causal, scale, block_q,
                             block_k, interpret=not _on_tpu(),
                             heads_a_row=heads_a_row, layer=layer)


@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4, 5, 6, 7))
def _flash(q, k, v, causal, scale, block_q, block_k, heads_a_row):
    return _fwd(q, k, v, causal, scale, block_q, block_k, heads_a_row)[0]


def _flash_fwd_rule(q, k, v, causal, scale, block_q, block_k, heads_a_row):
    from jax.ad_checkpoint import checkpoint_name

    o, lse = _fwd(q, k, v, causal, scale, block_q, block_k, heads_a_row)
    # Named so remat policies (gpt2 "dots_attn") can save BOTH outputs:
    # with o and lse saved, the rematerialized forward's kernel call is
    # dead code and the backward never re-runs flash.
    o = checkpoint_name(o, "attn_out")
    lse = checkpoint_name(lse, "attn_lse")
    return o, (q, k, v, o, lse)


def _flash_bwd_rule(causal, scale, block_q, block_k, heads_a_row, res, do):
    """Backward: the fused pallas kernel, recomputing P per block from
    the saved LSE (no S×S materialization across blocks) with bf16 matmul
    operands and fp32 accumulation. ``flash_attention`` admits only
    shapes the blocks divide, so there is no other path."""
    return _bwd(*res, do, causal, scale, block_q, block_k, heads_a_row)


_flash.defvjp(_flash_fwd_rule, _flash_bwd_rule)


def _lse_lanes(causal, sq, sk, block_q, block_k) -> int:
    """The lanes of a row of lse as ``_bwd``'s kernel takes it, a row a
    query block: ``_bwd``'s block, at ``_flash_bwd_pallas``'s
    ``_schedule``."""
    return _schedule(causal, sq, sk, _bwd_block_q(causal, sq, sk, block_q),
                     block_k)[1]


def _lse_rows(lse, causal, sq, sk, block_q, block_k):
    """lse ``[b, heads, sq]`` as rows of ``_lse_lanes`` lanes."""
    lanes = _lse_lanes(causal, sq, sk, block_q, block_k)
    return lse.reshape(lse.shape[:2] + (sq // lanes, lanes))


@functools.partial(jax.custom_vjp, nondiff_argnums=(5, 6, 7, 8, 9))
def _flash_of_saved(q, k, v, saved, layer, causal, scale, block_q, block_k,
                    heads_a_row):
    """``_flash`` of layer ``layer`` of a loop whose forward pass kept
    ``saved``, each layer's ``(q, k, v, o, lse rows)`` stacked: o as saved,
    so q, k and v are not read and whatever made them is dead, and the
    backward kernel reads its layer of the stacks by index."""
    return _layer_of(saved[3], layer)


def _flash_of_saved_fwd(q, k, v, saved, layer, *static):
    return _layer_of(saved[3], layer), (saved, layer)


def _flash_of_saved_bwd(causal, scale, block_q, block_k, heads_a_row, res,
                        do):
    saved, layer = res
    return (*_bwd(*saved, do, causal, scale, block_q, block_k, heads_a_row,
                  layer=layer), None, None)


_flash_of_saved.defvjp(_flash_of_saved_fwd, _flash_of_saved_bwd)


def _tileable(sq: int, sk: int, causal: bool, block_q: int, block_k: int):
    """Clamp block sizes to the sequences and decide whether the pallas
    kernels can tile them; (bq, bk, ok)."""
    bq = min(block_q, sq)
    bk = min(block_k, sk)
    ok = not (sq % bq != 0 or sk % bk != 0
              or (causal and bq % bk != 0 and bk % bq != 0))
    return bq, bk, ok


def _blocks_or_raise(q, k, causal: bool, block_q: int, block_k: int):
    bq, bk, ok = _tileable(q.shape[2], k.shape[2], causal, block_q, block_k)
    if not ok:
        raise ValueError(
            f"flash attention cannot tile q seq {q.shape[2]} / k seq "
            f"{k.shape[2]} with blocks {bq}/{bk} (causal={causal}); use "
            "impl='reference' or 'auto' for such a shape")
    return bq, bk


def _use_reference(impl: str, sq: int, sk: int, causal: bool,
                   block_q: int = 512, block_k: int = 512) -> bool:
    """'reference' always; 'auto' wherever the kernel would not run
    compiled (off TPU, or sequences it cannot tile); 'flash' never."""
    if impl == "auto":
        return not (_on_tpu()
                    and _tileable(sq, sk, causal, block_q, block_k)[2])
    return impl == "reference"


def packed_heads_for(head_dim: int, impl: str = "flash", seq: int = 0,
                     causal: bool = True) -> int:
    """How many heads :func:`attention` under ``impl`` takes side by side
    in one row of 128 lanes (2 at head dim 64); 1 where the kernels do not
    pack (a row is a head) or would not run on ``seq`` tokens: only they
    gain by the packed layout."""
    if _LANES % head_dim or _use_reference(impl, seq, seq, causal):
        return 1
    return _LANES // head_dim


def flash_attention(q, k, v, causal: bool = True,
                    scale: Optional[float] = None,
                    block_q: int = 512, block_k: int = 512,
                    head_dim: Optional[int] = None):
    """Flash attention. q/k/v: [batch, heads, seq, head_dim], or with
    ``head_dim`` given the packed layout, [batch, rows, seq, D]: head
    ``r * n + j`` in lanes ``[j * head_dim, (j + 1) * head_dim)`` of row
    ``r``, ``n = D // head_dim``; the result in the same layout.

    The Pallas kernel: compiled on TPU, interpreted (same code path) in
    CPU tests. Raises ``ValueError`` for a shape the kernel cannot tile.
    """
    return _flash(q, k, v, *_static_arguments(q, k, causal, scale, head_dim,
                                              block_q, block_k))


def _static_arguments(q, k, causal, scale, head_dim, block_q=512,
                      block_k=512):
    """``(causal, scale, block_q, block_k, heads a row)`` as ``_flash``
    and ``_flash_of_saved`` take them, or ``ValueError`` for sequences the
    kernels cannot tile."""
    head_dim = head_dim or q.shape[-1]
    if scale is None:
        scale = 1.0 / math.sqrt(head_dim)
    bq, bk = _blocks_or_raise(q, k, causal, block_q, block_k)
    return causal, scale, bq, bk, q.shape[-1] // head_dim


def attention(q, k, v, causal: bool = True, impl: str = "auto",
              scale: Optional[float] = None, mesh=None, spec=None,
              head_dim: Optional[int] = None):
    """Dispatch: 'flash' | 'reference' | 'auto' (flash on TPU for shapes
    it tiles, the reference elsewhere). ``head_dim``: q, k, v and the
    result are in the packed layout, as in :func:`flash_attention`; the
    kernels' alone, so ``ValueError`` where the reference would run
    (:func:`packed_heads_for` says 1 there, and a caller keeps its heads
    whole).

    ``mesh`` / ``spec``: GSPMD cannot partition a Mosaic kernel, so in a
    program over several devices the kernel runs per shard inside a
    ``shard_map`` over the whole ``mesh``, q/k/v and the output split as
    the PartitionSpec ``spec`` says. Attention is independent across
    batch and heads, so ``spec`` may shard those two dims and no other.
    The reference is plain XLA and needs neither.
    """
    if _use_reference(impl, q.shape[2], k.shape[2], causal):
        if head_dim is not None:
            raise ValueError(
                f"packed rows (head_dim={head_dim}) are the flash kernels' "
                f"layout; impl={impl!r} would run the reference on seq "
                f"{q.shape[2]} / {k.shape[2]}")
        return mha_reference(q, k, v, causal=causal, scale=scale)
    fn = functools.partial(flash_attention, causal=causal, scale=scale,
                           head_dim=head_dim)
    return _per_shard(fn, mesh, (spec, spec, spec), spec)(q, k, v)


def _per_shard(fn, mesh, in_specs, out_specs):
    """``fn`` on each device's shard where a program spans several."""
    # Not where the caller is itself a shard_map body (the pp pipeline's
    # stages): there the mesh axes are manual already.
    if (mesh is not None and mesh.size > 1
            and not jax.sharding.get_abstract_mesh().manual_axes):
        return jax.shard_map(fn, mesh=mesh, in_specs=in_specs,
                             out_specs=out_specs, check_vma=False)
    return fn


def attention_saving(q, k, v, causal: bool = True,
                     scale: Optional[float] = None, mesh=None, spec=None,
                     head_dim: Optional[int] = None, stacks=None,
                     layer=None):
    """The flash kernel's forward pass for a loop over layers that owns
    its backward pass: ``(o, saved)``, arguments as :func:`attention`
    under ``impl="flash"``. The loop stacks each layer's ``saved``
    ``[layers, ...]`` and hands the stacks to :func:`attention_of_saved`
    where it differentiates the layer.

    ``stacks`` and ``layer`` (an int32 scalar): the loop carries o's and
    lse's stacks itself, :func:`saved_stacks` to begin with, and the kernel
    writes layer ``layer`` of them in place; ``saved`` then holds the two
    stacks whole, to be carried on, and o is read from its stack."""
    from jax.sharding import PartitionSpec as P

    static = _static_arguments(q, k, causal, scale, head_dim)
    _, _, bq, bk, _ = static
    if stacks is None:
        def fn(q, k, v):
            o, lse = _fwd(q, k, v, *static)
            return o, _lse_rows(lse, causal, q.shape[2], k.shape[2], bq, bk)

        o, lse = _per_shard(fn, mesh, (spec,) * 3, (spec, spec))(q, k, v)
        return o, (q, k, v, o, lse)
    stack = None if spec is None else P(None, *spec)
    o, lse = _per_shard(
        lambda q, k, v, stacks, layer: _fwd(q, k, v, *static, layer=layer,
                                            stacks=stacks),
        mesh, (spec,) * 3 + ((stack,) * 2, P()), (stack,) * 2)(
            q, k, v, tuple(stacks), layer)
    return _layer_of(o, layer), (q, k, v, o, lse)


def saved_stacks(layers: int, shape, dtype, causal: bool = True,
                 head_dim: Optional[int] = None):
    """``(o, lse)`` stacks for :func:`attention_saving` to write ``layers``
    layers of q's ``shape`` and ``dtype`` into, ``causal`` and ``head_dim``
    as there. UNINITIALISED (``jax.lax.empty``: a buffer and no pass over
    it): a layer holds nothing until the forward kernel has written it,
    which a loop's forward pass has done for every layer before its
    backward pass reads one. On a mesh they take the sharding of the
    ``shard_map`` that writes them."""
    q = jax.ShapeDtypeStruct(shape, dtype)
    _, _, bq, bk, heads_a_row = _static_arguments(q, q, causal, None,
                                                  head_dim)
    b, rows, sq, _ = shape
    lanes = _lse_lanes(causal, sq, sq, bq, bk)
    return (jax.lax.empty((layers,) + tuple(shape), dtype),
            jax.lax.empty((layers, b, rows * heads_a_row, sq // lanes, lanes),
                          jnp.float32))


def attention_of_saved(q, k, v, saved, layer, causal: bool = True,
                       scale: Optional[float] = None, mesh=None, spec=None,
                       head_dim: Optional[int] = None):
    """Attention of layer ``layer`` (an int32 scalar) where the forward
    pass has run already and ``saved`` holds every layer's
    :func:`attention_saving` ``saved``, stacked: o is read from there, and
    the gradient reaches q, k and v from the backward kernel, which reads
    its operands in the stacks by index. q, k and v give their shape
    alone."""
    from jax.sharding import PartitionSpec as P

    static = _static_arguments(q, k, causal, scale, head_dim)
    stack = None if spec is None else P(None, *spec)
    return _per_shard(
        lambda *traced: _flash_of_saved(*traced, *static), mesh,
        (spec,) * 3 + ((stack,) * 5, P()), spec)(q, k, v, saved, layer)


def attention_with_lse(q, k, v, causal: bool = True,
                       scale: Optional[float] = None, impl: str = "auto",
                       block_q: int = 512, block_k: int = 512):
    """Attention returning (o, lse); ``impl`` as in :func:`attention`.
    Forward-only contract (no custom vjp): the ring TRAINING path uses
    the autodiff-able einsum body; this is the serving/inference block
    used by ``ring_flash_attention_local``.
    """
    if scale is None:
        scale = 1.0 / math.sqrt(q.shape[-1])
    if _use_reference(impl, q.shape[2], k.shape[2], causal, block_q,
                      block_k):
        return mha_reference_with_lse(q, k, v, causal=causal, scale=scale)
    bq, bk = _blocks_or_raise(q, k, causal, block_q, block_k)
    return _fwd(q, k, v, causal, scale, bq, bk)
