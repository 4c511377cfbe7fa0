"""What every serving family's step shares: the page pool as a model sees
it, the rows of a step, and the window a causal convolution carries from
one step to the next. ``models/serving.py`` states the contract in prose;
this module is the one place that implements it, and a family's
``paged_step`` is what is left: projections, mixers, a head, a loop.

The pool. ``cache["kv"]`` is ONE fused array ``[attention layers, 2,
num_pages, page_size, Hkv * hd]`` (index 0 = K, 1 = V): a token's KV
heads lie side by side in the minor axis, so a physical page is a
contiguous ``[page_size, Hkv * hd]`` block whose rows fill whole 128-lane
rows (hd = 64 alone is half of one: with ``[.., Hkv, hd]`` minor axes the
TPU pads hd to 128 or makes the PAGE index the lane axis and scatters a
page over the whole pool). A page table row ``[P]`` (P = max_seq //
page_size) maps a slot's logical page l to a physical page id. Physical
page 0 is the RESERVED SCRATCH page: every invalid write (parked slots,
chunk tail padding, position overshoot) is routed there explicitly, so
garbage can never land in a real — possibly shared — page. Unallocated
page-table entries are 0 for the same reason. Positions in unallocated
logical pages are always > the slot's current pos, so attention masks
them before they are ever read. The pool's leading axis counts the
family's ATTENTION layers, whatever else it has; whatever else a family
keeps in its cache tree (state a slot) lies beside ``"kv"`` and the page
functions here hand it back as it was.

The pool inside a step is touched only IN PLACE. A loop over layers
carries the whole pool and a layer index: a layer sliced out of the pool
or stacked back as the loop's xs/ys makes XLA copy the pool into the
loop's layout and back every step. On the TPU the pool's only reader and
writer is ``ops/paged_attention.py``: a Pallas kernel that takes the
whole pool, aliased to its output, and the layer index, puts the rows'
new K/V into their pages and DMAs only the pages a row has. Off the TPU
it is the reference in :func:`write_and_attend`.

Sharding: ``rules`` is a table logical axis -> mesh axis. Under a tp
mesh the serving engine maps the "kv" logical axis to tp, so the pool's
Hkv * hd axis — whole heads a shard — shards across chips while the
page/seq axes stay replicated; the kernel runs per shard in a shard_map.
With no mesh the constraints no-op.

The rows of a step, all through the same weight products: the B decode
rows, one token of slot i each, then the C tokens of one slot's prompt
chunk if there is one. :func:`step_rows` says which are in the step.
"""

from __future__ import annotations

from dataclasses import dataclass
from typing import Any, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from ..ops import paged_attention as paged_attention_op
from ..parallel.sharding import constrain, current_mesh, spec_for

# -- the page pool ---------------------------------------------------------------

# Logical axes of cache["kv"] — the heads-and-head_dim axis shards under
# the "kv" rule (the serving engine maps it to tp), whole heads a shard.
PAGED_KV_AXES = (None, None, None, None, "kv")


def init_pool(attention_layers: int, cfg, num_pages: int, page_size: int):
    """The cache of a family with ``attention_layers`` paged layers of
    ``cfg``'s ``num_kv_heads`` x ``head_dim``: ``{"kv": zeros}``."""
    if cfg.max_seq % page_size != 0:
        raise ValueError(
            f"page_size ({page_size}) must divide max_seq ({cfg.max_seq})")
    shape = (attention_layers, 2, num_pages, page_size,
             cfg.num_kv_heads * cfg.head_dim)
    return {"kv": jnp.zeros(shape, cfg.dtype)}


def write_and_attend(q, kn, vn, kv, layer, rows, cfg, page_size: int, rules):
    """The rows' new K/V into the carried pool, then attention of q over
    each row's pages of ``layer`` -> (o [R, T, D], pool).

    q [R, T, H, hd]; kn / vn [R, T, Hkv * hd]; rows: ``row_meta`` of the
    page tables [R, P], q_start [R] and lengths [R]; token t of row r is
    position q_start[r] + t, written if it is under lengths[r]
    (a parked row has length 0, a chunk's tail lies past it) and reading
    positions <= its own. On the TPU one Pallas kernel does both, in
    place, reading only the row's live pages. Off the TPU: a scatter with
    the invalid tokens routed to the scratch page, a gather of every
    table entry, and the masked einsum."""
    r, t, h, hd = q.shape
    if paged_attention_op.use_kernel():
        kv_spec = spec_for(("kv",), rules)
        with jax.named_scope("attn"):
            o, kv = paged_attention_op.paged_attention(
                q, kn, vn, kv, layer, rows, mesh=current_mesh(),
                heads_axis=kv_spec[0] if len(kv_spec) else None)
        return o.reshape(r, t, h * hd), kv
    tables, q_start, lengths = rows[:, :-2], rows[:, -2], rows[:, -1]
    pos = q_start[:, None] + jnp.arange(t)[None, :]              # [R, T]
    with jax.named_scope("kv_write"):
        phys, off = paged_attention_op.page_slots(
            tables, jnp.arange(r)[:, None], pos, pos < lengths[:, None],
            page_size)
        # Pin the written pool to the kv sharding: the scatter must never
        # trigger a resharding of the (multi-GB) pool, and the loop's
        # carry must match the donated input's sharding so donation stays
        # in place.
        kv = constrain(paged_attention_op.write_token_kv(
            kv, layer, kn.reshape(r * t, -1), vn.reshape(r * t, -1),
            phys.reshape(-1), off.reshape(-1)), PAGED_KV_AXES, rules)
    with jax.named_scope("kv_gather"):
        kv_l = jax.lax.dynamic_index_in_dim(kv, layer, 0, keepdims=False)
        kv_att = constrain(
            paged_attention_op.gather_pages(kv_l, tables, cfg.num_kv_heads),
            (None, None, None, "kv", None), rules)
    with jax.named_scope("attn"):
        mask = (jnp.arange(cfg.max_seq)[None, None, None, None, :]
                <= pos[:, None, None, :, None])
        return paged_attention_op.gqa_attention(
            q.transpose(0, 2, 1, 3), kv_att, mask, cfg.num_kv_heads), kv


def copy_pages(cache, src, dst):
    """Device-side page copy (the COW in copy-on-write): physical pages
    ``src[i]`` -> ``dst[i]`` across every layer in one program. src/dst
    [N] int32; jit with the cache donated so the copy is in-place."""
    kv = cache["kv"]
    return dict(cache, kv=kv.at[:, :, dst].set(kv[:, :, src]))


def write_pages(cache, dst, values):
    """Host->device page import (session migration): physical pages
    ``dst[i]`` <- ``values[:, :, i]`` across every layer in one program.
    dst [N] int32; values [L, 2, N, page_size, Hkv * hd] host frames of
    a peer engine's :func:`read_pages`. Jit with the cache donated so the
    import is an in-place scatter; callers pad N to a few bucket sizes
    (padding rows aimed at scratch page 0) so imports rarely recompile."""
    kv = cache["kv"]
    return dict(cache, kv=kv.at[:, :, dst].set(values.astype(kv.dtype)))


def read_pages(cache, idx):
    """Device->host page export: physical pages ``idx`` [N] of every
    layer as one contiguous host frame [L, 2, N, page_size, Hkv * hd]."""
    return np.ascontiguousarray(np.asarray(cache["kv"][:, :, idx]))


def check_frames(cache, frames) -> None:
    """ValueError unless ``frames`` are pages of a pool like this one."""
    kv_shape = cache["kv"].shape
    if (tuple(frames.shape[:2]) != tuple(kv_shape[:2])
            or tuple(frames.shape[3:]) != tuple(kv_shape[3:])):
        raise ValueError(
            f"KV frame shape {frames.shape} does not match "
            f"cache {kv_shape}")


# what every family registers for its pages (``serving.ServingModel``)
PAGE_FUNCTIONS = dict(copy_pages=copy_pages, write_pages=write_pages,
                      read_pages=read_pages, check_frames=check_frames)


# -- a step's rows -----------------------------------------------------------------

@dataclass(frozen=True)
class StepRows:
    """Which of a step's B + C rows are in the step (:func:`step_rows`)."""
    b: int                       # decode rows, one token of slot i each
    tokens: Tuple[Any, ...]      # the decode rows' tokens [B], the chunk's [C]
    valid: Any                   # [B] bool: decode rows that are not parked
    live: Any                    # [B + C] bool: valid, then the chunk's real rows
    decode: Any                  # ``row_meta`` of the decode rows [B, P + 2]
    c: int = 0                   # the chunk's rows after them; 0: no chunk
    chunk_at: Optional[Tuple[Any, Any]] = None   # (slot, n_valid)
    last: Any = None             # index among the chunk's rows of its logits
    chunk: Any = None            # ``row_meta`` of the chunk's one row

    @property
    def n_valid(self):
        """The chunk's real rows, 0 .. C (0 too where there is no chunk)."""
        return 0 if self.chunk_at is None else self.chunk_at[1]

    def packed(self):
        """The rows' tokens [B + C], concatenated where this is called
        (under the caller's ``embed`` scope)."""
        return jnp.concatenate(self.tokens)

    def parts(self, q, k, v, heads: int):
        """q [N, heads * hd], k / v [N, Hkv * hd] of the step's rows, cut
        into what :func:`attend` takes: the decode rows' ``[B, 1, ..]``
        and, with a chunk, the chunk's ``[1, C, ..]``, q by heads."""
        b, c = self.b, self.c
        parts = [(q[:b].reshape(b, 1, heads, -1), k[:b, None], v[:b, None])]
        if self.chunk_at is not None:
            parts.append((q[b:].reshape(1, c, heads, -1), k[None, b:],
                          v[None, b:]))
        return parts

    def state_rows(self):
        """How many rows' per-slot state the step reads and writes, a
        layer: the active decode rows + 1 for a non-empty chunk."""
        n = self.valid.sum().astype(jnp.int32)
        return n if self.chunk_at is None else n + (self.n_valid > 0)


def step_rows(tables, tokens, pos, chunk, max_seq: int) -> StepRows:
    """The rows of one step under ``models/serving.py``'s contract, from
    tables [B, P] int32, tokens [B], pos [B] (where each decode row's
    token is written) and chunk, None or (pre_tokens [C], pre_slot,
    pre_p0, pre_n_valid). The rules, stated once:

    * a decode row at ``pos >= max_seq`` is PARKED: not ``valid``, its
      pages' length 0, so it writes no page and no state;
    * of the chunk's C tokens the first ``n_valid = clip(min(pre_n_valid,
      max_seq - pre_p0), 0, C)`` are real: the tail, and whatever would
      lie past ``max_seq``, lands nowhere;
    * a chunk with ``n_valid`` 0 is EMPTY: it writes and reads nothing,
      and its logits mean nothing;
    * the chunk's logits are its row ``max(pre_n_valid, 1) - 1``'s."""
    b = tokens.shape[0]
    valid = pos < max_seq
    decode = paged_attention_op.row_meta(
        tables, pos, jnp.where(valid, pos + 1, 0))
    if chunk is None:
        return StepRows(b, (tokens,), valid, valid, decode)
    pre_tokens, pre_slot, pre_p0, pre_n_valid = chunk
    c = pre_tokens.shape[0]
    n_valid = jnp.clip(jnp.minimum(pre_n_valid, max_seq - pre_p0), 0, c)
    live = jnp.concatenate([valid, jnp.arange(c) < n_valid])
    in_chunk = paged_attention_op.row_meta(
        jax.lax.dynamic_slice(tables, (pre_slot, 0), (1, tables.shape[1])),
        jnp.reshape(pre_p0, (1,)), jnp.reshape(pre_p0 + n_valid, (1,)))
    return StepRows(b, (tokens, pre_tokens), valid, live, decode, c,
                    (pre_slot, n_valid), jnp.maximum(pre_n_valid, 1) - 1,
                    in_chunk)


def attend(rows: StepRows, parts, kv, layer, cfg, page_size: int, rules):
    """Attention of a step's rows over their pages of ``layer`` ->
    (o [N, H * hd], pool). ``parts``: the decode rows' (q [B, 1, H, hd],
    k [B, 1, Hkv * hd], v) and, with a chunk, the chunk's (q [1, C, H,
    hd], k, v), as the family projected (and rotated) them
    (:meth:`StepRows.parts` cuts them). Decode rows, then the chunk: each
    writes its own tokens before it attends, so in-chunk causality
    holds."""
    outs = []
    for (q, k, v), meta, rows_of in zip(
            parts, (rows.decode, rows.chunk),
            (lambda o: o[:, 0], lambda o: o[0])):
        o, kv = write_and_attend(q, k, v, kv, layer, meta, cfg, page_size,
                                 rules)
        outs.append(rows_of(o))
    return jnp.concatenate(outs), kv


def logits_of(rows: StepRows, x, head):
    """The step's first two results from its last hidden states x [N, d]
    and the family's own ``head`` ([R, d] -> [R, vocab] float32): (the
    decode rows' logits [B, vocab], the logits [vocab] of the chunk's
    last real row or None)."""
    b = rows.b
    if rows.chunk_at is None:
        return head(x[:b]), None
    logits = head(jnp.concatenate([x[:b], x[b + rows.last][None]], axis=0))
    return logits[:b], logits[b]


# -- the carried window ------------------------------------------------------------

def carried_conv(z, state, k, b: int, valid, chunk_at, bias=None):
    """A causal depthwise convolution on a step's rows, its window carried
    across decode rows, chunk and chunk boundary; ``bias [ch]`` float32,
    if given, is added to every row's result (``models/granite.py``).

    z [N, ch]: rows ``[:b]`` one token of slot i each, rows ``[b:]`` (if
    any) one slot's prompt chunk in order. state [L - 1, slots, ch]: each
    slot's last L - 1 inputs, TAP-MAJOR, so that a tap is a whole [slots,
    channels] tile (slots along sublanes, channels along lanes) and the
    decode rows' result is L multiply-adds of such tiles: a tap beside
    the slot would be L - 1 = 3 rows of a sublane tile of 8, and a
    contraction over it a product whose result lies channels x slots.
    k [L, ch] float32. b, valid [b] and chunk_at are :class:`StepRows`'s.
    Returns (conv [N, ch] float32, new state in the state's dtype): a
    parked row's state, an empty chunk's and every slot's not in the step
    are left as they were."""
    taps, f32 = k.shape[0], jnp.float32
    # (z is converted where it is used, a piece at a time: converted
    # whole, the conversion moves into the product that made z, which
    # then writes float32, twice the bytes, and need not round)
    zb = z[:b]
    # a decode row's sum in the order of a chunk token's below
    conv = sum(k[j] * state[j].astype(f32) for j in range(taps - 1)) \
        + k[taps - 1] * zb.astype(f32)
    new_state = jnp.where(
        valid[None, :, None],
        jnp.concatenate([state[1:], zb[None].astype(state.dtype)], axis=0),
        state)
    if chunk_at is not None:
        slot, n_valid = chunk_at
        c = z.shape[0] - b
        # the slot's window a tap (one [1, ch] row) at a time: cut whole,
        # a [L - 1, ch] piece of the layers' cache, it makes the compiler
        # lay the cache out tap beside channel, and every layer's window
        # again on the way in and out
        before = [jax.lax.dynamic_slice(state, (j, slot, 0),
                                        (1, 1, z.shape[1]))[0].astype(f32)
                  for j in range(taps - 1)]
        zz = jnp.concatenate(before + [z[b:].astype(f32)], axis=0)
        conv_c = sum(k[j] * zz[j:j + c] for j in range(taps))
        conv = jnp.concatenate([conv, conv_c], axis=0)
        # rows n_valid .. n_valid + L - 2 of zz [L - 1 + C, ch] are the
        # last L - 1 inputs; they go in by the select that writes the
        # decode rows' windows
        after = jax.lax.dynamic_slice_in_dim(zz, n_valid, taps - 1, 0)
        here = (jnp.arange(state.shape[1]) == slot) & (n_valid > 0)
        new_state = jnp.where(here[None, :, None],
                              after[:, None].astype(state.dtype), new_state)
    if bias is not None:
        conv = conv + bias
    return conv, new_state
