"""Attention ops: Pallas flash attention (TPU) + reference implementation.

The reference framework has no attention kernels (model code is user-space
there); this framework ships them because long-context SP/ring attention is
first-class (SURVEY §5.7). Design follows the standard online-softmax flash
algorithm, tiled for the MXU:

  - grid over (batch, query blocks) with ALL heads processed inside each
    program. At LM training shapes (head_dim 64, seq ~1-8k) the per-head
    tile work is far smaller than Mosaic's per-program overhead, so a
    (batch*heads, q-blocks) grid spends most of its time sequencing; head
    folding raises per-program work ~H× and measured ~4-5× kernel speedup
  - K/V resident in VMEM per program, streamed in ``block_k`` chunks with
    running (m, l, acc) online softmax
  - causal masking skips fully-masked K blocks (block-level early exit)
  - bf16 matmul operands, fp32 accumulation (``preferred_element_type``)

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
# Pallas forward kernel
# ---------------------------------------------------------------------------

def _causal_upper(qi, block_q: int, block_k: int, num_kb: int):
    """Number of K blocks the online-softmax loop must visit for Q block
    ``qi`` under causal masking (blocks past the diagonal are all-masked)."""
    upper = jnp.minimum(
        num_kb, (qi + 1) * block_q // block_k + (block_q // block_k == 0)
    )
    return jnp.maximum(upper, 1)


def _flash_fwd_kernel(q_ref, k_ref, v_ref, o_ref, lse_ref,
                      *, block_k: int, seq_k: int, scale: float,
                      causal: bool, block_q: int, num_heads: int):
    from jax.experimental import pallas as pl

    qi = pl.program_id(2)
    num_kb = seq_k // block_k
    upper = _causal_upper(qi, block_q, block_k, num_kb) if causal else num_kb
    q_pos = jax.lax.broadcasted_iota(jnp.int32, (block_q, block_k), 0)
    d = q_ref.shape[-1]

    def head_body(hh, _):
        # CRITICAL for MXU throughput: matmul operands stay in bf16 — only
        # the accumulator is fp32 (preferred_element_type). Casting inputs
        # to fp32 first pushes the dots off the fast MXU path (~8x slower).
        q = q_ref[0, hh]  # [block_q, D], input dtype

        m0 = jnp.full((block_q, 1), _NEG_INF, jnp.float32)
        l0 = jnp.zeros((block_q, 1), jnp.float32)
        acc0 = jnp.zeros((block_q, d), jnp.float32)

        def body(kb, carry):
            m, l, acc = carry
            k_blk = k_ref[0, hh, pl.ds(kb * block_k, block_k), :]
            v_blk = v_ref[0, hh, pl.ds(kb * block_k, block_k), :]
            s = jax.lax.dot_general(
                q, k_blk, (((1,), (1,)), ((), ())),
                preferred_element_type=jnp.float32,
            ) * scale  # [block_q, block_k] fp32
            if causal:
                k_pos = (
                    jax.lax.broadcasted_iota(
                        jnp.int32, (block_q, block_k), 1)
                    + kb * block_k
                )
                s = jnp.where(q_pos + qi * block_q >= k_pos, s, _NEG_INF)
            m_new = jnp.maximum(m, jnp.max(s, axis=-1, keepdims=True))
            p = jnp.exp(s - m_new)
            alpha = jnp.exp(m - m_new)
            l_new = l * alpha + jnp.sum(p, axis=-1, keepdims=True)
            acc_new = acc * alpha + jax.lax.dot_general(
                p.astype(v_blk.dtype), v_blk, (((1,), (0,)), ((), ())),
                preferred_element_type=jnp.float32,
            )
            return m_new, l_new, acc_new

        m, l, acc = jax.lax.fori_loop(0, upper, body, (m0, l0, acc0))
        safe_l = jnp.where(l == 0, 1.0, l)
        o_ref[0, hh] = (acc / safe_l).astype(o_ref.dtype)
        lse_ref[0, hh] = m + jnp.log(safe_l)  # [block_q, 1]
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


def _flash_fwd_single_pass_kernel(q_ref, k_ref, v_ref, o_ref, lse_ref,
                                  *, seq_k: int, scale: float, causal: bool,
                                  block_q: int, num_heads: int):
    """Short-sequence forward: the whole K/V fits VMEM, so compute the full
    [block_q, seq_k] score tile with ONE dot and a single softmax pass —
    no online-softmax carry chain (whose per-K-block VPU rescales dominate
    at seq ~1k where there are only 1-2 K blocks anyway)."""
    from jax.experimental import pallas as pl

    qi = pl.program_id(2)
    q_pos = jax.lax.broadcasted_iota(jnp.int32, (block_q, seq_k), 0)
    k_pos = jax.lax.broadcasted_iota(jnp.int32, (block_q, seq_k), 1)

    def head_body(hh, _):
        q = q_ref[0, hh]          # [block_q, d]
        k = k_ref[0, hh]          # [seq_k, d]
        v = v_ref[0, hh]
        s = jax.lax.dot_general(
            q, k, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32) * scale
        if causal:
            s = jnp.where(q_pos + qi * block_q >= k_pos, s, _NEG_INF)
        m = jnp.max(s, axis=-1, keepdims=True)
        p = jnp.exp(s - m)
        l = jnp.sum(p, axis=-1, keepdims=True)
        o = jax.lax.dot_general(
            p.astype(v.dtype), v, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)
        safe_l = jnp.where(l == 0, 1.0, l)
        o_ref[0, hh] = (o / safe_l).astype(o_ref.dtype)
        lse_ref[0, hh] = m + jnp.log(safe_l)
        return 0

    jax.lax.fori_loop(0, num_heads, head_body, 0)


# Below this K length the single-pass forward kernel (full score tile in
# VMEM) wins over the online-softmax loop.
_SINGLE_PASS_MAX_SK = 2048


def _flash_fwd_pallas(q, k, v, causal: bool, scale: float,
                      block_q: int, block_k: int, interpret: bool):
    from jax.experimental import pallas as pl

    b, h, sq, d = q.shape
    sk = k.shape[2]
    block_q = min(block_q, sq)
    block_k = min(block_k, sk)
    esize = q.dtype.itemsize
    # q + o blocks, full-seq k + v, lse; ×2 for pipeline double-buffering.
    per_head = 2 * (2 * block_q * d * esize + 2 * sk * d * esize
                    + 4 * block_q)
    hb = _pick_head_block(h, per_head)
    grid = (b, h // hb, sq // block_q)

    if sk <= _SINGLE_PASS_MAX_SK:
        kernel = functools.partial(
            _flash_fwd_single_pass_kernel, seq_k=sk, scale=scale,
            causal=causal, block_q=block_q, num_heads=hb,
        )
    else:
        kernel = functools.partial(
            _flash_fwd_kernel, block_k=block_k, seq_k=sk, scale=scale,
            causal=causal, block_q=block_q, num_heads=hb,
        )
    out_shape = [
        jax.ShapeDtypeStruct((b, h, sq, d), q.dtype),
        jax.ShapeDtypeStruct((b, h, sq, 1), jnp.float32),
    ]
    in_specs = [
        pl.BlockSpec((1, hb, block_q, d), lambda i, g, j: (i, g, j, 0)),
        pl.BlockSpec((1, hb, sk, d), lambda i, g, j: (i, g, 0, 0)),
        pl.BlockSpec((1, hb, sk, d), lambda i, g, j: (i, g, 0, 0)),
    ]
    out_specs = [
        pl.BlockSpec((1, hb, block_q, d), lambda i, g, j: (i, g, j, 0)),
        pl.BlockSpec((1, hb, block_q, 1), lambda i, g, j: (i, g, j, 0)),
    ]
    o, lse = pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=in_specs,
        out_specs=out_specs,
        out_shape=out_shape,
        interpret=interpret,
        compiler_params=_compiler_params(interpret),
    )(q, k, v)
    return o, lse.reshape(b, h, sq)


# ---------------------------------------------------------------------------
# Pallas backward kernels: dq (grid over Q blocks) + dk/dv (grid over K
# blocks). P/dS tiles live in VMEM — the XLA-recompute fallback materializes
# them to HBM, which dominates attention cost at training shapes.
# ---------------------------------------------------------------------------

def _flash_bwd_fused_kernel(q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref,
                            dq_ref, dk_ref, dv_ref, dk_acc, dv_acc, *,
                            block_q: int, block_k: int, seq_q: int,
                            seq_k: int, scale: float, causal: bool,
                            num_heads: int):
    """dq + dk + dv in ONE pallas program (per (batch, head-group)).

    Every pallas_call costs a large fixed launch overhead on TPU relative
    to this kernel's work, so the two classic backward kernels (dq gridded
    over Q blocks, dk/dv gridded over K blocks) are fused: one program
    walks Q blocks, recomputes P per (Q,K) tile from the saved LSE, and
    accumulates dk/dv into fp32 VMEM scratch across the Q loop.
    """
    from jax.experimental import pallas as pl

    num_qb = seq_q // block_q
    num_kb = seq_k // block_k
    d = q_ref.shape[-1]
    q_pos0 = jax.lax.broadcasted_iota(jnp.int32, (block_q, block_k), 0)
    k_pos0 = jax.lax.broadcasted_iota(jnp.int32, (block_q, block_k), 1)

    def head_body(hh, _):
        dk_acc[...] = jnp.zeros((seq_k, d), jnp.float32)
        dv_acc[...] = jnp.zeros((seq_k, d), jnp.float32)

        def q_body(qb, _q):
            q = q_ref[0, hh, pl.ds(qb * block_q, block_q), :]
            do = do_ref[0, hh, pl.ds(qb * block_q, block_q), :]
            lse = lse_ref[0, hh, pl.ds(qb * block_q, block_q), :]
            delta = delta_ref[0, hh, pl.ds(qb * block_q, block_q), :]
            upper = (_causal_upper(qb, block_q, block_k, num_kb)
                     if causal else num_kb)

            def k_body(kb, dq_part):
                k_blk = k_ref[0, hh, pl.ds(kb * block_k, block_k), :]
                v_blk = v_ref[0, hh, pl.ds(kb * block_k, block_k), :]
                s = jax.lax.dot_general(
                    q, k_blk, (((1,), (1,)), ((), ())),
                    preferred_element_type=jnp.float32) * scale
                if causal:
                    s = jnp.where(
                        q_pos0 + qb * block_q >= k_pos0 + kb * block_k,
                        s, _NEG_INF)
                p = jnp.exp(s - lse)  # [bq, bk] fp32
                p_lo = p.astype(do.dtype)
                dp = jax.lax.dot_general(
                    do, v_blk, (((1,), (1,)), ((), ())),
                    preferred_element_type=jnp.float32)
                ds = (p * (dp - delta) * scale).astype(q.dtype)
                dv_acc[pl.ds(kb * block_k, block_k), :] += (
                    jax.lax.dot_general(
                        p_lo, do, (((0,), (0,)), ((), ())),
                        preferred_element_type=jnp.float32))
                dk_acc[pl.ds(kb * block_k, block_k), :] += (
                    jax.lax.dot_general(
                        ds, q, (((0,), (0,)), ((), ())),
                        preferred_element_type=jnp.float32))
                return dq_part + jax.lax.dot_general(
                    ds, k_blk, (((1,), (0,)), ((), ())),
                    preferred_element_type=jnp.float32)

            dq = jax.lax.fori_loop(
                0, upper, k_body, jnp.zeros((block_q, d), jnp.float32))
            dq_ref[0, hh, pl.ds(qb * block_q, block_q), :] = (
                dq.astype(dq_ref.dtype))
            return 0

        jax.lax.fori_loop(0, num_qb, q_body, 0)
        dk_ref[0, hh] = dk_acc[...].astype(dk_ref.dtype)
        dv_ref[0, hh] = dv_acc[...].astype(dv_ref.dtype)
        return 0

    jax.lax.fori_loop(0, num_heads, head_body, 0)


def _flash_bwd_pallas(q, k, v, o, lse, do, causal, scale,
                      block_q, block_k, interpret):
    from jax.experimental import pallas as pl

    b, h, sq, d = q.shape
    sk = k.shape[2]
    lse4 = lse.reshape(b, h, sq, 1)
    delta4 = jnp.sum(do.astype(jnp.float32) * o.astype(jnp.float32),
                     axis=-1, keepdims=True)  # [b, h, sq, 1] fp32
    block_q = min(block_q, sq)
    block_k = min(block_k, sk)
    esize = q.dtype.itemsize
    # Full-seq q/k/v/do in, dq/dk/dv out, double-buffered, plus fp32
    # compiler temps for the tile chain — empirically ~5.5MB/head at
    # seq 1024/d 64, so budget ~40*sq*d bytes per folded head.
    per_head = 5 * (7 * sq * d * esize + 8 * sq) + 8 * sk * d
    hb = _pick_head_block(h, per_head)

    full_q = pl.BlockSpec((1, hb, sq, d), lambda i, g: (i, g, 0, 0))
    full_q1 = pl.BlockSpec((1, hb, sq, 1), lambda i, g: (i, g, 0, 0))
    full_k = pl.BlockSpec((1, hb, sk, d), lambda i, g: (i, g, 0, 0))

    from jax.experimental.pallas import tpu as pltpu
    scratch = [pltpu.VMEM((sk, d), jnp.float32),
               pltpu.VMEM((sk, d), jnp.float32)]

    dq, dk, dv = pl.pallas_call(
        functools.partial(_flash_bwd_fused_kernel, block_q=block_q,
                          block_k=block_k, seq_q=sq, seq_k=sk, scale=scale,
                          causal=causal, num_heads=hb),
        grid=(b, h // hb),
        in_specs=[full_q, full_k, full_k, full_q, full_q1, full_q1],
        out_specs=[full_q, full_k, full_k],
        out_shape=[jax.ShapeDtypeStruct((b, h, sq, d), q.dtype),
                   jax.ShapeDtypeStruct((b, h, sk, d), k.dtype),
                   jax.ShapeDtypeStruct((b, h, sk, d), v.dtype)],
        scratch_shapes=scratch,
        interpret=interpret,
        compiler_params=_compiler_params(interpret),
    )(q, k, v, do, lse4, delta4)

    return dq, dk, dv


# ---------------------------------------------------------------------------
# Differentiable wrapper: pallas forward, blockwise-recompute backward.
# ---------------------------------------------------------------------------

@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4, 5, 6))
def _flash(q, k, v, causal, scale, block_q, block_k):
    o, _ = _flash_fwd_pallas(q, k, v, causal, scale, block_q, block_k,
                             interpret=not _on_tpu())
    return o


def _flash_fwd_rule(q, k, v, causal, scale, block_q, block_k):
    from jax.ad_checkpoint import checkpoint_name

    o, lse = _flash_fwd_pallas(q, k, v, causal, scale, block_q, block_k,
                               interpret=not _on_tpu())
    # Named so remat policies (gpt2 "dots_attn") can save BOTH outputs:
    # with o and lse saved, the rematerialized forward's kernel call is
    # dead code and the backward never re-runs flash.
    o = checkpoint_name(o, "attn_out")
    lse = checkpoint_name(lse, "attn_lse")
    return o, (q, k, v, o, lse)


def _flash_bwd_rule(causal, scale, block_q, block_k, res, do):
    """Backward: the fused pallas kernel, recomputing P per block from
    the saved LSE (no S×S materialization across blocks) with bf16 matmul
    operands and fp32 accumulation. ``flash_attention`` admits only
    shapes the blocks divide, so there is no other path."""
    q, k, v, o, lse = res
    return _flash_bwd_pallas(q, k, v, o, lse, do, causal, scale,
                             block_q, block_k, interpret=not _on_tpu())


_flash.defvjp(_flash_fwd_rule, _flash_bwd_rule)


def _tileable(q, k, causal: bool, block_q: int, block_k: int):
    """Clamp block sizes to the sequence and decide whether the pallas
    kernels can tile this shape; (bq, bk, ok)."""
    sq, sk = q.shape[2], k.shape[2]
    bq = min(block_q, sq)
    bk = min(block_k, sk)
    ok = not (sq % bq != 0 or sk % bk != 0
              or (causal and bq % bk != 0 and bk % bq != 0))
    return bq, bk, ok


def _blocks_or_raise(q, k, causal: bool, block_q: int, block_k: int):
    bq, bk, ok = _tileable(q, k, causal, block_q, block_k)
    if not ok:
        raise ValueError(
            f"flash attention cannot tile q seq {q.shape[2]} / k seq "
            f"{k.shape[2]} with blocks {bq}/{bk} (causal={causal}); use "
            "impl='reference' or 'auto' for such a shape")
    return bq, bk


def _use_reference(impl: str, q, k, causal: bool,
                   block_q: int, block_k: int) -> bool:
    """'reference' always; 'auto' wherever the kernel would not run
    compiled (off TPU, or a shape it cannot tile); 'flash' never."""
    if impl == "auto":
        return not (_on_tpu()
                    and _tileable(q, k, causal, block_q, block_k)[2])
    return impl == "reference"


def flash_attention(q, k, v, causal: bool = True,
                    scale: Optional[float] = None,
                    block_q: int = 512, block_k: int = 512):
    """Flash attention. q/k/v: [batch, heads, seq, head_dim].

    The Pallas kernel: compiled on TPU, interpreted (same code path) in
    CPU tests. Raises ``ValueError`` for a shape the kernel cannot tile.
    """
    if scale is None:
        scale = 1.0 / math.sqrt(q.shape[-1])
    bq, bk = _blocks_or_raise(q, k, causal, block_q, block_k)
    return _flash(q, k, v, causal, scale, bq, bk)


def attention(q, k, v, causal: bool = True, impl: str = "auto",
              scale: Optional[float] = None, mesh=None, spec=None):
    """Dispatch: 'flash' | 'reference' | 'auto' (flash on TPU for shapes
    it tiles, the reference elsewhere).

    ``mesh`` / ``spec``: GSPMD cannot partition a Mosaic kernel, so in a
    program over several devices the kernel runs per shard inside a
    ``shard_map`` over the whole ``mesh``, q/k/v and the output split as
    the PartitionSpec ``spec`` says. Attention is independent across
    batch and heads, so ``spec`` may shard those two dims and no other.
    The reference is plain XLA and needs neither.
    """
    if _use_reference(impl, q, k, causal, 512, 512):
        return mha_reference(q, k, v, causal=causal, scale=scale)
    fn = functools.partial(flash_attention, causal=causal, scale=scale)
    # Not where the caller is itself a shard_map body (the pp pipeline's
    # stages): there the mesh axes are manual already.
    if (mesh is not None and mesh.size > 1
            and not jax.sharding.get_abstract_mesh().manual_axes):
        fn = jax.shard_map(fn, mesh=mesh, in_specs=(spec, spec, spec),
                           out_specs=spec, check_vma=False)
    return fn(q, k, v)


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
    if _use_reference(impl, q, k, causal, block_q, block_k):
        return mha_reference_with_lse(q, k, v, causal=causal, scale=scale)
    bq, bk = _blocks_or_raise(q, k, causal, block_q, block_k)
    return _flash_fwd_pallas(q, k, v, causal, scale, bq, bk,
                             interpret=not _on_tpu())
