"""Grouped matrix product over the experts of a sparse FFN: ``lhs [m, k]``
rows sorted by group, ``rhs [g, k, n]`` one matrix a group, ``sizes [g]``
rows a group; group e multiplies its own rows and nothing else. Rows
behind the last group belong to none: their result is unspecified (the
caller masks it).

On the TPU this is JAX's megablox kernel (``jax.experimental.pallas.ops.
tpu.megablox.gmm``) at a tiling chosen for FEW ROWS A GROUP, which is
what a decode step has (64 rows x 4 picks over 64 experts: ~4 rows an
expert). The kernel visits every group that has a row once for each row
tile it touches and multiplies a whole ``tm``-row tile there, so the MXU
work is visits x tm rows whatever the rows are: at the compiler's own
choice for ``lax.ragged_dot`` (tm = m up to 512) that work equals or
exceeds the time the weights take to stream, and the product sat at 53 %
(m = 256) and 36 % (m = 512) of the HBM roofline; at tm = 64, the whole
of k in one tile and 1024 lanes of n it reads 87 % / 84 % (PERF.md
Findings, PR 31: one v5e chip, [64, 2048, 3072] bfloat16). Taking k whole
also means one accumulation a tile, so a row's result does not depend on
m, on tm or on where the row lies: the engine's two step programs give a
decode row the same bits.

Off the TPU (the CPU tests) it is ``lax.ragged_dot``.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

from .attention import _on_tpu

_ROW_TILE = 64
_LANE_TILES = (1024, 512, 256, 128)


def use_kernel() -> bool:
    return _on_tpu()


def grouped_matmul(lhs, rhs, sizes, interpret: bool = False):
    """-> [m, n] in ``lhs.dtype`` (float32 accumulation). ``interpret``
    runs the kernel interpreted wherever it is called (the CPU tests'
    parity check); otherwise the TPU gets the kernel and everything else
    ``lax.ragged_dot``."""
    if not (interpret or use_kernel()):
        return jax.lax.ragged_dot(lhs, rhs, sizes)
    from jax.experimental.pallas.ops.tpu.megablox import gmm

    m, k = lhs.shape
    n = rhs.shape[2]
    tn = next((t for t in _LANE_TILES if n % t == 0), None)
    if tn is None or k % 128:
        raise ValueError(
            f"grouped_matmul cannot tile k={k}, n={n}: both must be "
            "multiples of 128")
    pad = -m % _ROW_TILE
    if pad:  # rows of no group
        lhs = jnp.pad(lhs, ((0, pad), (0, 0)))
    out = gmm(lhs, rhs, sizes.astype(jnp.int32),
              preferred_element_type=lhs.dtype, tiling=(_ROW_TILE, k, tn),
              interpret=interpret)
    return out[:m] if pad else out
