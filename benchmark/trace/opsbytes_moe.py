"""Operations and bytes of a grouped (ragged) matrix product over the
experts of a sparse FFN, from its shapes alone; kept with the benchmark
like ``opsbytes.py``, so that no PR that claims a gain can change the
count.

The product: ``lhs [m, k]`` rows sorted by expert, ``rhs [g, k, n]`` one
matrix an expert, ``out [m, n]``; expert e multiplies the rows of its
group and nothing else.
"""

from __future__ import annotations

FLOATS = ("bf16", "f16", "f32")


def classify_grouped_matmul(kernel: dict):
    """A traced kernel call -> ``(m, k, n, g, element bytes)`` if its
    shapes are a grouped product's — among its operands exactly one
    floating [g, k, n] and one floating [m, k], its first output [m, n] —
    else None. (The compiler's own grouped-matmul kernel takes the group
    offsets as small integer vectors before them; the paged-attention
    kernel's pool has five axes and matches nothing here.)"""
    from benchmark.trace.opsbytes import DTYPE_BYTES

    ops = [(d, dims) for d, dims in kernel["operands"] if d in FLOATS]
    rhs = [(d, dims) for d, dims in ops if len(dims) == 3]
    lhs = [(d, dims) for d, dims in ops if len(dims) == 2]
    outs = kernel["outputs"]
    if len(rhs) != 1 or len(lhs) != 1 or not outs or len(outs[0][1]) != 2:
        return None
    (dtype, (g, k, n)), (_, (m, k2)) = rhs[0], lhs[0]
    if k2 != k or tuple(outs[0][1]) != (m, n):
        return None
    return m, k, n, g, DTYPE_BYTES[dtype]


def grouped_matmul(rows: float, groups_hit: float, m: int, k: int, n: int,
                   ebytes: int = 2):
    """(flops, bytes) of one call in which ``rows`` of the m rows belong
    to a group and ``groups_hit`` of the groups have a row: 2 flops a
    multiply-add for the rows that are routed; the weights of the experts
    hit read once, the m rows read and the m result rows written once
    (whole: the buffers are those sizes whatever is routed)."""
    flops = 2.0 * rows * k * n
    nbytes = ebytes * (groups_hit * k * n + m * k + m * n)
    return flops, float(nbytes)
