"""Operations and bytes of the delta rule's recurrence over a matrix
state (Kimi Delta Attention), from its sizes alone; kept with the
benchmark like ``opsbytes.py``, so that no PR that claims a gain can
change the count, and counted from the algorithm, so that it reads the
same work whatever implements the scan.

A ROW is one sequence's passage through one layer in one step: a decode
row's one token, or a prompt chunk of one slot. Per head the state ``S
[dk, dv]`` float32 is read once and written once a row, however many
tokens the row carries; a token brings ``q, k, g`` (dk each), ``v`` (dv)
and ``b`` (1) in and takes ``o`` (dv) out, float32.
"""

from __future__ import annotations

STATE_BYTES = 4  # the state and a token's vectors are float32


def row(heads: int, dk: int, dv: int, tokens: float = 1.0):
    """(flops, bytes) of one row of ``tokens`` tokens over ``heads``
    heads. Per token and head: the decay (dk dv multiplies), ``S^T k``,
    the rank-one update and ``S^T q`` (2 dk dv each)."""
    flops = heads * tokens * 7.0 * dk * dv
    nbytes = heads * STATE_BYTES * (
        2.0 * dk * dv + tokens * (3 * dk + 2 * dv + 1))
    return flops, nbytes
