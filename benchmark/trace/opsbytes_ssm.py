"""Operations and bytes of the Mamba-2 state-space recurrence over a
matrix state a head, from its sizes alone; kept with the benchmark like
``opsbytes.py``, so that no PR that claims a gain can change the count,
and counted from the algorithm, so that it reads the same work whatever
implements the scan.

A ROW is one sequence's passage through one layer in one step: a decode
row's one token, or a prompt chunk of one slot. Per head the state ``S
[P, N]`` float32 is read once and written once a row, however many tokens
the row carries; a token brings ``x`` (P a head), the step size and the
decay (1 a head each) and ``B``, ``C`` (N each, shared by all heads) in
and takes ``y`` (P a head) out, float32.
"""

from __future__ import annotations

STATE_BYTES = 4  # the state and a token's vectors are float32


def rows(heads: int, p: int, n: int, rows: float, tokens: float):
    """(flops, bytes) of ``rows`` rows that carry ``tokens`` tokens in
    all, over ``heads`` heads. Per token and head: the decay (P N
    multiplies), the rank-one update and ``S C`` (2 P N each)."""
    flops = heads * tokens * 5.0 * p * n
    nbytes = STATE_BYTES * (rows * 2.0 * heads * p * n
                            + tokens * (heads * (2 * p + 2) + 2 * n))
    return flops, nbytes
