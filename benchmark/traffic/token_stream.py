"""Training batches from a seeded token stream.

Tokens follow a Zipf law over the vocabulary (rank r with weight r**-a),
as word frequencies do, so the loss has a unigram distribution to learn
and falls from ln(vocab) within the window. A fresh batch every step."""

from __future__ import annotations

import numpy as np

MODE = "train"


def batches(traffic: dict, seed: int, batch: int, vocab: int):
    """Yields ``[batch, seq + 1]`` int32 arrays for ever."""
    seq = int(traffic["seq"])
    a = float(traffic.get("zipf_a", 1.1))
    weights = np.arange(1, vocab + 1, dtype=np.float64) ** -a
    cdf = np.cumsum(weights / weights.sum())
    # The rank -> id map is the mix's own, so every seed trains on the
    # same distribution; the seed picks the samples.
    ids = np.random.default_rng(int(traffic.get("shape_seed", 0))
                                ).permutation(vocab).astype(np.int32)
    rng = np.random.default_rng(seed)
    while True:
        ranks = np.searchsorted(cdf, rng.random((batch, seq + 1)))
        yield ids[np.minimum(ranks, vocab - 1)]
