"""Seeded draws shared by the generators.

Every seed of a cell gets the SAME schedule: sizes, gaps and their order
come from the mix's own ``shape_seed``, and ``--seed`` draws the token ids
(and the weights). The engine's work does not depend on which ids it is
given (nothing is shared, nothing stops early), so two seeds differ no
more than two runs of one seed — at today's knee a window holds a few tens
of requests, and a tail over so few would otherwise swing with the order.
"""

from __future__ import annotations

import numpy as np


def lengths(spec: dict, n: int, rng: np.random.Generator) -> np.ndarray:
    """``n`` whole lengths from ``spec``: a log-normal given by its median
    and sigma, a uniform range, or one fixed value; clipped to min..max."""
    dist = spec["dist"]
    if dist == "lognormal":
        x = rng.lognormal(np.log(spec["median"]), spec["sigma"], size=n)
    elif dist == "uniform":
        x = rng.integers(spec["min"], spec["max"] + 1, size=n).astype(float)
    elif dist == "fixed":
        x = np.full(n, float(spec["value"]))
    else:
        raise ValueError(f"unknown length distribution {dist!r}")
    lo = spec.get("min", spec.get("value", 1))
    hi = spec.get("max", spec.get("value", 1 << 30))
    return np.clip(np.rint(x), lo, hi).astype(np.int64)


def token_ids(rng: np.random.Generator, n: int, vocab: int) -> list:
    """Uniform random ids in [1, vocab): nothing shared between prompts."""
    return [int(t) for t in rng.integers(1, vocab, size=n)]
