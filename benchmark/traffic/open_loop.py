"""Open loop: requests are due on a schedule whether or not earlier ones
have finished (independent users). The rate is fixed in the mix's file —
found once by a sweep on the chip — and never searched for in a run."""

from __future__ import annotations

import numpy as np

from benchmark.traffic import _draw

MODE = "open"


def plan(traffic: dict, seed: int, seconds: float, vocab: int,
         deployment: dict = None) -> dict:
    """Requests due inside ``[0, seconds)``: ``rate_per_s * seconds`` of
    them, Poisson gaps scaled so that they fill the window. Sizes and gaps are the mix's own; ``seed`` orders them."""
    rate = float(traffic["rate_per_s"])
    n = max(1, int(round(rate * seconds)))
    shape = np.random.default_rng(int(traffic.get("shape_seed", 0)))
    gaps = shape.exponential(1.0, size=n)
    prompt_len = _draw.lengths(traffic["prompt_len"], n, shape)
    output_len = _draw.lengths(traffic["output_len"], n, shape)
    rng = np.random.default_rng(seed)
    # scaled by the sum of all the gaps: the first request is due at 0 and
    # the last before the window closes
    due = (np.cumsum(gaps) - gaps[0]) * (seconds / gaps.sum())
    requests = []
    for i in range(n):
        requests.append({
            "id": i, "due_s": float(due[i]),
            "prompt": _draw.token_ids(rng, int(prompt_len[i]), vocab),
            "max_tokens": int(output_len[i]),
            "stream": bool(traffic.get("stream", True))})
    return {"mode": MODE, "requests": requests,
            "grace_s": float(traffic.get("grace_s", 20.0))}


def warmup(traffic: dict, seed: int, vocab: int) -> list:
    """The warm-up requests: the shortest and the longest prompt the mix
    can draw, and some between, so every shape is compiled in set-up."""
    rng = np.random.default_rng(seed + 1)
    lo, hi = traffic["prompt_len"]["min"], traffic["prompt_len"]["max"]
    n = int(traffic.get("warmup_requests", 2))
    sizes = np.linspace(lo, hi, n).astype(int)
    return [{"id": -1 - i, "due_s": 0.0,
             "prompt": _draw.token_ids(rng, int(s), vocab),
             "max_tokens": int(traffic.get("warmup_tokens", 9)),
             "stream": bool(traffic.get("stream", True))}
            for i, s in enumerate(sizes)]
