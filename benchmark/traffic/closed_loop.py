"""Closed loop: ``clients`` callers, each sends the next request of ONE
shared list when its last has returned (a batch job mapping over a Serve
handle). A slow system receives less load, so what is judged is tokens per
second completed. The clients start ``ramp_s`` before the window opens,
inside set-up, so the window sees a full batch from its first second.

The k-th request sent is the list's k-th whichever client sends it, and
the clients start ``start_stagger_s`` apart, so no two requests race each
other into the engine's queue: the program hands requests that arrive
together to the replica on separate threads, and which of them the engine
then admits first decides every later step of the run (PERF.md Findings,
PR 23: the check's runs differed by 92 tokens that way)."""

from __future__ import annotations

import numpy as np

from benchmark.traffic import _draw

MODE = "closed"


def plan(traffic: dict, seed: int, seconds: float, vocab: int,
         deployment: dict = None) -> dict:
    """More requests than the clients can finish in ramp + window; the
    sizes and their order are the mix's own, ``seed`` draws the ids."""
    clients = int(traffic.get("clients") or
                  traffic["clients_per_slot"] * deployment["num_slots"])
    per_client = int(traffic.get("requests_per_client", 64))
    n = clients * per_client
    shape = np.random.default_rng(int(traffic.get("shape_seed", 0)))
    prompt_len = _draw.lengths(traffic["prompt_len"], n, shape)
    output_len = _draw.lengths(traffic["output_len"], n, shape)
    rng = np.random.default_rng(seed)
    requests = [{"id": j,
                 "prompt": _draw.token_ids(rng, int(prompt_len[j]), vocab),
                 "max_tokens": int(output_len[j]),
                 "stream": bool(traffic.get("stream", False))}
                for j in range(n)]
    return {"mode": MODE, "clients": clients, "requests": requests,
            "start_stagger_s": float(traffic.get("start_stagger_s", 0.0)),
            "ramp_s": float(traffic.get("ramp_s", 0.0)),
            "grace_s": float(traffic.get("grace_s", 20.0))}


def warmup(traffic: dict, seed: int, vocab: int) -> list:
    rng = np.random.default_rng(seed + 1)
    lo, hi = traffic["prompt_len"]["min"], traffic["prompt_len"]["max"]
    return [{"id": -1 - i, "due_s": 0.0,
             "prompt": _draw.token_ids(rng, int(s), vocab),
             "max_tokens": 8, "stream": bool(traffic.get("stream", False))}
            for i, s in enumerate((lo, hi))]
