#!/usr/bin/env python3
"""Does a decode row get the same bits whatever rides along with it?
(chip only; a builder's probe, PERF.md Findings PR 31)

    python3 benchmark/tools/lfm2_invariance_probe.py

The ``lfm2-24b-a2b`` configuration cut after 0, 1, 2, ... layers; the
family's step on the same decode rows and the same cache (a) without a
chunk, (b) with an empty chunk and (c) with a real one, and how many of
the decode rows' logits differ. (b) and (c) are ONE compiled program: a
greedy request repeats bit for bit only if they never differ, because
one differing bit in a router's input sooner or later picks another
expert. (a) against (c) is two programs, and shows why the family asks
the engine for one (``models/serving.py one_program``).
"""
import dataclasses
import json
import os
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
sys.path.insert(0, ROOT)


def main() -> int:
    import jax
    import jax.numpy as jnp
    import numpy as np

    from benchmark.drivers.serve_lfm2_replica import lfm2_config
    from benchmark.manifest import Manifest
    from ray_tpu.models import lfm2, serving

    cfg = lfm2_config(Manifest(ROOT).config("lfm2-24b-a2b"))
    if os.environ.get("PROBE_TINY"):
        cfg = lfm2.CONFIGS["lfm2-tiny"]
    slots, page, chunk = 64, 16, 64
    if os.environ.get("PROBE_TINY"):
        slots, page, chunk = 4, 8, 16
    params, _ = lfm2.init_params(jax.random.PRNGKey(7), cfg)
    model = serving.model_for(cfg)
    rng = np.random.default_rng(7)
    pages_per = cfg.max_seq // page
    tables = np.arange(slots * pages_per, dtype=np.int32).reshape(
        slots, pages_per) + 1
    tokens = rng.integers(1, cfg.vocab_size, slots).astype(np.int32)
    pos = rng.integers(cfg.max_seq // 16, cfg.max_seq // 2,
                       slots).astype(np.int32)
    pos[-1] = cfg.max_seq                      # parked: the chunk's slot
    pre = rng.integers(1, cfg.vocab_size, chunk).astype(np.int32)
    for n in range(cfg.num_layers + 1):
        cut = dataclasses.replace(
            cfg, layer_types=cfg.layer_types[:n],
            num_dense_layers=min(cfg.num_dense_layers, n))
        p = dict(params, layers=params["layers"][:n])

        def cache():
            c = model.slot_state.attach(
                cut, model.init_cache(cut, slots * pages_per + 1, page),
                slots)
            # some history, the same on both sides
            return jax.tree.map(
                lambda a: (0.02 * jax.random.normal(
                    jax.random.PRNGKey(1), a.shape, jnp.float32)
                ).astype(a.dtype), c)

        step = jax.jit(lambda p, c, ch: model.step(
            p, c, jnp.asarray(tables), jnp.asarray(tokens),
            jnp.asarray(pos), ch, cut, page)[0])

        def chunk_of(toks, n_valid):
            return (jnp.asarray(toks), jnp.int32(slots - 1), jnp.int32(0),
                    jnp.int32(n_valid))

        alone = np.asarray(step(p, cache(), None), np.float32)[:-1]
        empty = np.asarray(step(p, cache(), chunk_of(0 * pre, 0)),
                           np.float32)[:-1]
        real = np.asarray(step(p, cache(), chunk_of(pre, chunk)),
                          np.float32)[:-1]
        out = {"layers": n, "last": list(cut.layer_types[-1:])}
        for name, a, b in (("two_programs", alone, real),
                           ("one_program", empty, real)):
            diff = np.abs(a - b)
            out[name] = {"rows_differing": int((diff.max(axis=1) > 0).sum()),
                         "max_abs": float(diff.max())}
        print(json.dumps(out), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
