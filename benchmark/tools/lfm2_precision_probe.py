#!/usr/bin/env python3
"""What the reference comparison of the ``lfm2-24b-a2b`` cells reads when
the model is computed wrong, for setting its bound (chip only, no engine):

    python3 benchmark/tools/lfm2_precision_probe.py <seed> [<seed> ...]

For each seed: the configuration's weights from the program's own
``init_params``, one sequence of random ids, the float32 reference's
logits at every position, and then the same forward pass (a) with the
experts' weights rounded to float8 e4m3, the nearest precision below the
bfloat16 the configuration states, and (b) with the fourth expert of
every token dropped (top-3 of the 4); and (c) what a token that is simply
wrong reads (uniform random ids). The tokens each variant would choose
are held to the reference as ``check_generated`` holds the
engine's: the largest reference logit minus the reference logit of the
chosen token. One JSON line a seed.
"""
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
    from benchmark.reference import lfm2_moe as ref
    from ray_tpu.models import lfm2

    if jax.devices()[0].platform == "cpu" and not os.environ.get(
            "PROBE_TINY"):
        raise SystemExit("the probe runs on the chip")
    cfg = Manifest(ROOT).config("lfm2-24b-a2b")
    if os.environ.get("PROBE_TINY"):  # a CPU rehearsal of the arithmetic
        cfg = dict(cfg, hidden_size=64, intermediate_size=160,
                   moe_intermediate_size=48, num_experts=8, vocab_size=512,
                   num_attention_heads=4, num_key_value_heads=2,
                   torch_dtype="float32")
    length = 512
    for seed in map(int, sys.argv[1:]):
        params, _ = lfm2.init_params(
            jax.random.PRNGKey(seed % (2**31 - 1)), lfm2_config(cfg))
        toks = np.random.default_rng(seed).integers(
            1, cfg["vocab_size"], size=length)
        want = ref.logits(params, cfg, toks)
        top = want.max(axis=-1)
        out = {"seed": seed, "positions": length}
        # a token that is simply wrong: uniform random ids
        wrong = np.random.default_rng(seed + 1).integers(
            1, cfg["vocab_size"], size=length)
        gaps = np.asarray(top - jnp.take_along_axis(
            want, jnp.asarray(wrong)[:, None], axis=1)[:, 0], np.float64)
        out["random_token"] = {"max_gap": float(gaps.max()),
                               "mean_gap": float(gaps.mean()),
                               "min_gap": float(gaps.min())}
        for name, kw, c in (
                ("float8_e4m3_experts",
                 {"round_experts_to": jnp.float8_e4m3fn}, cfg),
                ("top3_of_4", {}, dict(
                    cfg, num_experts_per_tok=cfg["num_experts_per_tok"] - 1))):
            chosen = jnp.argmax(ref.logits(params, c, toks, **kw), axis=-1)
            gaps = np.asarray(top - jnp.take_along_axis(
                want, chosen[:, None], axis=1)[:, 0], np.float64)
            out[name] = {"max_gap": float(gaps.max()),
                         "mean_gap": float(gaps.mean()),
                         "argmax_share": float((gaps == 0).mean())}
        del params, want
        print(json.dumps(out), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
