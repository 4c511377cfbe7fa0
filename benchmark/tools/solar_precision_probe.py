#!/usr/bin/env python3
"""What the reference comparison of the ``solar-open2-250b`` cell reads
when the model is computed wrong, for setting its limits (chip only, no
engine):

    python3 benchmark/tools/solar_precision_probe.py <seed> [<seed> ...]

For each seed: the configuration's weights as the cell's replica has
them (the program's own ``init_params``, then the expert bias balanced by
``serve_solar_replica.balance_expert_bias``), one sequence of random ids at the cell's longest length
(256 of prompt + 1024 generated), the float32 reference's logits at every
generated position, and then the same forward pass with ONE fault:

  * ``state_bfloat16``: the KDA state rounded to bfloat16 after every
    token (the nearest precision below the float32 the configuration
    states for it);
  * ``experts_float8``: the held experts' weights rounded to float8 e4m3
    (the nearest below their bfloat16);
  * ``pick_dropped``: the eighth expert of every token dropped;
  * ``state_not_reset``: the KDA state not zero at the sequence's start
    (what a slot keeps of its last request if admission does not reset
    it);

each once with every product in float32 (the fault alone) and once with
the products in the device's default precision, bfloat16 passes, which is
the rounding an engine that computes in bfloat16 has besides
(``products_bfloat16`` is that rounding with no fault). The tokens each
variant would choose are held to the reference as ``check_generated``
holds the engine's: the largest reference logit minus the reference logit
of the chosen token; and the KDA state each variant holds after the last
token as ``check_generated`` holds a slot's (``state_err``, ``state_bits``).
One JSON line a seed.
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

    from benchmark.drivers.serve_solar_replica import (balance_expert_bias,
                                                       solar_config)
    from benchmark.manifest import Manifest
    from benchmark.reference import solar_open2 as ref
    from ray_tpu.models import solar

    tiny = bool(os.environ.get("PROBE_TINY"))
    if jax.devices()[0].platform == "cpu" and not tiny:
        raise SystemExit("the probe runs on the chip")
    cfg = Manifest(ROOT).config("solar-open2-250b")
    prompt, length = 256, 1280
    if tiny:  # a CPU rehearsal of the arithmetic
        cfg = dict(cfg, hidden_size=64, num_attention_heads=4,
                   num_key_value_heads=2, head_dim=16,
                   moe_intermediate_size=32, kda_lowrank_width=16,
                   linear_attn_config=dict(cfg["linear_attn_config"],
                                           head_dim=16, num_heads=4),
                   n_routed_experts=4, n_routed_experts_published=16,
                   experts_held_first=4, vocab_size=256,
                   vocab_size_published=512, torch_dtype="float32")
        prompt, length = 16, 96
    faults = (
        ("state_bfloat16", {"state_dtype": jnp.bfloat16}, cfg),
        ("experts_float8", {"round_experts_to": jnp.float8_e4m3fn}, cfg),
        ("pick_dropped", {}, dict(
            cfg, num_experts_per_tok=cfg["num_experts_per_tok"] - 1)),
        ("state_not_reset", {"stale_state": True}, cfg))
    for seed in map(int, sys.argv[1:]):
        params, _ = solar.init_params(
            jax.random.PRNGKey(seed % (2**31 - 1)), solar_config(cfg))
        params = balance_expert_bias(ref, params, cfg, seed % (2**31 - 1))
        toks = np.random.default_rng(seed).integers(
            0, cfg["vocab_size"], size=length)
        want, want_states = ref.logits(params, cfg, toks,
                                       states_after=length)
        want, want_states = want[prompt:], np.asarray(want_states[:, 1])
        top = want.max(axis=-1)

        def held(chosen, states=None):
            gaps = np.asarray(top - jnp.take_along_axis(
                want, jnp.asarray(chosen)[:, None], axis=1)[:, 0],
                np.float64)
            out = {"max_gap": float(gaps.max()),
                   "mean_gap": float(gaps.mean()),
                   "min_gap": float(gaps.min()),
                   "argmax_share": float((gaps == 0).mean())}
            if states is not None:
                out.update(
                    state_err=ref.state_error(states[:, 1], want_states),
                    state_bits=ref.mantissa_bits(states[:, 1]))
            return out

        def variant(c, **kw):
            lg, states = ref.logits(params, c, toks, states_after=length,
                                    **kw)
            return held(jnp.argmax(lg[prompt:], -1), states)

        out = {"seed": seed, "positions": length - prompt,
               "random_token": held(np.random.default_rng(seed + 1).integers(
                   0, cfg["vocab_size"], size=length - prompt)),
               "products_bfloat16": variant(cfg, precision="default")}
        for name, kw, c in faults:
            for tag, precision in (("", "highest"),
                                   ("+products_bfloat16", "default")):
                out[name + tag] = variant(c, precision=precision, **kw)
        del params, want
        print(json.dumps(out), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
