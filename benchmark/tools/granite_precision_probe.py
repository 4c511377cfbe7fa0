#!/usr/bin/env python3
"""What the reference comparison of the ``granite-4.0-h-micro`` cell reads
when the model is computed wrong, for setting its limits (chip only, no
engine):

    python3 benchmark/tools/granite_precision_probe.py <seed> [<seed> ...]

For each seed: the configuration's weights as the cell's replica has them
(the program's own ``init_params``), one sequence of random ids at the
cell's longest length (1024 of prompt + 512 generated), the float32
reference's logits at every generated position, and then the same forward
pass with ONE fault:

  * ``state_bfloat16``: the state rounded to bfloat16 after every token
    (the nearest precision below the float32 the configuration states);
  * ``weights_float8``: every weight matrix rounded to float8 e4m3 (the
    nearest below their bfloat16);
  * ``state_not_reset``: the state not zero at the sequence's start (what
    a slot keeps of its last request if admission does not reset it);
  * ``skip_dropped``: ``y = S C`` without ``D x``;
  * ``attention_scale_8th``: the softmax at ``head_dim^-1/2`` = 1/8, every
    other family's scale, for the published 1/64;

each with the products in the device's default precision, bfloat16
passes, which is the rounding an engine that computes in bfloat16 has
besides (``products_bfloat16`` is that rounding with no fault). The tokens
each variant would choose are held to the reference as
``check_generated`` holds the engine's (the largest reference logit minus
the reference logit of the chosen token), and the state each variant
holds after the last token as it holds a slot's (``state_err``,
``state_bits``; ``state_err_early``: the same after the sequence's first
40 tokens, where a state that was not reset has not yet faded). One JSON
line a seed. ``PROBE_TINY=1`` rehearses the
arithmetic on the CPU.
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

    from benchmark.drivers.serve_granite_replica import granite_config
    from benchmark.manifest import Manifest
    from benchmark.reference import granite_hybrid as ref
    from ray_tpu.models import granite

    tiny = bool(os.environ.get("PROBE_TINY"))
    if jax.devices()[0].platform == "cpu" and not tiny:
        raise SystemExit("the probe runs on the chip")
    cfg = Manifest(ROOT).config("granite-4.0-h-micro")
    prompt, length, early = 1024, 1536, 40
    if tiny:
        cfg = dict(cfg, hidden_size=64, num_attention_heads=4,
                   num_key_value_heads=2, shared_intermediate_size=128,
                   mamba_n_heads=8, mamba_d_head=16, mamba_d_state=16,
                   num_hidden_layers=4, vocab_size=256,
                   layer_types=["mamba", "mamba", "attention", "mamba"],
                   torch_dtype="float32")
        prompt, length = 16, 96
    head_dim = cfg["hidden_size"] // cfg["num_attention_heads"]
    faults = (
        ("state_bfloat16", {"state_dtype": jnp.bfloat16}),
        ("weights_float8", {"round_weights_to": jnp.float8_e4m3fn}),
        ("state_not_reset", {"stale_state": True}),
        ("skip_dropped", {"drop_skip": True}),
        ("attention_scale_8th", {"attention_scale": head_dim ** -0.5}))
    for seed in map(int, sys.argv[1:]):
        params, _ = granite.init_params(
            jax.random.PRNGKey(seed % (2**31 - 1)), granite_config(cfg))
        toks = np.random.default_rng(seed).integers(
            0, cfg["vocab_size"], size=length)
        rows = np.arange(prompt, length)
        want, want_states = ref.logits(params, cfg, toks, rows=rows,
                                       states_after=length)
        want_states = np.asarray(want_states[:, 1])
        top = want.max(axis=-1)

        def held(chosen, states=None):
            gaps = np.asarray(top - jnp.take_along_axis(
                want, jnp.asarray(chosen)[:, None], axis=1)[:, 0],
                np.float64)
            out = {"max_gap": float(gaps.max()),
                   "mean_gap": float(gaps.mean()),
                   "argmax_share": float((gaps == 0).mean())}
            if states is not None:
                out.update(
                    state_err=ref.state_error(states[:, 1], want_states),
                    state_bits=ref.mantissa_bits(states[:, 1]))
            return out

        early_want = np.asarray(ref.logits(
            params, cfg, toks[:early], rows=np.zeros(1, int),
            states_after=early)[1][:, 1])

        def variant(**kw):
            lg, states = ref.logits(params, cfg, toks, rows=rows,
                                    states_after=length, **kw)
            out = held(jnp.argmax(lg, -1), states)
            # the state a few tokens in, as the replica's early replay
            # reads it
            out["state_err_early"] = ref.state_error(np.asarray(ref.logits(
                params, cfg, toks[:early], rows=np.zeros(1, int),
                states_after=early, **kw)[1][:, 1]), early_want)
            return out

        out = {"seed": seed, "positions": length - prompt,
               "logit_std": float(jnp.std(want, axis=1).mean()),
               "random_token": held(np.random.default_rng(seed + 1).integers(
                   0, cfg["vocab_size"], size=length - prompt)),
               "products_bfloat16": variant(precision="default")}
        for name, kw in faults:
            out[name + "+products_bfloat16"] = variant(precision="default",
                                                       **kw)
        del params, want
        print(json.dumps(out), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
