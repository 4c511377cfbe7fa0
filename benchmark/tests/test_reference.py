"""The two float32 references against the program at a tiny size on the
CPU: the llama one against ``llama.forward`` and against greedy decoding
through the KV cache (``llama.generate`` / ``decode_step``), the GPT-2 one
against ``gpt2.loss_fn``."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmark.reference import gpt2 as ref_gpt2, llama as ref_llama
from ray_tpu.models import gpt2, llama

TINY = {"num_attention_heads": 4, "num_key_value_heads": 2,
        "rope_theta": 10000.0, "rms_norm_eps": 1e-6, "num_hidden_layers": 2}


@pytest.fixture(scope="module")
def tiny_llama():
    cfg = llama.CONFIGS["llama-tiny"]
    params, _ = llama.init_params(jax.random.PRNGKey(0), cfg)
    return params, cfg


def test_llama_logits_match_the_programs_forward(tiny_llama):
    params, cfg = tiny_llama
    toks = np.random.default_rng(0).integers(1, cfg.vocab_size, size=48)
    want = llama.forward(params, jnp.asarray(toks)[None], cfg)[0]
    got = ref_llama.logits(params, TINY, toks)
    # both float32 on the CPU: only the order of summation differs
    assert float(jnp.max(jnp.abs(got - want))) < 1e-5


def test_llama_reference_accepts_cached_greedy_decoding(tiny_llama):
    params, cfg = tiny_llama
    prompt = np.random.default_rng(1).integers(1, cfg.vocab_size, size=20)
    out = np.asarray(llama.generate(params, jnp.asarray(prompt)[None], cfg,
                                    max_new=8))[0]
    sample = {"prompt": prompt.tolist(), "tokens": out[20:].tolist()}
    res = ref_llama.check_generated(params, TINY, [sample])
    assert res["n"] == 8 and res["finite"]
    assert res["max_gap"] < 1e-4 and res["argmax_share"] == 1.0
    # a wrong token is seen: its gap is far beyond any tolerance in use
    wrong = dict(sample, tokens=[(t + 1) % cfg.vocab_size
                                 for t in sample["tokens"]])
    assert ref_llama.check_generated(params, TINY, [wrong])["max_gap"] > 0.2


def test_gpt2_loss_matches_the_programs():
    cfg = gpt2.GPT2Config(vocab_size=512, max_seq=64, num_layers=2,
                          num_heads=4, d_model=64, dtype=jnp.float32,
                          attention_impl="reference")
    params, _ = gpt2.init_params(jax.random.PRNGKey(0), cfg)
    toks = np.random.default_rng(0).integers(0, 512, (3, 65)).astype(np.int32)
    want = float(gpt2.loss_fn(params, {"tokens": jnp.asarray(toks)}, cfg))
    got = ref_gpt2.loss(params, {"n_head": 4, "n_layer": 2,
                                 "layer_norm_epsilon": 1e-5}, toks)
    assert abs(got - want) < 1e-5
