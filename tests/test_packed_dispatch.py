"""A dispatch crosses to the device as one buffer (PR 38): the packed
``host_in`` vector's layout (``llm/engine.py HostInputs``: rows, page
table, lane) and — since the CPU backend may alias a host array it is
handed — the tokens that a stale table or a refilled buffer would get
wrong, for every family the engine serves, greedy and sampled."""

import os
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

from benchmark.reference import lfm2_moe as lfm2_ref  # noqa: E402
from ray_tpu.llm.engine import HostInputs, SlotEngine  # noqa: E402
from ray_tpu.models import lfm2, llama  # noqa: E402

PS = 8
LLAMA = llama.CONFIGS["llama-tiny"]
LFM2 = lfm2.CONFIGS["lfm2-tiny"]
# the tiny lfm2 preset as the benchmark's configuration file spells it
LFM2_REF = {"num_attention_heads": LFM2.num_heads,
            "num_key_value_heads": LFM2.num_kv_heads,
            "rope_parameters": {"rope_theta": LFM2.rope_theta},
            "norm_eps": LFM2.norm_eps, "conv_L_cache": LFM2.conv_L_cache,
            "num_experts_per_tok": LFM2.num_experts_per_tok,
            "use_expert_bias": True, "norm_topk_prob": True,
            "routed_scaling_factor": 1.0,
            "layer_types": list(LFM2.layer_types),
            "num_dense_layers": LFM2.num_dense_layers}
# family -> (config, every position's logits of one full forward pass of
# the plain model: the reference path tests/test_llm_paged.py and
# tests/test_lfm2_serving.py already hold the engine to)
FAMILIES = {
    "llama": (LLAMA, lambda p, toks: llama.forward(
        p, jnp.asarray([toks], jnp.int32), LLAMA)[0]),
    "lfm2": (LFM2, lambda p, toks: lfm2_ref.logits(p, LFM2_REF, toks)),
}


@pytest.fixture(scope="module")
def params():
    return {"llama": llama.init_params(jax.random.PRNGKey(0), LLAMA)[0],
            "lfm2": lfm2.init_params(jax.random.PRNGKey(0), LFM2)[0]}


def drain(eng, handles, max_steps=800):
    for _ in range(max_steps):
        if all(h._done.is_set() for h in handles):
            return [h.result(timeout=0).tokens for h in handles]
        eng.step()
    raise AssertionError("engine did not finish in max_steps")


def prompt_of(seed, n, cfg=LLAMA):
    return np.random.default_rng(seed).integers(
        1, cfg.vocab_size, size=n).tolist()


# -- (d) the layout ------------------------------------------------------------

@pytest.mark.parametrize("chunk", [16, 64, 256])
@pytest.mark.parametrize("rows", [1, 3, 64])
def test_layout_round_trips_through_jit_bit_for_bit(rows, chunk):
    """What the packer writes through ``views`` is what a jitted program
    reads through ``unpack``: a float32 temperature of 0.7 and a negative
    seed come back with the bits they went in with, the mask as a bool,
    the page table in its shape, the lane's scalars as scalars; the
    decode-only layout is the fused one's beginning."""
    pages = 128
    fused, decode = HostInputs(rows, pages, chunk), HostInputs(rows, pages)
    assert decode.size == rows * (5 + pages)
    assert fused.size == decode.size + chunk + 5
    assert all(fused.fields[n] == decode.fields[n] for n in decode.fields)
    rng = np.random.default_rng(rows * 1000 + chunk)
    for layout in (fused, decode):
        idle = layout.idle(2048)
        f = layout.views(idle.copy())
        assert f["override_mask"].all() and (f["pos"] == 2048).all()
        assert not f["temps"].any() and not f["seeds"].any()
        assert f["tables"].shape == (rows, pages) and not f["tables"].any()
        buf = idle.copy()
        f = layout.views(buf)
        want = {"override_vals": rng.integers(0, 2**31 - 1, rows),
                "override_mask": rng.integers(0, 2, rows),
                "pos": rng.integers(0, 2048, rows),
                "temps": rng.random(rows).astype(np.float32),
                "seeds": rng.integers(-2**31, 2**31 - 1, rows),
                "tables": rng.integers(0, 8193, (rows, pages))}
        want["temps"][0] = np.float32(0.7)
        want["seeds"][0] = -123456789
        if layout is fused:
            want.update(pre_tokens=rng.integers(0, 65536, chunk),
                        lane_slot=[rows - 1], p0=[1792], n_valid=[chunk - 3],
                        lane_temp=[np.float32(0.7)], lane_seed=[-7])
        for name, vals in want.items():
            f[name][:] = vals
        assert buf.dtype == np.int32 and buf.shape == (layout.size,)
        assert not (idle[layout.fields["tables"]]).any()   # views, not copies
        got = jax.jit(layout.unpack)(jnp.asarray(buf))
        assert set(got) == set(want)
        for name, vals in want.items():
            x = np.asarray(got[name])
            if name == "override_mask":
                assert x.dtype == np.bool_
                assert x.tolist() == [bool(v) for v in vals]
                continue
            is_float = name in HostInputs.FLOATS
            assert x.dtype == (np.float32 if is_float else np.int32)
            assert x.shape == (() if name in HostInputs.LANE
                               else np.shape(vals))
            want_bits = np.asarray(vals, x.dtype).reshape(x.shape)
            assert x.tobytes() == want_bits.tobytes(), name
    with pytest.raises(TypeError, match="int32"):
        jax.jit(fused.unpack)(jnp.zeros((decode.size,), jnp.int32))


# -- (a) every family, greedy and sampled --------------------------------------

def expected_token(logits_row, temperature, seed, qpos):
    """Token index ``qpos`` of a request, as ``engine._sample`` draws it."""
    if temperature == 0:
        return int(jnp.argmax(logits_row))
    key = jax.random.fold_in(jax.random.PRNGKey(seed), qpos)
    return int(jax.random.categorical(
        key, logits_row / jnp.maximum(jnp.float32(temperature), 1e-6)))


def expected_tokens(logits_of, params, prompt, toks, temperature=0.0,
                    seed=0):
    """What the plain model says at each position of ``toks`` after
    ``prompt``, from one forward pass over both."""
    logits = logits_of(params, prompt + toks)
    return [expected_token(logits[len(prompt) + j - 1], temperature, seed,
                           len(prompt) + j) for j in range(len(toks))]


@pytest.mark.parametrize("temperature", [0.0, 0.7],
                         ids=["greedy", "sampled"])
@pytest.mark.parametrize("family", sorted(FAMILIES))
def test_tokens_through_the_packed_dispatch_are_the_plain_models(
        params, family, temperature):
    """Five requests over two slots, so that prompts are admitted while
    the other slot decodes (fused steps, steps with no prompt, slots
    released and mapped again): every token of every request is what the
    family's plain forward pass says at that position, drawn from the
    request's own ``fold_in`` stream."""
    cfg, logits_of = FAMILIES[family]
    eng = SlotEngine(params[family], cfg, num_slots=2, chunk=16,
                     page_size=PS)
    prompts = [prompt_of(40 + i, n, cfg)
               for i, n in enumerate((21, 9, 37, 16, 5))]
    seeds = [1234, 2**31 - 5, 7, 99, 31337]
    handles = [eng.submit(p, max_new=6 + i, temperature=temperature, seed=s)
               for i, (p, s) in enumerate(zip(prompts, seeds))]
    outs = drain(eng, handles)
    assert eng.steps_block > 0 and eng.steps_decode_only > 0
    for prompt, seed, toks in zip(prompts, seeds, outs):
        assert toks == expected_tokens(logits_of, params[family], prompt,
                                       toks, temperature, seed)


# -- (b) the table a dispatch carries is never stale ---------------------------

def _watched(eng):
    """Every dispatch of ``eng`` checks the table inside the vector it is
    handed against the engine's own, word for word, before it runs, and
    keeps the vector with a copy of its words as they were then. The
    engine's live table and its two idle vectors are moved to 64-byte
    aligned memory first, which the CPU backend aliases when handed it as
    it is."""
    def aligned(x):
        raw = np.zeros((x.nbytes + 64,), np.uint8)
        at = -raw.ctypes.data % 64
        out = raw[at:at + x.nbytes].view(x.dtype).reshape(x.shape)
        out[...] = x
        return out

    eng._tables = aligned(eng._tables)
    eng._host_in = {fused: (layout, aligned(idle))
                    for fused, (layout, idle) in eng._host_in.items()}
    seen = []

    def watching(fn, fused):
        layout = eng._host_in[fused][0]

        def call(*args):
            host_in = np.array(args[3])
            assert np.array_equal(layout.views(host_in)["tables"],
                                  eng._tables)
            seen.append((args[3], host_in))
            return fn(*args)
        return call

    eng._block, eng._decode_only = (watching(eng._block, True),
                                    watching(eng._decode_only, False))
    return seen


def _readmit(eng):
    """One slot: each request takes the slot its predecessor released,
    with other pages behind the same table row (the pool hands freed
    pages out oldest first)."""
    out = []
    for i, n in enumerate((30, 12, 45)):
        prompt = prompt_of(60 + i, n)
        out += [(prompt, *drain(eng, [eng.submit(prompt, max_new=7)]))]
    return out


def _prefix_hit_and_cow(eng):
    """A second request shares the first one's full pages read-only and
    copies the partly filled one on write; then both decode at once."""
    base = prompt_of(70, 29)            # 3 full pages of 8 and 5 tokens
    (said,) = drain(eng, [eng.submit(base, max_new=9)])
    prompts = [base + said[:6] + prompt_of(71, 4),
               base[:20] + prompt_of(72, 9)]
    outs = drain(eng, [eng.submit(p, max_new=8) for p in prompts])
    assert eng.prefix_hits >= 2
    return [(base, said)] + list(zip(prompts, outs))


def _session_import(eng):
    """A session exported from another engine is imported between two
    steps, while a request decodes, and its next turn hits the imported
    pages."""
    src = SlotEngine(eng._params, LLAMA, num_slots=2, chunk=8, page_size=PS,
                     num_pages=64)
    first = prompt_of(80, 32)
    (said,) = drain(src, [src.submit(first, max_new=4, session_id="s")])
    snap = src.export_session("s")
    prompts = [prompt_of(81, 11), first + said + prompt_of(82, 3)]
    busy = eng.submit(prompts[0], max_new=20)
    for _ in range(5):
        eng.step()
    info = eng.import_session(snap)
    assert info["pages_imported"] > 0
    turn = eng.submit(prompts[1], max_new=6, session_id="s")
    outs = drain(eng, [busy, turn])
    assert turn.result(timeout=0).timing["matched_tokens"] >= 32
    return list(zip(prompts, outs))


@pytest.mark.parametrize("schedule", [_readmit, _prefix_hit_and_cow,
                                      _session_import],
                         ids=lambda f: f.__name__.strip("_"))
def test_table_in_the_vector_is_the_engines_at_every_dispatch(params,
                                                              schedule):
    """After a release and a re-admission, a prefix hit with a copy on
    write, and a session import, the table a dispatch carries is the
    engine's at that moment, no vector a dispatch was handed has been
    written since, and the tokens are the plain model's."""
    eng = SlotEngine(params["llama"], LLAMA, num_slots=1 if schedule
                     is _readmit else 2, chunk=8, page_size=PS, num_pages=64)
    seen = _watched(eng)
    got = schedule(eng)
    assert len(seen) == eng.steps_block + eng.steps_decode_only > 10
    # every dispatch got memory of its own: the engine's live table and
    # the next dispatch's vector are written elsewhere
    for handed, then in seen:
        assert np.array_equal(np.asarray(handed), then)
    logits_of = FAMILIES["llama"][1]
    for prompt, toks in got:
        assert toks and toks == expected_tokens(logits_of, params["llama"],
                                                prompt, toks)
