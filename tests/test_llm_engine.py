"""Continuous-batching engine tests: slot-batched output must match the
single-request decode path token-for-token (VERDICT r4 item 1)."""

import dataclasses
import threading

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from ray_tpu.llm.engine import SlotEngine, prefill_lane
from ray_tpu.models import lfm2, llama, serving
from ray_tpu.parallel.mesh import DEVICE_PEAKS

CFG = llama.CONFIGS["llama-tiny"]


@pytest.fixture(scope="module")
def params():
    p, _ = llama.init_params(jax.random.PRNGKey(0), CFG)
    return p


def reference_tokens(params, prompt, max_new):
    """Single-request greedy reference via the plain generate() path."""
    out = llama.generate(params, np.asarray([prompt], dtype=np.int32),
                         CFG, max_new=max_new)
    return [int(t) for t in np.asarray(out)[0, len(prompt):]]


def drain(engine, handles, max_steps=500):
    for _ in range(max_steps):
        if all(h._done.is_set() for h in handles):
            return
        engine.step()
    raise AssertionError("engine did not finish in max_steps")


def test_single_request_matches_generate(params):
    prompt = [3, 141, 59, 26, 5]
    engine = SlotEngine(params, CFG, num_slots=4, chunk=8)
    h = engine.submit(prompt, max_new=12)
    drain(engine, [h])
    res = h.result(timeout=0)
    assert res.tokens == reference_tokens(params, prompt, 12)
    assert res.finish_reason == "length"
    assert res.prompt_len == len(prompt)


def test_chunked_prefill_matches_generate(params):
    # Prompt much longer than the chunk: 23 tokens / chunk 4 -> 6 chunks
    # with a ragged tail.
    rng = np.random.default_rng(7)
    prompt = [int(t) for t in rng.integers(1, CFG.vocab_size, size=23)]
    engine = SlotEngine(params, CFG, num_slots=2, chunk=4)
    h = engine.submit(prompt, max_new=8)
    drain(engine, [h])
    assert h.result(timeout=0).tokens == reference_tokens(params, prompt, 8)


def test_staggered_joins_token_for_token(params):
    """Requests joining mid-flight must not perturb earlier slots."""
    rng = np.random.default_rng(11)
    prompts = [[int(t) for t in rng.integers(1, CFG.vocab_size, size=n)]
               for n in (5, 17, 3, 9)]
    max_news = [10, 6, 14, 8]
    engine = SlotEngine(params, CFG, num_slots=3, chunk=8)
    handles = []
    # Stagger: submit one, run a few steps, submit the next. With 3
    # slots and 4 requests the last request also exercises queueing.
    for p, m in zip(prompts, max_news):
        handles.append(engine.submit(p, max_new=m))
        for _ in range(3):
            engine.step()
    drain(engine, handles)
    for p, m, h in zip(prompts, max_news, handles):
        assert h.result(timeout=0).tokens == reference_tokens(params, p, m), \
            f"prompt len {len(p)} diverged under slot batching"


def test_decode_block_matches_generate(params):
    """K-step decode blocks (one device dispatch per K tokens) must be
    token-for-token identical to single-step decoding."""
    rng = np.random.default_rng(19)
    prompts = [[int(t) for t in rng.integers(1, CFG.vocab_size, size=n)]
               for n in (6, 13, 4)]
    engine = SlotEngine(params, CFG, num_slots=2, chunk=8, decode_block=4)
    handles = []
    for p in prompts:
        handles.append(engine.submit(p, max_new=10))
        engine.step()
    drain(engine, handles)
    for p, h in zip(prompts, handles):
        assert h.result(timeout=0).tokens == reference_tokens(params, p, 10)


def test_decode_block_eos_overshoot_discarded(params):
    prompt = [3, 141, 59, 26, 5]
    ref = reference_tokens(params, prompt, 12)
    eos = ref[4]
    first = ref.index(eos)
    engine = SlotEngine(params, CFG, num_slots=2, chunk=8, decode_block=8)
    h = engine.submit(prompt, max_new=12, eos_id=eos)
    drain(engine, [h])
    res = h.result(timeout=0)
    assert res.finish_reason == "stop"
    assert res.tokens == ref[:first + 1]


def test_slots_recycle_many_requests(params):
    engine = SlotEngine(params, CFG, num_slots=2, chunk=8)
    rng = np.random.default_rng(3)
    handles = [engine.submit(
        [int(t) for t in rng.integers(1, CFG.vocab_size, size=4)],
        max_new=5) for _ in range(7)]
    drain(engine, handles)
    for h in handles:
        assert len(h.result(timeout=0).tokens) == 5
    assert engine.requests_completed == 7
    assert engine.tokens_generated == 35


def test_eos_stops_early(params):
    prompt = [3, 141, 59, 26, 5]
    ref = reference_tokens(params, prompt, 12)
    eos = ref[4]  # a token the model provably emits
    first = ref.index(eos)  # generation stops at its FIRST occurrence
    engine = SlotEngine(params, CFG, num_slots=2, chunk=8)
    h = engine.submit(prompt, max_new=12, eos_id=eos)
    drain(engine, [h])
    res = h.result(timeout=0)
    assert res.finish_reason == "stop"
    assert res.tokens == ref[:first + 1]  # includes the eos token


def test_threaded_engine_with_streaming_iter(params):
    engine = SlotEngine(params, CFG, num_slots=4, chunk=8).start()
    try:
        prompt = [9, 2, 77, 31]
        ref = reference_tokens(params, prompt, 9)
        streamed = []
        h = engine.submit(prompt, max_new=9)
        for tok in h:  # blocks as tokens arrive from the engine thread
            streamed.append(tok)
        assert streamed == ref
        # concurrent submissions from several threads
        results = {}

        def worker(seed):
            rng = np.random.default_rng(seed)
            p = [int(t) for t in rng.integers(1, CFG.vocab_size, size=6)]
            results[seed] = (p, engine.submit(p, max_new=7).result(60))

        threads = [threading.Thread(target=worker, args=(s,))
                   for s in range(5)]
        for t in threads:
            t.start()
        for t in threads:
            t.join()
        for seed, (p, res) in results.items():
            assert res.tokens == reference_tokens(params, p, 7)
    finally:
        engine.stop()


def test_submit_validation(params):
    engine = SlotEngine(params, CFG, num_slots=2, chunk=8)
    with pytest.raises(ValueError):
        engine.submit([], max_new=4)
    with pytest.raises(ValueError):
        engine.submit(list(range(1, 100)),
                      max_new=CFG.max_seq)  # prompt+new > max_seq


# -- the prefill lane's width --------------------------------------------------

V5E = {"hbm_gbps": 819.0, "bf16_tflops": 197.0}   # DEVICE_PEAKS' one row


@pytest.mark.parametrize("one_program,peaks,dtype,max_seq,want", [
    # a family that carries the lane on every step: 64 on any device
    pytest.param(True, V5E, jnp.bfloat16, 2048, 64, id="one-program-v5e"),
    pytest.param(True, None, jnp.bfloat16, 2048, 64, id="one-program-cpu"),
    # two programs on a v5e: the ridge, 240.5 rows, to a power of two
    pytest.param(False, V5E, jnp.bfloat16, 2048, 256, id="ridge-v5e"),
    # four-byte weights take twice as long to read: twice the rows
    pytest.param(False, V5E, jnp.float32, 2048, 512, id="ridge-v5e-f32"),
    # no published peaks, no ridge
    pytest.param(False, None, jnp.bfloat16, 2048, 64, id="no-peaks"),
    # held to a divisor of max_seq, and to max_seq itself
    pytest.param(False, V5E, jnp.bfloat16, 128, 128, id="short-max-seq"),
    pytest.param(False, V5E, jnp.bfloat16, 96, 32, id="odd-max-seq"),
    pytest.param(False, None, jnp.float32, 32, 32, id="no-peaks-short"),
])
def test_prefill_lane_from_record_peaks_and_config(one_program, peaks,
                                                   dtype, max_seq, want):
    assert prefill_lane(one_program, peaks, dtype, max_seq) == want


@pytest.mark.parametrize("family,known,chunk,want", [
    # this CPU has no peaks: today's 64 (llama-tiny's max_seq is 128)
    pytest.param("llama", False, None, 64, id="llama-cpu"),
    pytest.param("lfm2", False, None, 64, id="lfm2-cpu"),
    # the same device with a v5e's peaks: llama-tiny is float32, so the
    # ridge is 512 rows, held to max_seq; lfm2 stays, by its record
    pytest.param("llama", True, None, 128, id="llama-peaks"),
    pytest.param("lfm2", True, None, 64, id="lfm2-peaks"),
    # a caller's number is the lane, whatever the device
    pytest.param("llama", True, 16, 16, id="llama-explicit"),
    pytest.param("lfm2", False, 32, 32, id="lfm2-explicit"),
])
def test_engine_resolves_its_lane_once(params, monkeypatch, family, known,
                                       chunk, want):
    """``chunk=None`` becomes a width in ``__init__`` from the family's
    record (``one_program``), ``DEVICE_PEAKS`` and ``cfg``; an explicit
    ``chunk`` passes through."""
    if known:
        monkeypatch.setitem(DEVICE_PEAKS, jax.devices()[0].device_kind, V5E)
    if family == "lfm2":
        cfg = lfm2.CONFIGS["lfm2-tiny"]
        params = lfm2.init_params(jax.random.PRNGKey(0), cfg)[0]
    else:
        cfg = CFG
    eng = SlotEngine(params, cfg, num_slots=2, chunk=chunk, page_size=8)
    assert eng.chunk == want
    assert serving.model_for(cfg).one_program == (family == "lfm2")


LONG = dataclasses.replace(CFG, max_seq=512)
# shorter than every lane, equal to each, and spanning the widest
LANE_PROMPTS = (9, 16, 64, 256, 300)


@pytest.fixture(scope="module")
def lane_reference(params):
    rng = np.random.default_rng(23)
    prompts = [[int(t) for t in rng.integers(1, CFG.vocab_size, size=n)]
               for n in LANE_PROMPTS]
    want = [[int(t) for t in np.asarray(llama.generate(
        params, np.asarray([p], dtype=np.int32), LONG, max_new=6))[0, n:]]
        for p, n in zip(prompts, LANE_PROMPTS)]
    return prompts, want


@pytest.mark.parametrize("lane", [16, 64, 256])
def test_greedy_tokens_do_not_depend_on_the_lane(params, lane_reference,
                                                 lane):
    """One engine run at each lane width: prompts shorter than, equal to
    and spanning the lane, each admitted while earlier requests decode
    beside it, give the single-request path's tokens."""
    prompts, want = lane_reference
    engine = SlotEngine(params, LONG, num_slots=3, chunk=lane)
    handles = []
    for p in prompts:
        handles.append(engine.submit(p, max_new=6))
        for _ in range(2):
            engine.step()
    drain(engine, handles)
    assert [h.result(timeout=0).tokens for h in handles] == want
    assert 0 < engine.prefill_lane_fill <= 1
    assert engine.prefill_tokens == sum(LANE_PROMPTS)
