"""Paged KV cache + radix prefix reuse (ISSUE 15): token parity of the
paged engine vs the dense reference, bit-for-bit prefix-hit
outputs (greedy AND seeded sampling), COW fork isolation, page
accounting (no leaks, reserved scratch page), LRU eviction under pool
pressure, and bounded-admission shedding."""

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from ray_tpu.llm.engine import SlotEngine
from ray_tpu.llm.paged import OverloadedError, PagePool, RadixIndex
from ray_tpu.models import llama
from ray_tpu.models.step import PAGED_KV_AXES, init_pool

CFG = llama.CONFIGS["llama-tiny"]
PS = 8  # page size under test: 16 pages per 128-token sequence


@pytest.fixture(scope="module")
def params():
    p, _ = llama.init_params(jax.random.PRNGKey(0), CFG)
    return p


@pytest.fixture(scope="module")
def engine(params):
    """Shared prefix-caching engine (compiles once for the module)."""
    return SlotEngine(params, CFG, num_slots=3, chunk=8, page_size=PS)


def reference_tokens(params, prompt, max_new):
    out = llama.generate(params, np.asarray([prompt], dtype=np.int32),
                         CFG, max_new=max_new)
    return [int(t) for t in np.asarray(out)[0, len(prompt):]]


def drain(engine, handles, max_steps=800):
    for _ in range(max_steps):
        if all(h._done.is_set() for h in handles):
            return
        engine.step()
    raise AssertionError("engine did not finish in max_steps")


def run_one(engine, prompt, max_new=8, **kw):
    h = engine.submit(prompt, max_new=max_new, **kw)
    drain(engine, [h])
    return h.result(timeout=0).tokens


# -- pool / radix units -------------------------------------------------------

def test_page_pool_refcounts_and_lru():
    pool = PagePool(6)  # scratch + 5
    assert pool.free_count == 5 and pool.used_count == 1
    a, b = pool.alloc(), pool.alloc()
    assert 0 not in (a, b), "scratch page must never be allocated"
    pool.ref(a)
    assert not pool.unref(a)  # still borrowed
    assert pool.unref(a) and pool.free_count == 4
    assert pool.unref(b) and pool.free_count == 5
    # LRU: freed pages re-issue oldest-first, after the untouched tail.
    order = [pool.alloc() for _ in range(5)]
    assert order[-2:] == [a, b]
    assert pool.used_count + pool.free_count == pool.num_pages


def test_radix_match_insert_evict():
    pool = PagePool(8)
    idx = RadixIndex(pool, 4)
    prompt = list(range(1, 11))  # 10 tokens -> 2 full pages of 4
    pages = [pool.alloc(), pool.alloc()]
    assert idx.insert(prompt, pages) == 2
    full, partial = idx.match(prompt)
    assert full == pages and partial is None  # 2 tokens left < 1 chunk
    # Extending prompt: same 2 full pages match, no partial beyond.
    full, partial = idx.match(prompt + [99, 98, 97])
    assert full == pages
    # Diverging inside the second chunk: 1 full page + partial tokens.
    full, partial = idx.match(prompt[:6] + [55, 44, 33, 22])
    assert full == pages[:1]
    assert partial == (pages[1], 2)  # tokens 5,6 shared inside page 2
    # Release the inserter's refs: index alone holds the pages now.
    for p in pages:
        pool.unref(p)
    # Eviction is leaf-first: one page frees from the deepest node.
    assert idx.evict(1) == 1
    full, _ = idx.match(prompt)
    assert full == pages[:1]
    assert idx.clear() == 1
    assert pool.used_count == 1  # only scratch


# -- kernel parity: paged vs dense programs -----------------------------------

def test_paged_cache_layout_heads_minor():
    """The page pool is ONE fused array [L, 2, pages, page_size,
    Hkv * hd] — K and V stacked so one indexed read serves both, a
    token's heads side by side in the minor axis so a page's rows fill
    whole lane rows on the TPU (PERF.md, PR 25) and a gathered page
    reshapes to the seq-major attention view without a materializing
    transpose, and axis 4 carries the 'kv' logical axis for tp sharding
    (whole heads a shard)."""
    cache = init_pool(CFG.num_layers, CFG, 7, PS)
    assert set(cache) == {"kv"}
    assert cache["kv"].shape == (CFG.num_layers, 2, 7, PS,
                                 CFG.num_kv_heads * CFG.head_dim)
    # The logical-axis annotation must line up with that shape: exactly
    # one 'kv' entry, on the heads axis.
    assert PAGED_KV_AXES == (None, None, None, None, "kv")


def test_paged_sampled_parity_vs_dense_reference(engine, params):
    """Seeded sampling through the paged engine must reproduce a dense
    decode_step loop drawing from the same per-request fold_in stream —
    pins both the kernel numerics (heads-minor layout) and the sampling
    position bookkeeping (token j drawn at qpos = prompt_len + j)."""
    rng = np.random.default_rng(67)
    prompt = [int(t) for t in rng.integers(1, CFG.vocab_size, size=18)]
    temperature, seed, max_new = 0.8, 4242, 10
    # Dense reference: single-row KV cache, one decode_step per token.
    cache = llama.init_kv_cache(CFG, 1)
    logits = None
    for i, t in enumerate(prompt):
        logits, cache = llama.decode_step(
            params, cache, jnp.asarray([t], jnp.int32),
            jnp.asarray(i, jnp.int32), CFG)
    ref = []
    for j in range(max_new):
        key = jax.random.fold_in(jax.random.PRNGKey(seed),
                                 len(prompt) + j)
        tok = int(jax.random.categorical(key, logits[0] / temperature))
        ref.append(tok)
        logits, cache = llama.decode_step(
            params, cache, jnp.asarray([tok], jnp.int32),
            jnp.asarray(len(prompt) + j, jnp.int32), CFG)
    got = run_one(engine, prompt, max_new=max_new,
                  temperature=temperature, seed=seed)
    assert got == ref


@pytest.mark.parametrize("heads", ["gqa", "mha"])
@pytest.mark.parametrize("case", ["chunk_only", "decode_only", "both"])
def test_paged_kernels_match_dense(params, case, heads):
    """The one paged step against the dense ``decode_step``, token by
    token — pages only move the bytes, never the math — for llama-tiny's
    grouped KV heads (4 query heads on 2) and for as many KV heads as
    query heads, the published SmolLM2 shape: the step keeps q / k / v on
    the flat lanes its matmuls leave and rotates them there
    (``rope_lanes``), the reference cuts heads out first (``rope``). A
    prompt goes into slot 1's scattered pages through the chunk lane
    (decode rows parked: what the engine sends when nothing decodes) or a
    token a step through its decode row; "both" has slot 0 decoding
    another sequence beside the chunks. Then a few greedy decode steps on
    top."""
    cfg = CFG
    if heads == "mha":
        cfg = dataclasses.replace(CFG, num_kv_heads=CFG.num_heads)
        params, _ = llama.init_params(jax.random.PRNGKey(0), cfg)
    rng = np.random.default_rng(23)
    prompt = rng.integers(1, CFG.vocab_size, size=13).astype(np.int32)
    other = rng.integers(1, CFG.vocab_size, size=6).astype(np.int32)
    nrows, pps, c = 2, CFG.max_seq // PS, 8
    tables = np.zeros((nrows, pps), dtype=np.int32)
    tables[0] = np.arange(pps + 1, 2 * pps + 1)
    tables[1] = np.arange(1, pps + 1)[::-1]  # an arbitrary scattered set
    tables = jnp.asarray(tables)
    step = jax.jit(lambda cache, toks, pos, chunk: llama.paged_step(
        params, cache, tables, toks, pos, chunk, cfg, PS))
    dense_step = jax.jit(lambda cache, tok, i: llama.decode_step(
        params, cache, tok[None], i, cfg))

    def dense(tokens):
        """Row 0's logits after each token, and the cache."""
        cache, out = llama.init_kv_cache(cfg, 1), []
        for i, t in enumerate(tokens):
            lg, cache = dense_step(cache, jnp.asarray(t, jnp.int32),
                                   jnp.asarray(i, jnp.int32))
            out.append(np.asarray(lg[0]))
        return out, cache

    def close(got, want):
        np.testing.assert_allclose(np.asarray(got), want, rtol=1e-5,
                                   atol=1e-5)

    want_prompt, dense_cache = dense(prompt)
    want_other, _ = dense(other)
    paged = init_pool(cfg.num_layers, cfg, nrows * pps + 1, PS)
    toks = np.zeros((nrows,), np.int32)
    pos = np.full((nrows,), CFG.max_seq, np.int32)  # every row parked
    if case == "decode_only":
        for i, t in enumerate(prompt):
            toks[1], pos[1] = t, i
            lg, none, paged = step(paged, jnp.asarray(toks),
                                   jnp.asarray(pos), None)
            assert none is None
            close(lg[1], want_prompt[i])
        last = lg[1]
    else:
        for n, p0 in enumerate(range(0, len(prompt), c)):
            piece = prompt[p0:p0 + c]
            buf = np.zeros((c,), np.int32)  # tail-padded
            buf[:len(piece)] = piece
            if case == "both":
                toks[0], pos[0] = other[n], n
            lg, last, paged = step(
                paged, jnp.asarray(toks), jnp.asarray(pos),
                (jnp.asarray(buf), jnp.asarray(1, jnp.int32),
                 jnp.asarray(p0, jnp.int32),
                 jnp.asarray(len(piece), jnp.int32)))
            close(last, want_prompt[p0 + len(piece) - 1])
            if case == "both":
                close(lg[0], want_other[n])
    toks[0], pos[0] = 0, CFG.max_seq
    tok = int(jnp.argmax(last))
    for j in range(4):
        toks[1], pos[1] = tok, len(prompt) + j
        want, dense_cache = dense_step(
            dense_cache, jnp.asarray(tok, jnp.int32),
            jnp.asarray(len(prompt) + j, jnp.int32))
        lg, _, paged = step(paged, jnp.asarray(toks), jnp.asarray(pos),
                            None)
        close(lg[1], np.asarray(want[0]))
        assert int(jnp.argmax(lg[1])) == int(jnp.argmax(want[0]))
        tok = int(jnp.argmax(lg[1]))


# -- engine: prefix hit parity ------------------------------------------------

def test_prefix_hit_greedy_bit_for_bit(engine, params):
    rng = np.random.default_rng(31)
    prompt = [int(t) for t in rng.integers(1, CFG.vocab_size, size=21)]
    ref = reference_tokens(params, prompt, 10)
    cold = run_one(engine, prompt, max_new=10)
    assert cold == ref
    hits0, saved0 = engine.prefix_hits, engine.prefix_tokens_saved
    warm = run_one(engine, prompt, max_new=10)
    assert warm == ref, "prefix-hit output diverged from cold output"
    assert engine.prefix_hits == hits0 + 1
    # 21 tokens: the 2 fully-covered pages (16 tokens) are indexed and
    # shared; the 5-token tail was never indexed (only full pages are),
    # so it re-prefills.
    assert engine.prefix_tokens_saved - saved0 == 16


def test_multi_turn_session_extends_prefix(engine, params):
    """Turn 2's prompt = turn-1 prompt + turn-1 output + new tokens:
    the radix must hand back the whole shared history."""
    rng = np.random.default_rng(37)
    turn1 = [int(t) for t in rng.integers(1, CFG.vocab_size, size=16)]
    out1 = run_one(engine, turn1, max_new=8)
    assert out1 == reference_tokens(params, turn1, 8)
    turn2 = turn1 + out1 + [int(t) for t in
                            rng.integers(1, CFG.vocab_size, size=5)]
    saved0 = engine.prefix_tokens_saved
    out2 = run_one(engine, turn2, max_new=8)
    assert out2 == reference_tokens(params, turn2, 8)
    # turn-1's 16 prompt tokens are 2 indexed pages; the rest of turn 2
    # (turn-1's output) was freshly prefilled at turn 1's *generation*
    # time into decode pages, which are never indexed — so >= 16 saved.
    assert engine.prefix_tokens_saved - saved0 >= 16


@pytest.mark.parametrize("lane", [8, 64])
def test_prefix_hit_sampled_bit_for_bit(params, lane):
    """Seeded sampling: a prefix-hit request must reproduce the cold
    request's tokens exactly — per-request fold_in streams make the
    draw independent of how much prefill the hit skipped, in a lane
    narrower than the prompt (the hit skips whole chunks) and in one
    wider (the hit starts the one chunk inside a page)."""
    cold_eng = SlotEngine(params, CFG, num_slots=2, chunk=lane,
                          page_size=PS, prefix_cache=False)
    warm_eng = SlotEngine(params, CFG, num_slots=2, chunk=lane,
                          page_size=PS)
    rng = np.random.default_rng(41)
    prompt = [int(t) for t in rng.integers(1, CFG.vocab_size, size=19)]
    cold = run_one(cold_eng, prompt, max_new=12, temperature=0.8,
                   seed=1234)
    assert run_one(cold_eng, prompt, max_new=12, temperature=0.8,
                   seed=1234) == cold, "sampling is not deterministic"
    # Warm engine: first run populates the radix, second hits it.
    assert run_one(warm_eng, prompt, max_new=12, temperature=0.8,
                   seed=1234) == cold
    hits0 = warm_eng.prefix_hits
    assert run_one(warm_eng, prompt, max_new=12, temperature=0.8,
                   seed=1234) == cold
    assert warm_eng.prefix_hits == hits0 + 1


def test_cow_fork_divergence(engine, params):
    """Two sessions fork from a shared prefix mid-page and diverge; COW
    must keep every page intact: both match their cold references, and
    the original prompt still replays clean afterwards."""
    rng = np.random.default_rng(43)
    shared = [int(t) for t in rng.integers(1, CFG.vocab_size, size=12)]
    a = shared + [int(t) for t in rng.integers(1, CFG.vocab_size, size=6)]
    b = shared + [int(t) for t in rng.integers(1, CFG.vocab_size, size=7)]
    ref_a = reference_tokens(params, a, 8)
    ref_b = reference_tokens(params, b, 8)
    assert run_one(engine, a, max_new=8) == ref_a  # seeds the radix
    saved0 = engine.prefix_tokens_saved
    # Concurrent fork: both match `a`'s first page + 4 COW tokens.
    ha = engine.submit(a, max_new=8)
    hb = engine.submit(b, max_new=8)
    drain(engine, [ha, hb])
    assert ha.result(timeout=0).tokens == ref_a
    assert hb.result(timeout=0).tokens == ref_b
    assert engine.prefix_tokens_saved > saved0
    # The shared pages survived both writers: replay is still clean.
    assert run_one(engine, a, max_new=8) == ref_a


# -- accounting / eviction ----------------------------------------------------

def test_page_accounting_drains_clean(params):
    eng = SlotEngine(params, CFG, num_slots=2, chunk=8, page_size=PS)
    assert eng.pages_total == 2 * (CFG.max_seq // PS) + 1, \
        "pool must cost one scratch PAGE, not a scratch slot-row"
    rng = np.random.default_rng(47)
    handles = [eng.submit(
        [int(t) for t in rng.integers(1, CFG.vocab_size, size=n)],
        max_new=4) for n in (5, 11, 9, 17, 6)]
    drain(eng, handles)
    for h in handles:
        assert len(h.result(timeout=0).tokens) == 4
    # Invariant at rest: every page is either on the free list, held by
    # the radix index, or the scratch page.
    assert eng.pages_used + eng.pages_free == eng.pages_total
    assert np.all(eng._tables == 0), "drained slots must unmap pages"
    held = eng.pages_used - 1  # minus scratch
    assert held == eng.prefix_cache_len(), \
        "resident pages at rest must all be radix-held"
    freed = eng.clear_prefix_cache()
    assert freed == held
    assert eng.pages_used == 1, "only the scratch page may remain"
    # Scratch is reserved: never allocated, never refcounted.
    assert eng._pool.refcount(0) == 0


def test_whole_pool_request_with_partial_hit_admits(params):
    """A request whose worst-case footprint needs every allocatable
    page, arriving with a PARTIAL radix match, must still admit: the
    partial borrow pins its source page without reducing the fresh-page
    need, so admission has to drop the borrow (not livelock retrying
    forever with the pin in place)."""
    eng = SlotEngine(params, CFG, num_slots=1, chunk=8, page_size=PS,
                     num_pages=5)  # scratch + 4 allocatable
    rng = np.random.default_rng(61)
    base = [int(t) for t in rng.integers(1, CFG.vocab_size, size=16)]
    assert len(run_one(eng, base, max_new=4)) == 4  # seeds the radix
    # Shares 10 leading tokens -> 1 full page + a partial; needs
    # ceil((20+12)/8) = 4 pages == the whole allocatable pool.
    fork = base[:10] + [int(t) for t in
                        rng.integers(1, CFG.vocab_size, size=10)]
    tokens = run_one(eng, fork, max_new=12)
    assert tokens == reference_tokens(params, fork, 12)
    assert eng.pages_used + eng.pages_free == eng.pages_total


def test_lru_eviction_under_pool_pressure(params):
    """A pool with zero headroom forces radix eviction at admission:
    distinct prompts keep rotating through, correctness holds, and the
    pool never leaks."""
    eng = SlotEngine(params, CFG, num_slots=2, chunk=8, page_size=PS)
    rng = np.random.default_rng(53)
    for i in range(6):
        # 100-token prompts: 13 pages each; two in flight exhaust the
        # 32-page pool, so admission must evict earlier radix entries.
        prompt = [int(t) for t in
                  rng.integers(1, CFG.vocab_size, size=100)]
        assert run_one(eng, prompt, max_new=4) == \
            reference_tokens(params, prompt, 4), f"round {i} diverged"
        assert eng.pages_used + eng.pages_free == eng.pages_total
    assert eng.pages_free >= 0


# -- bounded admission --------------------------------------------------------

def test_bounded_pending_sheds_with_typed_error(params):
    eng = SlotEngine(params, CFG, num_slots=1, chunk=8, page_size=PS,
                     max_pending=2)
    eng.warmup()
    prompt = [3, 141, 59, 26, 5]
    keep = [eng.submit(prompt, max_new=4) for _ in range(2)]
    eng.step()  # admits the first into the slot; queue holds one
    keep.append(eng.submit(prompt, max_new=4))  # queue back at the cap
    with pytest.raises(OverloadedError):
        eng.submit(prompt, max_new=4)
    assert eng.requests_shed == 1
    drain(eng, keep)
    for h in keep:
        assert len(h.result(timeout=0).tokens) == 4


def test_queue_timeout_expires_pending_only(params):
    import time

    eng = SlotEngine(params, CFG, num_slots=1, chunk=8, page_size=PS,
                     queue_timeout_s=0.2)
    eng.warmup()
    prompt = [9, 2, 77, 31]
    resident = eng.submit(prompt, max_new=4)
    eng.step()  # admits `resident` into the slot before `late` arrives
    late = eng.submit(prompt, max_new=4)
    time.sleep(0.3)  # `late` (still queued — slot busy) expires
    drain(eng, [resident, late])
    assert len(resident.result(timeout=0).tokens) == 4, \
        "resident session must ride out the shed"
    with pytest.raises(OverloadedError):
        late.result(timeout=0)
