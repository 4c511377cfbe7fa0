"""The paged-attention kernel against the gather-and-einsum reference.

On the CPU the Pallas kernel runs interpreted: the same code the TPU
compiles (tests/test_tpu_compile.py holds it to that), DMAs, semaphores
and all. Every case builds a pool of random pages, scattered page tables
and ragged rows, and asks three things: the live queries' outputs match
the reference, the pages the rows own hold exactly what the reference's
scatter put there, and the scratch page 0 — where the reference routes
what is invalid — is left alone.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from ray_tpu.llm.engine import SlotEngine
from ray_tpu.models import llama
from ray_tpu.models.step import init_pool
from ray_tpu.ops import paged_attention as PA

# name: (H, Hkv, hd, page_size, dtype) — llama-tiny's own widths, then MHA
# and GQA at head_dim 64 and 128 with groups 1, 4 and 8.
GEOMETRIES = {
    "llama-tiny": (4, 2, 16, 8, jnp.float32),
    "mha-hd64": (4, 4, 64, 16, jnp.bfloat16),
    "gqa4-hd64": (16, 4, 64, 16, jnp.bfloat16),
    "gqa8-hd128": (8, 1, 128, 16, jnp.bfloat16),
    "mha-hd128": (2, 2, 128, 16, jnp.float32),
}
# a tp shard's leftovers: fewer than 128 lanes of KV a token
NARROW = {"gqa8-one-head-of-64": (8, 1, 64, 16, jnp.bfloat16),
          "two-heads-of-32": (4, 2, 32, 16, jnp.bfloat16)}
PAGES_PER_ROW = 8


def _problem(name, rows, t, seed, pages_per_row=PAGES_PER_ROW):
    h, hkv, hd, ps, dtype = GEOMETRIES.get(name) or NARROW[name]
    rng = np.random.default_rng(seed)
    f, n_pages = hkv * hd, rows * pages_per_row + 1
    pool = jnp.asarray(rng.standard_normal((2, 2, n_pages, ps, f)), dtype)
    q = jnp.asarray(rng.standard_normal((rows, t, h, hd)), dtype)
    k_new, v_new = (jnp.asarray(rng.standard_normal((rows, t, f)), dtype)
                    for _ in range(2))
    # every row its own scattered pages; page 0 is nobody's
    tables = rng.permutation(np.arange(1, n_pages)).reshape(
        rows, pages_per_row).astype(np.int32)
    return q, k_new, v_new, pool, jnp.asarray(tables), ps, dtype


def _run_both(q, k_new, v_new, pool, tables, q_start, lengths):
    args = (q, k_new, v_new, pool, jnp.asarray(1, jnp.int32),
            PA.row_meta(tables, jnp.asarray(q_start, jnp.int32),
                        jnp.asarray(lengths, jnp.int32)))
    got = jax.jit(lambda *a: PA.paged_attention(*a, interpret=True))(*args)
    want = PA.paged_attention_reference(*args)
    return got, want


def _check(got, want, pool, q_start, lengths, dtype):
    (o, new_pool), (o_ref, ref_pool) = got, want
    t = o.shape[1]
    live = np.arange(t)[None, :] < (np.asarray(lengths)
                                   - np.asarray(q_start))[:, None]
    tol = 2e-2 if dtype == jnp.bfloat16 else 2e-5
    o, o_ref = np.asarray(o, np.float32), np.asarray(o_ref, np.float32)
    assert np.isfinite(o).all()
    np.testing.assert_allclose(o[live], o_ref[live], rtol=tol, atol=tol)
    # a row with nothing live reads nothing and returns zeros
    assert not o[np.asarray(lengths) == 0].any()
    # the owned pages: exactly the reference's scatter, every layer
    np.testing.assert_array_equal(np.asarray(new_pool[:, :, 1:], np.float32),
                                  np.asarray(ref_pool[:, :, 1:], np.float32))
    # the scratch page: the kernel writes what is invalid nowhere
    np.testing.assert_array_equal(np.asarray(new_pool[:, :, 0], np.float32),
                                  np.asarray(pool[:, :, 0], np.float32))
    # only the asked layer changed
    np.testing.assert_array_equal(np.asarray(new_pool[0], np.float32),
                                  np.asarray(pool[0], np.float32))


@pytest.mark.parametrize("name", list(GEOMETRIES))
def test_decode_rows_match_the_reference(name):
    """q_len 1, one row each of: a first token (length 1), a length on a
    page boundary and one past it, a ragged middle, the last position of
    the sequence, an empty slot and a parked row (position max_seq)."""
    q, k_new, v_new, pool, tables, ps, dtype = _problem(name, 7, 1, seed=3)
    max_seq = PAGES_PER_ROW * ps
    lengths = [1, ps, ps + 1, 3 * ps - 5, max_seq, 0, 0]
    q_start = [0, ps - 1, ps, 3 * ps - 6, max_seq - 1, 5, max_seq]
    got, want = _run_both(q, k_new, v_new, pool, tables, q_start, lengths)
    _check(got, want, pool, q_start, lengths, dtype)


# The lane a TPU v5e's ridge gives (llm/engine.py prefill_lane), at the
# benchmark's head_dim and page size: 256 query tokens from inside a
# page, so their K/V land in a window of 17 pages.
RIDGE_LANE = 256


@pytest.mark.parametrize("name,c", [
    *[pytest.param(n, None, id=n) for n in GEOMETRIES],
    pytest.param("mha-hd64", RIDGE_LANE, id="mha-hd64-ridge-lane")])
def test_a_prompt_chunk_matches_the_reference(name, c):
    """q_len C for the prefill lane's one slot: the chunk starts inside a
    page and straddles the next ones, the in-chunk causal mask holds, and
    its tail past n_valid is written nowhere."""
    _, _, _, ps, _ = GEOMETRIES[name]
    c = c or 2 * ps + 8
    q, k_new, v_new, pool, tables, ps, dtype = _problem(
        name, 1, c, seed=5, pages_per_row=max(PAGES_PER_ROW, c // ps + 3))
    p0, n_valid = ps + 3, c - 5
    got, want = _run_both(q, k_new, v_new, pool, tables, [p0],
                          [p0 + n_valid])
    _check(got, want, pool, [p0], [p0 + n_valid], dtype)


def test_a_whole_chunk_from_position_zero():
    q, k_new, v_new, pool, tables, ps, dtype = _problem(
        "llama-tiny", 1, 16, seed=7)
    got, want = _run_both(q, k_new, v_new, pool, tables, [0], [16])
    _check(got, want, pool, [0], [16], dtype)


@pytest.mark.parametrize("name", list(NARROW))
def test_a_pool_row_narrower_than_a_lane_row_is_padded(name):
    """A tp shard left with 64 lanes of KV: compiled, the kernel runs on
    the layer's slice padded to 128 lanes with heads of zeros. The same
    adapter, interpreted: the reference's outputs and pool."""
    q, k_new, v_new, pool, tables, ps, dtype = _problem(name, 4, 1, seed=9)
    q_start, lengths = [0, ps, 3 * ps + 2, 40], [1, ps + 1, 3 * ps + 3, 0]
    rows = PA.row_meta(tables, jnp.asarray(q_start, jnp.int32),
                       jnp.asarray(lengths, jnp.int32))
    layer = jnp.asarray(1, jnp.int32)
    got = jax.jit(lambda *a: PA._padded_to_lanes(*a, interpret=True))(
        q, k_new, v_new, pool, layer, rows)
    want = PA.paged_attention_reference(q, k_new, v_new, pool, layer, rows)
    _check(got, want, pool, q_start, lengths, dtype)


@pytest.mark.parametrize("why,shape,interpret", [
    ("head_dim does not divide the 128-lane chunk", (4, 4, 48, 16), True),
    ("page_size under the dtype's sublane tile", (4, 4, 64, 8), True),
    ("compiled, a pool row that does not divide 128 lanes", (2, 2, 48, 16),
     False),
])
def test_a_shape_the_kernel_cannot_tile_raises(why, shape, interpret):
    """An error, on the TPU too: never a quiet run of the reference."""
    h, hkv, hd, ps = shape
    q = jnp.zeros((2, 1, h, hd), jnp.bfloat16)
    pool = jnp.zeros((1, 2, 5, ps, hkv * hd), jnp.bfloat16)
    new = jnp.zeros((2, 1, hkv * hd), jnp.bfloat16)
    with pytest.raises(ValueError, match="cannot tile"):
        PA.paged_attention(q, new, new, pool, jnp.asarray(0),
                           jnp.zeros((2, 6), jnp.int32), interpret=interpret)


# -- whole programs: the kernel forced on, interpreted ------------------------

TINY_BF16 = llama.LlamaConfig(vocab_size=512, max_seq=128, num_layers=2,
                              num_heads=4, num_kv_heads=2, d_model=256,
                              d_mlp=344, dtype=jnp.bfloat16, remat=False)
PROGRAM_CONFIGS = {"llama-tiny": (llama.CONFIGS["llama-tiny"], 8, 1e-4),
                   "tiny-bf16": (TINY_BF16, 16, 6e-2)}


def _greedy_run(cfg, ps, params, prompt, chunk, steps):
    """One slot of three prefilled through the step's chunk lane a chunk
    at a time while another decodes beside it, then steps with no chunk;
    every step's logits, greedy-chained."""
    b, pps = 3, cfg.max_seq // ps
    cache = init_pool(cfg.num_layers, cfg, b * pps + 1, ps)
    tables = jnp.asarray(
        np.arange(1, b * pps + 1)[::-1].reshape(b, pps).astype(np.int32))
    step = jax.jit(lambda cache, toks, pos, chunk: llama.paged_step(
        params, cache, tables, toks, pos, chunk, cfg, ps))
    out = []
    pos = np.full((b,), cfg.max_seq, np.int32)   # all parked
    toks = np.zeros((b,), np.int32)
    pos[0], toks[0] = 0, 7                       # row 0 decodes from scratch
    for p0 in range(0, len(prompt), chunk):
        piece = prompt[p0:p0 + chunk]
        buf = np.zeros((chunk,), np.int32)
        buf[:len(piece)] = piece
        dec, pre, cache = step(
            cache, jnp.asarray(toks), jnp.asarray(pos),
            (jnp.asarray(buf), jnp.asarray(2, jnp.int32),
             jnp.asarray(p0, jnp.int32), jnp.asarray(len(piece), jnp.int32)))
        out += [np.asarray(dec[0]), np.asarray(pre)]
        toks[0] = int(jnp.argmax(dec[0]))
        pos[0] += 1
    pos[2], toks[2] = len(prompt), int(jnp.argmax(pre))
    for _ in range(steps):
        logits, _, cache = step(cache, jnp.asarray(toks), jnp.asarray(pos),
                                None)
        live = np.asarray(logits)[[0, 2]]
        out.append(live)
        toks[[0, 2]] = live.argmax(-1)
        pos[[0, 2]] += 1
    return out


@pytest.mark.parametrize("name", list(PROGRAM_CONFIGS))
def test_programs_with_the_kernel_match_the_reference_path(name,
                                                           monkeypatch):
    """``paged_step``, with a chunk and without, with the kernel in it
    gives the reference path's logits, step after step, and therefore the
    same greedy tokens."""
    cfg, ps, tol = PROGRAM_CONFIGS[name]
    params, _ = llama.init_params(jax.random.PRNGKey(0), cfg)
    params = jax.tree.map(lambda x: x.astype(cfg.dtype), params)
    prompt = list(np.random.default_rng(11).integers(1, 500, size=2 * ps + 5))
    want = _greedy_run(cfg, ps, params, prompt, ps + 8, steps=4)
    monkeypatch.setattr(PA, "use_kernel", lambda: True)
    got = _greedy_run(cfg, ps, params, prompt, ps + 8, steps=4)
    for g, w in zip(got, want):
        np.testing.assert_allclose(g, w, rtol=tol, atol=tol)
        assert (g.argmax(-1) == w.argmax(-1)).all()


def test_engine_tokens_with_the_kernel_are_the_reference_paths(monkeypatch):
    """The whole engine — admission, the fused lane, decode blocks, a
    second request sharing the first one's prefix pages — with the kernel
    forced on: the tokens the reference path gives."""
    cfg = llama.CONFIGS["llama-tiny"]
    params, _ = llama.init_params(jax.random.PRNGKey(0), cfg)
    prompts = [list(range(1, 40)), list(range(1, 30)) + [99, 98, 97]]

    def run():
        eng = SlotEngine(params, cfg, num_slots=2, chunk=16, page_size=8,
                         decode_block=2)
        handles = [eng.submit(p, max_new=9) for p in prompts]
        handles.append(eng.submit(prompts[0], max_new=9, temperature=0.7,
                                  seed=5))
        for _ in range(4000):
            if not eng.step():
                break
        return [h.result(timeout=0).tokens for h in handles]

    want = run()
    monkeypatch.setattr(PA, "use_kernel", lambda: True)
    assert run() == want
