"""``models/step.py``: the one place that says which of a step's rows are
in the step (``models/serving.py``'s contract: a parked row, a chunk's
tail, a chunk that would pass ``max_seq``, an empty chunk), and the page
functions every family registers, on a cache tree that holds more than
pages."""

import jax.numpy as jnp
import numpy as np
import pytest

from ray_tpu.models import step

MAX_SEQ, PAGE, B, C = 32, 8, 4, 8
TABLES = jnp.arange(B * (MAX_SEQ // PAGE), dtype=jnp.int32).reshape(B, -1) + 1

# name -> (pos [B], chunk (slot, p0, n_valid) or None,
#          want: valid, n_valid, last, state rows)
ROWS = {
    "no_chunk": ([3, 0, 31, 7], None,
                 [True, True, True, True], 0, None, 4),
    "parked_row": ([3, MAX_SEQ, 31, MAX_SEQ + 5], (1, 0, 5),
                   [True, False, True, False], 5, 4, 3),
    "chunk_ends_past_max_seq": ([3, 0, 31, 7], (1, MAX_SEQ - 3, 6),
                                [True, True, True, True], 3, 5, 5),
    "chunk_starts_past_max_seq": ([3, 0, 31, 7], (1, MAX_SEQ + 2, 6),
                                  [True, True, True, True], 0, 5, 4),
    "empty_chunk": ([MAX_SEQ, 0, 31, 7], (0, 0, 0),
                    [False, True, True, True], 0, 0, 3),
    "full_chunk": ([3, 0, 31, 7], (2, 8, C),
                   [True, True, True, True], C, C - 1, 5),
}


@pytest.mark.parametrize("case", list(ROWS))
def test_step_rows_says_which_rows_are_in_the_step(case):
    pos, at, valid, n_valid, last, state_rows = ROWS[case]
    pos = jnp.asarray(pos, jnp.int32)
    tokens = jnp.arange(B, dtype=jnp.int32) + 10
    chunk = None
    if at is not None:
        slot, p0, n = (jnp.int32(x) for x in at)
        chunk = (jnp.arange(C, dtype=jnp.int32) + 50, slot, p0, n)
    rows = step.step_rows(TABLES, tokens, pos, chunk, MAX_SEQ)
    assert (rows.b, rows.c) == (B, 0 if at is None else C)
    np.testing.assert_array_equal(rows.valid, valid)
    assert int(rows.n_valid) == n_valid
    assert int(rows.state_rows()) == state_rows
    # the kernel is told the same: a parked row's pages have length 0
    decode = np.asarray(rows.decode)
    np.testing.assert_array_equal(decode[:, :-2], TABLES)
    np.testing.assert_array_equal(decode[:, -2], pos)
    np.testing.assert_array_equal(
        decode[:, -1], np.where(valid, np.asarray(pos) + 1, 0))
    if at is None:
        assert rows.chunk_at is None and rows.chunk is None
        assert rows.last is None
        np.testing.assert_array_equal(rows.live, valid)
        np.testing.assert_array_equal(rows.packed(), tokens)
        return
    np.testing.assert_array_equal(rows.live,
                                  valid + [i < n_valid for i in range(C)])
    np.testing.assert_array_equal(
        rows.packed(), list(range(10, 10 + B)) + list(range(50, 50 + C)))
    assert int(rows.last) == last
    assert int(rows.chunk_at[0]) == at[0]
    assert int(rows.chunk_at[1]) == n_valid
    in_chunk = np.asarray(rows.chunk)
    assert in_chunk.shape == (1, TABLES.shape[1] + 2)
    np.testing.assert_array_equal(in_chunk[0, :-2], TABLES[at[0]])
    assert tuple(in_chunk[0, -2:]) == (at[1], at[1] + n_valid)


def test_logits_of_takes_the_chunks_last_real_row():
    """The decode rows' logits and the chunk's row ``last``, through the
    family's own head; no chunk, no chunk logits."""
    x = jnp.arange((B + C) * 2, dtype=jnp.float32).reshape(B + C, 2)
    head = lambda x: 10 * x  # noqa: E731
    tokens, pos = jnp.zeros((B,), jnp.int32), jnp.zeros((B,), jnp.int32)
    chunk = (jnp.zeros((C,), jnp.int32), jnp.int32(1), jnp.int32(0),
             jnp.int32(3))
    rows = step.step_rows(TABLES, tokens, pos, chunk, MAX_SEQ)
    logits, last = step.logits_of(rows, x, head)
    np.testing.assert_array_equal(logits, 10 * x[:B])
    np.testing.assert_array_equal(last, 10 * x[B + 2])
    rows = step.step_rows(TABLES, tokens, pos, None, MAX_SEQ)
    logits, last = step.logits_of(rows, x[:B], head)
    np.testing.assert_array_equal(logits, 10 * x[:B])
    assert last is None


@pytest.fixture
def cache():
    """A pool of 2 layers x 6 pages beside state that is no page."""
    kv = jnp.arange(2 * 2 * 6 * PAGE * 4, dtype=jnp.float32).reshape(
        2, 2, 6, PAGE, 4)
    return {"kv": kv, "conv": [jnp.ones((3, 5))], "ssm": jnp.zeros((2, 7))}


@pytest.mark.parametrize("fn", ["copy_pages", "write_pages"])
def test_page_functions_leave_the_rest_of_the_tree_as_it_is(cache, fn):
    kv = np.asarray(cache["kv"])
    if fn == "copy_pages":
        out = step.copy_pages(cache, jnp.asarray([1, 2]), jnp.asarray([4, 5]))
        want = kv.copy()
        want[:, :, [4, 5]] = kv[:, :, [1, 2]]
    else:
        frames = step.read_pages(cache, jnp.asarray([1, 2]))
        assert frames.shape == (2, 2, 2, PAGE, 4)
        assert frames.flags["C_CONTIGUOUS"]
        step.check_frames(cache, frames)
        with pytest.raises(ValueError, match="does not match"):
            step.check_frames(cache, frames[:1])
        out = step.write_pages(cache, jnp.asarray([3, 0]), -frames)
        want = kv.copy()
        want[:, :, [3, 0]] = -kv[:, :, [1, 2]]
    assert set(out) == set(cache)
    assert out["conv"] is cache["conv"] and out["ssm"] is cache["ssm"]
    np.testing.assert_array_equal(out["kv"], want)


def test_init_pool_is_the_one_shape_and_the_one_check():
    class Cfg:
        max_seq, num_kv_heads, head_dim, dtype = MAX_SEQ, 2, 16, jnp.bfloat16

    cache = step.init_pool(3, Cfg, 9, PAGE)
    assert set(cache) == {"kv"} and cache["kv"].dtype == jnp.bfloat16
    assert cache["kv"].shape == (3, 2, 9, PAGE, 2 * 16)
    assert len(step.PAGED_KV_AXES) == cache["kv"].ndim
    with pytest.raises(ValueError, match="must divide max_seq"):
        step.init_pool(3, Cfg, 9, 5)
