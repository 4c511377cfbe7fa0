"""``ops/ssm_scan.py``: the Pallas kernel, interpreted, against the
sequential recurrence at the published sizes of a block (a state of N =
128 a channel, 128 channels = two heads of 64 a block), on the contract of
one call a layer: a step's rows as the layer computes them, decode rows
first (one token of slot i each) and then one slot's chunk, with the
step's plan. One token a row with a parked row between, a chunk that stops
at its last valid token, both in one call, no row in the step, the corners
of the decay (``a`` near 1 and near 0), a state carried over many steps,
and the plan and the DMA skeleton it shares with ``ops/delta_rule.py``:
each at 1, 2 and 4 decode rows a burst (``burst``: the kernel's own count
follows the bytes of a row's block, and a test's blocks are small), with
part-filled bursts, parked rows inside a burst and several chunk tokens a
grid step."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from ray_tpu.ops import delta_rule, slot_stream, ssm_scan as SS
from ray_tpu.ops.ssm_scan import (heads_view, recurrence, ssm_scan,
                                  ssm_scan_reference, step_plan)

G, N, W = 4, 128, 128          # 8 heads of 64
LAYERS, SLOTS = 2, 5

# The interpreted kernel and its references are traced and built once a
# shape, layer, burst and blocks (jit's own cache, the module's for its
# life): cases that differ in the plan's values share the build.
ssm_scan = jax.jit(ssm_scan, static_argnums=1,
                   static_argnames=("interpret", "blocks", "burst"))
ssm_scan_reference = jax.jit(ssm_scan_reference, static_argnums=1)
recurrence = jax.jit(recurrence)


def _inputs(seed, r, decay=None, slots=SLOTS):
    """A state and r rows' x, a [r, G, W] and bc [r, 2, N]; ``a`` is one
    number a head of 64 channels, as the model gives it."""
    ks = jax.random.split(jax.random.PRNGKey(seed), 4)
    state = jax.random.normal(ks[0], (LAYERS, slots, G, N, W), jnp.float32)
    x = jax.random.normal(ks[1], (r, G, W))
    a = jax.nn.sigmoid(jax.random.normal(ks[2], (r, 2 * G, 1)) + 2)
    a = jnp.broadcast_to(a, (r, 2 * G, W // 2)).reshape(r, G, W)
    if decay is not None:
        a = jnp.full_like(a, decay)
    bc = jax.random.normal(ks[3], (r, 2, N))
    return state, x, a, bc


def _sequential(state, x, a, bc, heads=2 * G):
    """The recurrence as the layer's equations write it, a head at a time
    in float64 on the host: S_p = a_p S_p + x_p B^T; y_p = S_p C, from a
    state [G, N, W] and rows [T, ..] -> (y [T, G, W], state [G, N, W])."""
    s = np.asarray(heads_view(jnp.asarray(state), heads), np.float64)
    p = s.shape[1]
    x = np.asarray(x, np.float64).reshape(x.shape[0], heads, p)
    a = np.asarray(a, np.float64).reshape(a.shape[0], heads, p)[:, :, 0]
    bc = np.asarray(bc, np.float64)
    out = np.zeros(x.shape)
    for t in range(x.shape[0]):
        for h in range(heads):
            s[h] = a[t, h] * s[h] + np.outer(x[t, h], bc[t, 0])
            out[t, h] = s[h] @ bc[t, 1]
    # back to the program's layout: [G, N, W] with channel = head x P + p
    back = np.moveaxis(s.reshape(state.shape[0], state.shape[2], -1), -1, -2)
    return out.reshape(x.shape[0], state.shape[0], -1), back


def _plan(valid, chunk_at=None):
    if chunk_at is not None:
        chunk_at = tuple(jnp.int32(x) for x in chunk_at)
    return step_plan(jnp.asarray(valid, bool), chunk_at)


def _both(layer, valid, chunk_at, *args, **kw):
    plan = _plan(valid, chunk_at)
    want = ssm_scan_reference(args[0], layer, plan, *args[1:])
    got = ssm_scan(args[0], layer, plan, *args[1:], interpret=True, **kw)
    return got, want


BURSTS = [1, 2, 4]


@pytest.mark.parametrize("burst", BURSTS)
def test_one_token_a_row_with_a_parked_row_between(burst):
    state, *rest = _inputs(0, 4)
    (y, s), (y_ref, s_ref) = _both(1, [1, 0, 1, 1], None, state, *rest,
                                   burst=burst)
    live = np.asarray([True, False, True, True])
    np.testing.assert_allclose(np.asarray(y)[live], np.asarray(y_ref)[live],
                               atol=2e-5)
    np.testing.assert_allclose(np.asarray(s), np.asarray(s_ref), atol=2e-6)
    # the other layer, the parked row's slot and the slot no row names
    # are bit for bit what they were
    assert (np.asarray(s[0]) == np.asarray(state[0])).all()
    assert (np.asarray(s[1, 1]) == np.asarray(state[1, 1])).all()
    assert (np.asarray(s[1, 4]) == np.asarray(state[1, 4])).all()
    # and the equations themselves, head by head, for one row
    want_y, want_s = _sequential(state[1, 2], *(v[2:3] for v in rest))
    np.testing.assert_allclose(np.asarray(y[2:3]), want_y, atol=5e-5)
    np.testing.assert_allclose(np.asarray(s[1, 2]), want_s, atol=1e-5)


@pytest.mark.parametrize("n_valid", [16, 9, 1])
def test_a_chunk_of_one_slot_stops_at_its_last_valid_token(n_valid):
    state, *rest = _inputs(1, 16)
    (y, s), (y_ref, s_ref) = _both(0, [], (3, n_valid), state, *rest)
    np.testing.assert_allclose(np.asarray(y)[:n_valid],
                               np.asarray(y_ref)[:n_valid], atol=5e-5)
    np.testing.assert_allclose(np.asarray(s), np.asarray(s_ref), atol=1e-5)
    want_y, want_s = _sequential(state[0, 3], *(v[:n_valid] for v in rest))
    np.testing.assert_allclose(np.asarray(y)[:n_valid], want_y, atol=2e-4)
    np.testing.assert_allclose(np.asarray(s[0, 3]), want_s, atol=5e-5)
    touched = np.zeros((LAYERS, SLOTS), bool)
    touched[0, 3] = True
    assert (np.asarray(s)[~touched] == np.asarray(state)[~touched]).all()


@pytest.mark.parametrize("burst", BURSTS)
@pytest.mark.parametrize("n_valid", [0, 1, 15, 16])
@pytest.mark.parametrize("slot", [4, 1])
def test_decode_rows_and_a_chunk_in_one_call(slot, n_valid, burst):
    """Four decode rows, row 1 parked, and a 16-token chunk of a slot
    that is no decode row's (4) or the parked row's (1), against the
    recurrence itself run on each sequence alone: three active rows are a
    part-filled last burst of 2 and fewer rows than a burst of 4, and 16
    tokens over 4, 2 and 1 grid steps are 4, 8 and 16 a step."""
    valid = [True, False, True, True]
    state, *rest = _inputs(5, 4 + 16)
    plan = _plan(valid, (slot, n_valid))
    y, s = ssm_scan(state, 1, plan, *rest, interpret=True, burst=burst)
    want = np.array(state)
    y_d, s_d = recurrence(state[1, :4], jnp.asarray(valid, jnp.int32),
                          *(v[:4, None] for v in rest))
    want[1, :4] = np.asarray(s_d)
    y_c, s_c = recurrence(state[1, slot][None],
                          jnp.asarray([n_valid], jnp.int32),
                          *(v[None, 4:] for v in rest))
    want[1, slot] = np.asarray(s_c[0])
    live = np.asarray(valid)
    np.testing.assert_allclose(np.asarray(y)[:4][live],
                               np.asarray(y_d)[live, 0], atol=2e-5)
    np.testing.assert_allclose(np.asarray(y)[4:4 + n_valid],
                               np.asarray(y_c)[0, :n_valid], atol=5e-5)
    np.testing.assert_allclose(np.asarray(s), want, atol=1e-5)
    # what no row of the step names is bit for bit what it was
    touched = np.zeros((LAYERS, SLOTS), bool)
    touched[1, [0, 2, 3]] = True
    touched[1, slot] = n_valid > 0
    assert (np.asarray(s)[~touched] == np.asarray(state)[~touched]).all()


@pytest.mark.parametrize("burst", BURSTS)
@pytest.mark.parametrize("b,c,n_valid", [(8, 2, 2), (8, 4, 3), (6, 2, 1)])
def test_a_chunk_shorter_than_the_decode_rows_is_spread_over_their_steps(
        b, c, n_valid, burst):
    """With as many bursts as 2 C or more a chunk token comes every
    bursts // C grid steps and its blocks go through it over as many of
    them as divide the four (4, 2 and, for 3 steps, all at once); with
    fewer bursts than tokens, several tokens a grid step: the same
    numbers, with a parked row among the decode rows (inside a burst) and
    slot ``b`` the chunk's."""
    valid = [i != 2 for i in range(b)]
    state, *rest = _inputs(6, b + c, slots=b + 1)
    (y, s), (y_ref, s_ref) = _both(0, valid, (b, n_valid), state, *rest,
                                   burst=burst)
    live = np.asarray(valid + [i < n_valid for i in range(c)])
    np.testing.assert_allclose(np.asarray(y)[live], np.asarray(y_ref)[live],
                               atol=2e-5)
    np.testing.assert_allclose(np.asarray(s), np.asarray(s_ref), atol=2e-6)
    assert (np.asarray(s[0, 2]) == np.asarray(state[0, 2])).all()
    assert (np.asarray(s[1]) == np.asarray(state[1])).all()


@pytest.mark.parametrize("b,c", [(4, 0), (0, 16), (4, 16)])
def test_no_sequence_in_the_step_changes_nothing(b, c):
    state, *rest = _inputs(2, b + c)
    (_, s), _ = _both(1, [False] * b, (2, 0) if c else None, state, *rest)
    assert (np.asarray(s) == np.asarray(state)).all()


@pytest.mark.parametrize("decay", [0.9999, 1e-4, 0.5])
def test_the_corners_of_the_decay(decay):
    """a near 1 forgets nothing, a near 0 forgets everything: each as the
    equations say, over a chunk."""
    state, *rest = _inputs(3, 16, decay=decay)
    (y, s), _ = _both(0, [], (0, 16), state, *rest)
    want_y, want_s = _sequential(state[0, 0], *rest)
    np.testing.assert_allclose(np.asarray(y), want_y, atol=3e-4)
    np.testing.assert_allclose(np.asarray(s[0, 0]), want_s, atol=5e-5)
    if decay < 1e-3:   # of the old state nothing is left: the last update
        np.testing.assert_allclose(
            np.asarray(s[0, 0]),
            np.asarray(rest[2][-1, 0])[None, :, None]
            * np.asarray(rest[0][-1])[:, None, :], atol=1e-2)


@pytest.mark.parametrize("b,c,valid,chunk_at", [
    # an odd count of active rows: the last burst of 2 or 4 is part-filled
    (7, 0, [1, 1, 1, 0, 1, 1, 0], None),
    # fewer active rows than a burst of 2 or 4 holds
    (8, 0, [0, 0, 0, 0, 0, 1, 0, 0], None),
    # parked rows between the active ones of one burst, and beside them a
    # chunk with more tokens than grid steps that ends mid-step: 12 tokens
    # over 4 and 2 bursts are 3 and 6 a grid step, 7 of them live
    (8, 12, [1, 0, 0, 1, 1, 0, 1, 1], (8, 7)),
    # a chunk of a prime count of tokens beside three bursts of 2: all
    # seven in the first grid step
    (5, 7, [1, 1, 1, 0, 1], (6, 6)),
    # a chunk alone: a token a grid step, whatever the burst
    (0, 12, [], (2, 12)),
])
@pytest.mark.parametrize("burst", BURSTS)
def test_bursts_part_filled_parked_inside_and_tokens_a_grid_step(
        burst, b, c, valid, chunk_at):
    valid = [bool(v) for v in valid]
    state, *rest = _inputs(8, b + c, slots=9)
    (y, s), (y_ref, s_ref) = _both(1, valid, chunk_at, state, *rest,
                                   burst=burst)
    live = np.asarray(valid + [i < (chunk_at or (0, 0))[1] for i in range(c)])
    np.testing.assert_allclose(np.asarray(y)[live], np.asarray(y_ref)[live],
                               atol=5e-5)
    np.testing.assert_allclose(np.asarray(s), np.asarray(s_ref), atol=1e-5)
    # what no row of the step names is bit for bit what it was
    touched = np.zeros((LAYERS, 9), bool)
    touched[1, :b] = valid
    if chunk_at:
        touched[1, chunk_at[0]] = True
    assert (np.asarray(s)[~touched] == np.asarray(state)[~touched]).all()
    # and a burst changes no bit of what one row a grid step gives
    y1, s1 = ssm_scan(state, 1, _plan(valid, chunk_at), *rest,
                      interpret=True, burst=1)
    assert (np.asarray(s) == np.asarray(s1)).all()
    assert (np.asarray(y)[live] == np.asarray(y1)[live]).all()


def test_blocks_a_grid_step_give_the_same_numbers():
    """Two decode rows and 5 live tokens of an 8-token chunk, at 4, 2 and
    1 blocks of channels a grid step."""
    state, *rest = _inputs(4, 2 + 8)
    plan = _plan([True, True], (4, 5))
    outs = [ssm_scan(state, 1, plan, *rest, interpret=True, blocks=gb)
            for gb in (4, 2, 1)]
    for y, s in outs[1:]:
        assert (np.asarray(s) == np.asarray(outs[0][1])).all()
        assert (np.asarray(y)[:2 + 5] == np.asarray(outs[0][0])[:2 + 5]).all()


@pytest.mark.parametrize("burst", [1, 2])
def test_a_state_carried_over_512_steps_stays_on_the_references(burst):
    """The decode path's own loop: the kernel's state fed back to it, 448
    tokens as seven chunks and 64 one-token calls, against float64: the
    float32 state neither drifts nor blows up (a reaches 1). At a burst
    of 2 the sequence is slot 1's, the second row of its burst, beside
    two decode rows that carry states of their own (the chunks then go 32
    tokens a grid step beside the one burst)."""
    steps, g = 512, 1
    ks = jax.random.split(jax.random.PRNGKey(7), 3)
    x = jax.random.normal(ks[0], (steps, g, W)) * 0.05
    a = jax.nn.sigmoid(jax.random.normal(ks[1], (steps, 2, 1)) * 3 + 4)
    a = jnp.broadcast_to(a, (steps, 2, W // 2)).reshape(steps, g, W)
    bc = jax.random.normal(ks[2], (steps, 2, N))
    b = 3 if burst > 1 else 1         # decode rows; the sequence's is b - 2
    mine = max(b - 2, 0)
    state = jnp.zeros((1, b, g, N, W), jnp.float32)
    lane = _plan([i != mine for i in range(b)] if b > 1 else [], (mine, 64))
    row = _plan([True] * b)

    def rows(v, at):
        """The b decode rows' operands: the sequence's token ``at`` in
        row ``mine``, other tokens of it in the rows beside."""
        return jnp.concatenate([jax.lax.dynamic_slice_in_dim(
            v, (at + 7 * (i - mine)) % steps, 1) for i in range(b)])

    @jax.jit
    def chunk(state, i):
        sl = lambda v: jnp.concatenate(  # noqa: E731
            [rows(v, i)[:b if b > 1 else 0],
             jax.lax.dynamic_slice_in_dim(v, i * 64, 64)])
        y, state = ssm_scan(state, 0, lane, sl(x), sl(a), sl(bc),
                            interpret=True, burst=burst)
        return y[b if b > 1 else 0:], state

    @jax.jit
    def one(state, t):
        y, state = ssm_scan(state, 0, row, rows(x, t), rows(a, t),
                            rows(bc, t), interpret=True, burst=burst)
        return y[mine:mine + 1], state

    outs = []
    for i in range(steps // 64 - 1):
        y, state = chunk(state, i)
        outs.append(y)
    for t in range(steps - 64, steps):
        y, state = one(state, t)
        outs.append(y)
    got = np.concatenate([np.asarray(y) for y in outs])
    want_y, want_s = _sequential(np.zeros((g, N, W)), x, a, bc, heads=2)
    assert np.isfinite(got).all() and np.abs(want_s).max() < 100
    np.testing.assert_allclose(got, want_y, atol=2e-4)
    np.testing.assert_allclose(np.asarray(state[0, mine]), want_s, atol=5e-5)


def test_heads_view_is_the_state_as_the_equations_index_it():
    state = jnp.arange(2 * 3 * 4 * 8, dtype=jnp.float32).reshape(2, 3, 4, 8)
    view = np.asarray(heads_view(state, 6))          # [2, 6 heads, 4, N 4]
    assert view.shape == (2, 6, 4, 4)
    for g in range(3):
        for w in range(8):
            h, p = divmod(g * 8 + w, 4)
            assert (view[:, h, p] == np.asarray(state[:, g, :, w])).all()
    assert SS.blocks_of(4096) == (32, 128) and SS.blocks_of(64) == (1, 64)
    with pytest.raises(ValueError):
        SS.blocks_of(192)


def test_the_plan_and_the_skeleton_are_the_delta_rules_too():
    """One ``step_plan`` serves both kernels, and the delta rule still
    reads it as it did: the decode of a plan gives ``valid`` back."""
    assert delta_rule.step_plan is slot_stream.step_plan is SS.step_plan
    assert delta_rule.stream_rows is slot_stream.stream_rows
    valid = jnp.asarray([True, False, True, False, True])
    plan = step_plan(valid, (jnp.int32(3), jnp.int32(7)))
    assert np.asarray(plan).tolist() == [3, 3, 7, 0, 2, 4, 1, 3]
    assert (np.asarray(slot_stream.plan_valid(plan))
            == np.asarray(valid)).all()
    assert np.asarray(step_plan(valid)).tolist()[:3] == [3, 0, 0]
    # (tokens a grid step, a chunk token every stride steps, its units
    # over `parts` of them, grid steps) for (B, C, units a block, rows a
    # burst): the delta rule's cell as it was, a row a burst
    geometry = slot_stream.stream_geometry
    assert geometry(128, 64, 64, 1) == (1, 2, 2, 128)
    assert geometry(6, 2, 4, 1) == (1, 3, 1, 6)
    assert geometry(64, 0, 32, 1) == (1, 1, 1, 64)
    # more tokens than bursts: several a grid step, and no step without a
    # burst (a row a burst ran 256 steps here, 192 of them a token alone)
    assert geometry(64, 256, 32, 1) == (4, 1, 1, 64)
    # the state-space cell, two rows a burst: 32 bursts, the lane's 128
    # tokens 4 a step (the probe's 256: 8), and at four rows 8 (16)
    assert geometry(64, 128, 32, 2) == (4, 1, 1, 32)
    assert geometry(64, 256, 32, 2) == (8, 1, 1, 32)
    assert geometry(64, 128, 32, 4) == (8, 1, 1, 16)
    assert geometry(64, 0, 32, 2) == (1, 1, 1, 32)
    # fewer tokens than bursts: one every bursts // C steps, as before
    assert geometry(128, 16, 64, 2) == (1, 4, 4, 64)
    # a step's tokens are one block of the rows: a count that divides C
    assert geometry(5, 7, 4, 2) == (7, 1, 1, 3)
    assert geometry(8, 12, 4, 2) == (3, 1, 1, 4)
    # a chunk alone: a token a grid step
    assert geometry(0, 16, 4, 2) == (1, 1, 1, 16)
    # rows a burst: the fewest whose blocks are 4 MiB, and no more than B
    mib = 1024 * 1024
    assert slot_stream.BURST_BYTES == 4 * mib
    assert slot_stream.burst_rows(2 * mib, 64) == 2
    assert slot_stream.burst_rows(4 * mib, 128) == 1
    assert slot_stream.burst_rows(3 * mib, 64) == 2
    assert slot_stream.burst_rows(mib, 64) == 4
    assert slot_stream.burst_rows(mib // 4, 5) == 5
    assert slot_stream.burst_rows(mib, 0) == 1
