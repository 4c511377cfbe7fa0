"""``ops/delta_rule.py``: the Pallas kernel, interpreted, against the
sequential recurrence at the published head size (d_k = d_v = 128), on
the contract of one call a layer: a step's rows as the layer computes
them, decode rows first (one token of slot i each) and then one slot's
chunk, with the step's plan. One token a row with a parked row between,
a chunk that stops at its last valid token, both in one call, no row in
the step, the corners of the transition (``b`` near 2: negative
eigenvalues; ``a`` near 1 and near 0), a state carried over many steps,
and the skeleton it shares with ``ops/ssm_scan.py`` at two decode rows a
burst (``burst``: the cell's blocks are 4 MiB and go a row a burst, so
only these tests run the shared path's bursts with this arithmetic), with
the rule that says how many rows a burst has."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from ray_tpu.ops import slot_stream
from ray_tpu.ops.delta_rule import (delta_rule, delta_rule_reference,
                                    recurrence, step_plan)

H, D = 8, 128
LAYERS, SLOTS = 2, 5

# The interpreted kernel and its references are traced and built once a
# shape, layer, burst and head block (jit's own cache, the module's for
# its life): cases that differ in the plan's values share the build.
delta_rule = jax.jit(delta_rule, static_argnums=1,
                     static_argnames=("interpret", "head_block", "burst"))
delta_rule_reference = jax.jit(delta_rule_reference, static_argnums=1)
recurrence = jax.jit(recurrence)


def _inputs(seed, n, beta=None, decay=None, slots=SLOTS):
    """A state and n rows' q, k, v, g [n, H, D] and beta [n, H]."""
    ks = jax.random.split(jax.random.PRNGKey(seed), 6)
    state = jax.random.normal(ks[0], (LAYERS, slots, H, D, D), jnp.float32)
    unit = lambda x: x / jnp.linalg.norm(x, axis=-1, keepdims=True)  # noqa: E731
    q = unit(jax.random.normal(ks[1], (n, H, D))) * D ** -0.5
    k = unit(jax.random.normal(ks[2], (n, H, D)))
    v = jax.random.normal(ks[3], (n, H, D))
    g = -jnp.exp(jax.random.normal(ks[4], (n, H, D)) - 2.0)
    b = 2 * jax.nn.sigmoid(jax.random.normal(ks[5], (n, H)))
    if decay is not None:
        g = jnp.full_like(g, np.log(decay))
    if beta is not None:
        b = jnp.full_like(b, beta)
    return state, q, k, v, g, b


def _sequential(state, q, k, v, g, b):
    """The recurrence as the layer's equations write it, in float64 on
    the host: S_t = (I - b k k^T) Diag(a) S_{t-1} + b k v^T; o = S^T q.
    With M = Diag(a) S_{t-1} that is M + b k (v - M^T k)^T, which is how
    it is computed: a D x D product a token and head was most of the
    file's seconds beside busy workers."""
    s = np.asarray(state, np.float64)
    q, k, v, g, b = (np.asarray(x, np.float64) for x in (q, k, v, g, b))
    out = np.zeros(v.shape)
    for t in range(q.shape[0]):
        for h in range(q.shape[1]):
            kk = k[t, h][:, None]
            m = np.exp(g[t, h])[:, None] * s[h]
            s[h] = m + b[t, h] * kk * (v[t, h] - (kk * m).sum(0))
            out[t, h] = (s[h] * q[t, h][:, None]).sum(0)
    return out, s


def test_the_reference_is_the_equations_as_they_are_written():
    """``_sequential``'s rank-one form against the D x D products of the
    layer's equations, both float64, over a few tokens."""
    state, q, k, v, g, b = (np.asarray(x, np.float64)
                            for x in _inputs(11, 4, slots=1))
    s = state[0, 0].copy()
    for t in range(4):
        for h in range(H):
            kk = k[t, h][:, None]
            s[h] = (np.eye(D) - b[t, h] * kk @ kk.T) @ (
                np.exp(g[t, h])[:, None] * s[h]) + b[t, h] * kk * v[t, h]
    out, got = _sequential(state[0, 0], q, k, v, g, b)
    np.testing.assert_allclose(got, s, rtol=0, atol=1e-12)
    np.testing.assert_allclose(out[-1], np.einsum("hkv,hk->hv", s, q[-1]),
                               rtol=0, atol=1e-12)


def _plan(valid, chunk_at=None):
    """``step_plan`` of B = len(valid) decode rows and a chunk (slot, its
    live tokens)."""
    if chunk_at is not None:
        chunk_at = tuple(jnp.int32(x) for x in chunk_at)
    return step_plan(jnp.asarray(valid, bool), chunk_at)


def _both(layer, valid, chunk_at, *args, **kw):
    plan = _plan(valid, chunk_at)
    want = delta_rule_reference(args[0], layer, plan, *args[1:])
    got = delta_rule(args[0], layer, plan, *args[1:], interpret=True, **kw)
    return got, want


@pytest.mark.parametrize("burst", [1, 2])
def test_one_token_a_row_with_a_parked_row_between(burst):
    state, *rest = _inputs(0, 4)
    (o, s), (o_ref, s_ref) = _both(1, [1, 0, 1, 1], None, state, *rest,
                                   burst=burst)
    live = np.asarray([True, False, True, True])
    np.testing.assert_allclose(np.asarray(o)[live], np.asarray(o_ref)[live],
                               atol=2e-6)
    np.testing.assert_allclose(np.asarray(s), np.asarray(s_ref), atol=2e-6)
    # the other layer, the parked row's slot and the slot no row names
    # are bit for bit what they were
    assert (np.asarray(s[0]) == np.asarray(state[0])).all()
    assert (np.asarray(s[1, 1]) == np.asarray(state[1, 1])).all()
    assert (np.asarray(s[1, 4]) == np.asarray(state[1, 4])).all()
    # and the equations themselves, for one row
    want_o, want_s = _sequential(state[1, 2], *(x[2:3] for x in rest))
    np.testing.assert_allclose(np.asarray(o[2:3]), want_o, atol=1e-5)
    np.testing.assert_allclose(np.asarray(s[1, 2]), want_s, atol=1e-5)


@pytest.mark.parametrize("n_valid", [64, 37, 1])
def test_a_chunk_of_one_slot_stops_at_its_last_valid_token(n_valid):
    state, *rest = _inputs(1, 64)
    (o, s), (o_ref, s_ref) = _both(0, [], (3, n_valid), state, *rest)
    np.testing.assert_allclose(np.asarray(o)[:n_valid],
                               np.asarray(o_ref)[:n_valid], atol=5e-6)
    np.testing.assert_allclose(np.asarray(s), np.asarray(s_ref), atol=5e-6)
    want_o, want_s = _sequential(state[0, 3], *(x[:n_valid] for x in rest))
    np.testing.assert_allclose(np.asarray(o)[:n_valid], want_o, atol=2e-5)
    np.testing.assert_allclose(np.asarray(s[0, 3]), want_s, atol=2e-5)
    touched = np.zeros((LAYERS, SLOTS), bool)
    touched[0, 3] = True
    assert (np.asarray(s)[~touched] == np.asarray(state)[~touched]).all()


@pytest.mark.parametrize("n_valid", [0, 1, 63, 64])
@pytest.mark.parametrize("slot", [4, 1])
def test_decode_rows_and_a_chunk_in_one_call(slot, n_valid):
    """Four decode rows, row 1 parked, and a 64-token chunk of a slot
    that is no decode row's (4) or the parked row's (1), against the
    recurrence itself run on each sequence alone."""
    valid = [True, False, True, True]
    state, *rest = _inputs(5, 4 + 64)
    plan = _plan(valid, (slot, n_valid))
    o, s = delta_rule(state, 1, plan, *rest, interpret=True)
    want = np.array(state)
    o_d, s_d = recurrence(state[1, :4], jnp.asarray(valid, jnp.int32),
                          *(x[:4, None] for x in rest))
    want[1, :4] = np.asarray(s_d)
    o_c, s_c = recurrence(state[1, slot][None],
                          jnp.asarray([n_valid], jnp.int32),
                          *(x[None, 4:] for x in rest))
    want[1, slot] = np.asarray(s_c[0])
    live = np.asarray(valid)
    np.testing.assert_allclose(np.asarray(o)[:4][live],
                               np.asarray(o_d)[live, 0], atol=2e-6)
    np.testing.assert_allclose(np.asarray(o)[4:4 + n_valid],
                               np.asarray(o_c)[0, :n_valid], atol=5e-6)
    np.testing.assert_allclose(np.asarray(s), want, atol=5e-6)
    # what no row of the step names is bit for bit what it was
    touched = np.zeros((LAYERS, SLOTS), bool)
    touched[1, [0, 2, 3]] = True
    touched[1, slot] = n_valid > 0
    assert (np.asarray(s)[~touched] == np.asarray(state)[~touched]).all()


@pytest.mark.parametrize("burst", [1, 2])
@pytest.mark.parametrize("b,c,n_valid", [(8, 2, 2), (8, 4, 3), (6, 2, 1)])
def test_a_chunk_shorter_than_the_decode_rows_is_spread_over_their_steps(
        b, c, n_valid, burst):
    """With as many bursts as 2 C or more a chunk token comes every
    bursts // C grid steps and its heads go through it over as many of
    them as divide the block (4, 2 and, for 3 steps, all at once): the
    same numbers, with a parked row among the decode rows (inside a
    burst of 2) and slot ``b`` the chunk's."""
    valid = [i != 2 for i in range(b)]
    state, *rest = _inputs(6, b + c, slots=b + 1)
    (o, s), (o_ref, s_ref) = _both(0, valid, (b, n_valid), state, *rest,
                                   burst=burst)
    live = np.asarray(valid + [i < n_valid for i in range(c)])
    np.testing.assert_allclose(np.asarray(o)[live], np.asarray(o_ref)[live],
                               atol=2e-6)
    np.testing.assert_allclose(np.asarray(s), np.asarray(s_ref), atol=2e-6)
    assert (np.asarray(s[0, 2]) == np.asarray(state[0, 2])).all()
    assert (np.asarray(s[1]) == np.asarray(state[1])).all()


@pytest.mark.parametrize("b,c,valid,chunk_at", [
    # an odd count of active rows: the last burst is part-filled
    (7, 0, [1, 1, 1, 0, 1, 1, 0], None),
    # fewer active rows than a burst holds
    (8, 0, [0, 0, 0, 0, 0, 1, 0, 0], None),
    # parked rows between the active ones of one burst, and a chunk with
    # more tokens than grid steps that ends mid-step (12 over 4 bursts: 3
    # a grid step, 7 live)
    (8, 12, [1, 0, 0, 1, 1, 0, 1, 1], (8, 7)),
    # a chunk alone
    (0, 12, [], (2, 12)),
])
def test_bursts_of_two_rows_give_what_a_row_a_grid_step_gives(
        b, c, valid, chunk_at):
    valid = [bool(v) for v in valid]
    state, *rest = _inputs(8, b + c, slots=9)
    (o, s), (o_ref, s_ref) = _both(1, valid, chunk_at, state, *rest, burst=2)
    live = np.asarray(valid + [i < (chunk_at or (0, 0))[1] for i in range(c)])
    np.testing.assert_allclose(np.asarray(o)[live], np.asarray(o_ref)[live],
                               atol=5e-6)
    np.testing.assert_allclose(np.asarray(s), np.asarray(s_ref), atol=5e-6)
    touched = np.zeros((LAYERS, 9), bool)
    touched[1, :b] = valid
    if chunk_at:
        touched[1, chunk_at[0]] = True
    assert (np.asarray(s)[~touched] == np.asarray(state)[~touched]).all()
    o1, s1 = delta_rule(state, 1, _plan(valid, chunk_at), *rest,
                        interpret=True, burst=1)
    assert (np.asarray(s) == np.asarray(s1)).all()
    assert (np.asarray(o)[live] == np.asarray(o1)[live]).all()


def test_the_rule_of_rows_a_burst_places_every_row_and_token_once():
    """``slot_stream``'s rule in plain Python: a row a burst at the delta
    rule's block (64 heads x 128 x 128 float32 = 4 MiB), two or more at
    the state-space scan's (32 blocks x 128 x 128 = 2 MiB); and for a
    sweep of decode rows, chunk sizes, active rows and live tokens, every
    active row is in exactly one burst and every live chunk token at
    exactly one grid step (every share of its units once)."""
    f32 = 4
    assert slot_stream.burst_rows(64 * 128 * 128 * f32, 128) == 1
    assert slot_stream.burst_rows(32 * 128 * 128 * f32, 64) >= 2
    ub = 4
    for b in (0, 1, 3, 8, 13):
        for c in (0, 1, 2, 7, 12, 16):
            for burst in (1, 2, 3, 4):
                tokens, stride, parts, steps = slot_stream.stream_geometry(
                    b, c, ub, burst)
                assert tokens == 1 or c % tokens == 0
                assert ub % parts == 0 and parts <= stride
                for n_active in {0, 1, b // 2, b - 1, b} & set(range(b + 1)):
                    rows = [slot_stream.burst_row(s, k, burst)
                            for s in range(steps) for k in range(burst)]
                    assert sorted(i for i in rows if i < n_active) == list(
                        range(n_active)), (b, c, burst, n_active)
                for n_valid in {0, 1, c // 2, c - 1, c} & set(range(c + 1)):
                    shares = []
                    for s in range(steps):
                        t, r = slot_stream.chunk_tokens(s, tokens, stride)
                        shares += [(t + i, r) for i in range(tokens)
                                   if t + i < n_valid and r < parts]
                    assert sorted(shares) == [
                        (t, r) for t in range(n_valid) for r in range(parts)
                    ], (b, c, burst, n_valid)


@pytest.mark.parametrize("b,c", [(4, 0), (0, 64), (4, 64)])
def test_no_sequence_in_the_step_changes_nothing(b, c):
    state, *rest = _inputs(2, b + c)
    (_, s), _ = _both(1, [False] * b, (2, 0) if c else None, state, *rest)
    assert (np.asarray(s) == np.asarray(state)).all()


@pytest.mark.parametrize("beta,decay", [(1.999, 0.999), (1.999, 1e-4),
                                        (0.001, 0.999), (1.0, 0.5)])
def test_the_corners_of_the_transition(beta, decay):
    """b near 2 reflects the state across k (eigenvalue -1), a near 1
    forgets nothing, a near 0 forgets everything: each as the equations
    say, over a chunk."""
    state, *rest = _inputs(3, 16, beta=beta, decay=decay)
    (o, s), _ = _both(0, [], (0, 16), state, *rest)
    want_o, want_s = _sequential(state[0, 0], *rest)
    np.testing.assert_allclose(np.asarray(o), want_o, atol=2e-5)
    np.testing.assert_allclose(np.asarray(s[0, 0]), want_s, atol=2e-5)
    if decay < 1e-3:   # nothing of the old state is left after 16 tokens
        assert np.abs(want_s).max() < 10


def test_head_blocks_give_the_same_numbers():
    """Two decode rows and 9 live tokens of a 16-token chunk, at 8, 4
    and 2 heads a grid step."""
    state, *rest = _inputs(4, 2 + 16)
    plan = _plan([True, True], (4, 9))
    outs = [delta_rule(state, 1, plan, *rest, interpret=True, head_block=hb)
            for hb in (8, 4, 2)]
    for o, s in outs[1:]:
        np.testing.assert_allclose(np.asarray(s), np.asarray(outs[0][1]),
                                   atol=1e-6)
        np.testing.assert_allclose(np.asarray(o)[:2 + 9],
                                   np.asarray(outs[0][0])[:2 + 9], atol=1e-6)


def test_a_state_carried_over_1024_steps_stays_on_the_references():
    """The decode path's own loop: the kernel's state fed back to it 1024
    times, one token a step, against float64: the float32 state neither
    drifts nor blows up (b reaches 2, a reaches 1)."""
    steps, h = 1024, 2
    ks = jax.random.split(jax.random.PRNGKey(7), 5)
    unit = lambda x: x / jnp.linalg.norm(x, axis=-1, keepdims=True)  # noqa: E731
    q = unit(jax.random.normal(ks[0], (steps, h, D))) * D ** -0.5
    k = unit(jax.random.normal(ks[1], (steps, h, D)))
    v = jax.random.normal(ks[2], (steps, h, D))
    g = -jnp.exp(jax.random.normal(ks[3], (steps, h, D)) * 2 - 4.0)
    b = 2 * jax.nn.sigmoid(jax.random.normal(ks[4], (steps, h)) * 3)
    state = jnp.zeros((1, 1, h, D, D), jnp.float32)
    lane, row = _plan([], (0, 64)), _plan([True])

    # 1024 tokens as 15 calls of a 64-token chunk, then one-token calls
    # (a decode row) on the last 64 tokens
    # (each traced and built once: the position is an argument)
    def call(plan, n):
        @jax.jit
        def step(state, at):
            sl = lambda x: jax.lax.dynamic_slice_in_dim(x, at, n)  # noqa: E731
            return delta_rule(state, 0, plan, sl(q), sl(k), sl(v), sl(g),
                              sl(b), interpret=True)
        return step

    chunk, token = call(lane, 64), call(row, 1)
    outs = []
    for i in range(steps // 64 - 1):
        o, state = chunk(state, i * 64)
        outs.append(o)
    for t in range(steps - 64, steps):
        o, state = token(state, t)
        outs.append(o)
    got = np.concatenate([np.asarray(o) for o in outs])
    want_o, want_s = _sequential(np.zeros((h, D, D)), q, k, v, g, b)
    assert np.isfinite(got).all() and np.abs(want_s).max() < 100
    np.testing.assert_allclose(got, want_o, atol=5e-5)
    np.testing.assert_allclose(np.asarray(state[0, 0]), want_s, atol=5e-5)
