"""``ops/delta_rule.py``: the Pallas kernel, interpreted, against the
sequential recurrence at the published head size (d_k = d_v = 128): one
token a row (the decode rows), a chunk of tokens of one slot (the prefill
lane), sequences that are not in the step, the corners of the transition
(``b`` near 2: negative eigenvalues; ``a`` near 1 and near 0), and a state
carried over many steps."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from ray_tpu.ops.delta_rule import delta_rule, delta_rule_reference

H, D = 8, 128
LAYERS, SLOTS = 2, 5


def _inputs(seed, r, t, beta=None, decay=None):
    ks = jax.random.split(jax.random.PRNGKey(seed), 6)
    state = jax.random.normal(ks[0], (LAYERS, SLOTS, H, D, D), jnp.float32)
    unit = lambda x: x / jnp.linalg.norm(x, axis=-1, keepdims=True)  # noqa: E731
    q = unit(jax.random.normal(ks[1], (r, t, H, D))) * D ** -0.5
    k = unit(jax.random.normal(ks[2], (r, t, H, D)))
    v = jax.random.normal(ks[3], (r, t, H, D))
    g = -jnp.exp(jax.random.normal(ks[4], (r, t, H, D)) - 2.0)
    b = 2 * jax.nn.sigmoid(jax.random.normal(ks[5], (r, t, H)))
    if decay is not None:
        g = jnp.full_like(g, np.log(decay))
    if beta is not None:
        b = jnp.full_like(b, beta)
    return state, q, k, v, g, b


def _sequential(state, q, k, v, g, b):
    """The recurrence as the layer's equations write it, in float64 on
    the host: S_t = (I - b k k^T) Diag(a) S_{t-1} + b k v^T; o = S^T q."""
    s = np.asarray(state, np.float64)
    q, k, v, g, b = (np.asarray(x, np.float64) for x in (q, k, v, g, b))
    out = np.zeros(v.shape)
    for t in range(q.shape[0]):
        for h in range(q.shape[1]):
            kk = k[t, h][:, None]
            s[h] = (np.eye(D) - b[t, h] * kk @ kk.T) @ (
                np.exp(g[t, h])[:, None] * s[h]) + b[t, h] * kk * v[t, h]
            out[t, h] = s[h].T @ q[t, h]
    return out, s


def _both(layer, slot_of, n_tok, *args, **kw):
    slot_of = jnp.asarray(slot_of, jnp.int32)
    n_tok = jnp.asarray(n_tok, jnp.int32)
    want = delta_rule_reference(args[0], layer, slot_of, n_tok, *args[1:])
    got = delta_rule(args[0], layer, slot_of, n_tok, *args[1:],
                     interpret=True, **kw)
    return got, want


def test_one_token_a_row_with_a_parked_row_between():
    state, *rest = _inputs(0, 4, 1)
    (o, s), (o_ref, s_ref) = _both(1, [0, 1, 2, 3], [1, 0, 1, 1], state,
                                   *rest)
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
    want_o, want_s = _sequential(state[1, 2], *(x[2] for x in rest))
    np.testing.assert_allclose(np.asarray(o[2]), want_o, atol=1e-5)
    np.testing.assert_allclose(np.asarray(s[1, 2]), want_s, atol=1e-5)


@pytest.mark.parametrize("n_valid", [64, 37, 1])
def test_a_chunk_of_one_slot_stops_at_its_last_valid_token(n_valid):
    state, *rest = _inputs(1, 1, 64)
    (o, s), (o_ref, s_ref) = _both(0, [3], [n_valid], state, *rest)
    np.testing.assert_allclose(np.asarray(o)[0, :n_valid],
                               np.asarray(o_ref)[0, :n_valid], atol=5e-6)
    np.testing.assert_allclose(np.asarray(s), np.asarray(s_ref), atol=5e-6)
    want_o, want_s = _sequential(state[0, 3],
                                 *(x[0, :n_valid] for x in rest))
    np.testing.assert_allclose(np.asarray(o)[0, :n_valid], want_o, atol=2e-5)
    np.testing.assert_allclose(np.asarray(s[0, 3]), want_s, atol=2e-5)
    touched = np.zeros((LAYERS, SLOTS), bool)
    touched[0, 3] = True
    assert (np.asarray(s)[~touched] == np.asarray(state)[~touched]).all()


@pytest.mark.parametrize("r,t", [(4, 1), (1, 64)])
def test_no_sequence_in_the_step_changes_nothing(r, t):
    state, *rest = _inputs(2, r, t)
    (_, s), _ = _both(1, list(range(r)), [0] * r, state, *rest)
    assert (np.asarray(s) == np.asarray(state)).all()


@pytest.mark.parametrize("beta,decay", [(1.999, 0.999), (1.999, 1e-4),
                                        (0.001, 0.999), (1.0, 0.5)])
def test_the_corners_of_the_transition(beta, decay):
    """b near 2 reflects the state across k (eigenvalue -1), a near 1
    forgets nothing, a near 0 forgets everything: each as the equations
    say, over a chunk."""
    state, *rest = _inputs(3, 1, 16, beta=beta, decay=decay)
    (o, s), _ = _both(0, [0], [16], state, *rest, token_block=8)
    want_o, want_s = _sequential(state[0, 0], *(x[0] for x in rest))
    np.testing.assert_allclose(np.asarray(o)[0], want_o, atol=2e-5)
    np.testing.assert_allclose(np.asarray(s[0, 0]), want_s, atol=2e-5)
    if decay < 1e-3:   # nothing of the old state is left after 16 tokens
        assert np.abs(want_s).max() < 10


def test_head_blocks_and_token_blocks_give_the_same_numbers():
    state, *rest = _inputs(4, 2, 16)
    outs = [delta_rule(state, 1, jnp.asarray([4, 0]), jnp.asarray([16, 9]),
                       *rest, interpret=True, head_block=hb, token_block=tb)
            for hb, tb in ((8, 8), (8, 16), (8, 1))]
    for o, s in outs[1:]:
        np.testing.assert_allclose(np.asarray(s), np.asarray(outs[0][1]),
                                   atol=1e-6)
        np.testing.assert_allclose(np.asarray(o)[1, :9],
                                   np.asarray(outs[0][0])[1, :9], atol=1e-6)


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
    slot, one = jnp.zeros((1,), jnp.int32), jnp.ones((1,), jnp.int32)

    # 1024 tokens as 16 calls of a 64-token chunk, then checked against
    # one-token calls on the last 8 tokens
    @jax.jit
    def chunk(state, i):
        sl = lambda x: jax.lax.dynamic_slice_in_dim(x, i * 64, 64)[None]  # noqa: E731
        return delta_rule(state, 0, slot, one * 64, sl(q), sl(k), sl(v),
                          sl(g), sl(b), interpret=True)

    outs = []
    for i in range(steps // 64 - 1):
        o, state = chunk(state, i)
        outs.append(o[0])
    for t in range(steps - 64, steps):
        o, state = delta_rule(state, 0, slot, one, q[None, t:t + 1],
                              k[None, t:t + 1], v[None, t:t + 1],
                              g[None, t:t + 1], b[None, t:t + 1],
                              interpret=True)
        outs.append(o[0])
    got = np.concatenate([np.asarray(o) for o in outs])
    want_o, want_s = _sequential(np.zeros((h, D, D)), q, k, v, g, b)
    assert np.isfinite(got).all() and np.abs(want_s).max() < 100
    np.testing.assert_allclose(got, want_o, atol=5e-5)
    np.testing.assert_allclose(np.asarray(state[0, 0]), want_s, atol=5e-5)
