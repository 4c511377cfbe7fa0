"""``models/step.py carried_conv`` (the convolution ``solar``'s KDA layers
and ``granite``'s mamba layers carry from step to step, its window
tap-major) against a plain NumPy causal convolution that carries each
slot's last inputs: the sums to float32 rounding, the new window bit for
bit."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from ray_tpu.models.step import carried_conv

TAPS, SLOTS, CH, CHUNK = 4, 6, 256, 8


def _reference(z, state, k, b, valid, chunk_at, bias):
    """One sequence a slot, in float64: a decode row appends its input to
    the slot's window, a chunk its first ``n_valid`` inputs; every result
    is the taps' sum over the last ``TAPS`` inputs. -> (conv, new state)"""
    taps = k.shape[0]
    conv = np.zeros(z.shape, np.float64)
    new = state.copy()
    for i in range(b):
        seq = np.concatenate([state[:, i], z[i:i + 1]])
        conv[i] = (k * seq).sum(0)
        if valid[i]:
            new[:, i] = seq[1:]
    if chunk_at is not None:
        slot, n_valid = chunk_at
        seq = np.concatenate([state[:, slot], z[b:]])
        for t in range(z.shape[0] - b):
            conv[b + t] = (k * seq[t:t + taps]).sum(0)
        if n_valid > 0:
            new[:, slot] = seq[n_valid:n_valid + taps - 1]
    return conv + (0.0 if bias is None else bias), new


# name: (valid, chunk_at); the chunk's slot is parked as a decode row in
# every case but the last, as it is in the engine
ALL = [True] * SLOTS
CASES = {
    "decode_rows_only": (ALL, None),
    "with_a_chunk": ([True, True, False, True, True, True], (2, CHUNK)),
    "a_parked_row_keeps_its_window":
        ([True, False, True, False, False, True], (4, 5)),
    "an_empty_chunk_keeps_the_slots_window":
        ([True, True, True, False, True, True], (3, 0)),
    "a_chunk_shorter_than_the_window":
        ([False, True, True, True, True, True], (0, TAPS - 2)),
    "a_chunk_of_one_token": ([True, True, True, True, True, False], (5, 1)),
    "an_empty_chunk_at_a_decoding_slot": (ALL, (1, 0)),
}


@pytest.mark.parametrize("with_bias", [False, True], ids=["plain", "bias"])
@pytest.mark.parametrize("case", list(CASES))
def test_carried_conv_is_the_causal_convolution(case, with_bias):
    valid, chunk_at = CASES[case]
    rng = np.random.default_rng(sorted(CASES).index(case) + 7 * with_bias)
    rows = SLOTS + (0 if chunk_at is None else CHUNK)
    # the window keeps bfloat16; a step's rows arrive as bfloat16 values,
    # in bfloat16 (solar) or already in float32 (granite)
    z = jnp.asarray(rng.normal(size=(rows, CH)), jnp.bfloat16)
    if with_bias:
        z = z.astype(jnp.float32)
    state = jnp.asarray(rng.normal(size=(TAPS - 1, SLOTS, CH)), jnp.bfloat16)
    k = rng.normal(size=(TAPS, CH)).astype(np.float32)
    bias = rng.normal(size=CH).astype(np.float32) if with_bias else None

    @jax.jit
    def run(z, state, valid, slot, n_valid):
        at = None if chunk_at is None else (slot, n_valid)
        return carried_conv(z, state, jnp.asarray(k), SLOTS, valid, at,
                            bias=None if bias is None else jnp.asarray(bias))

    conv, new = run(z, state, jnp.asarray(valid),
                    *(jnp.int32(x) for x in (chunk_at or (0, 0))))
    want, window = _reference(
        np.asarray(z, np.float64), np.asarray(state, np.float64),
        k.astype(np.float64), SLOTS, valid, chunk_at, bias)
    assert conv.dtype == jnp.float32 and conv.shape == z.shape
    # four products and their sum (and a bias) in float32, of terms up to
    # ~10: a few ulps of the largest (1e-6 at 8)
    np.testing.assert_allclose(np.asarray(conv), want, rtol=0, atol=1e-5)
    assert new.dtype == state.dtype and new.shape == state.shape
    assert (np.asarray(new, np.float64) == window).all()
    # a row that changed nothing: untouched slots, parked rows
    untouched = [i for i in range(SLOTS) if not valid[i] and (
        chunk_at is None or chunk_at[0] != i or chunk_at[1] == 0)]
    assert (np.asarray(new)[:, untouched]
            == np.asarray(state)[:, untouched]).all()
