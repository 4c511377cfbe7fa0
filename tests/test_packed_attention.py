"""The flash kernels on packed rows (two 64-wide heads to a 128-lane row,
``ops/attention.py flash_attention(.., head_dim=)``) and the gpt2 block
whose projections write and read that layout: interpreted here, against
the plain reference.
"""

import dataclasses

import jax
import jax.numpy as jnp
import pytest

from ray_tpu.models import gpt2
from ray_tpu.ops import attention as A


def _qkv(heads, seq, head_dim, seed=0, batch=1):
    keys = jax.random.split(jax.random.PRNGKey(seed), 4)
    return [jax.random.normal(k, (batch, heads, seq, head_dim),
                              jnp.float32).astype(jnp.bfloat16)
            for k in keys]


def _rows(x, head_dim):
    """[b, heads, s, hd] -> the kernels' packed layout, [b, rows, s, 128]:
    head ``r * n + j`` in lanes ``[j * hd, (j + 1) * hd)`` of row ``r``;
    heads that do not fill the last row are made up with zero heads, as
    the gpt2 block makes them. A relayout of the whole array: a caller
    that wants speed computes its projections INTO this layout."""
    n = 128 // head_dim
    x = jnp.pad(x, ((0, 0), (0, -x.shape[1] % n), (0, 0), (0, 0)))
    b, h, s, _ = x.shape
    return x.reshape(b, h // n, n, s, head_dim).swapaxes(2, 3).reshape(
        b, h // n, s, n * head_dim)


def _heads(x, heads, head_dim):
    """The inverse of ``_rows``."""
    b, rows, s, d = x.shape
    n = d // head_dim
    return x.reshape(b, rows, s, n, head_dim).swapaxes(2, 3).reshape(
        b, rows * n, s, head_dim)[:, :heads]


def _gap(a, b):
    return float(jnp.max(jnp.abs(a.astype(jnp.float32)
                                 - b.astype(jnp.float32))))


# (heads, seq, head dim, block): 256 / 128 is the static schedule, 1024 /
# 128 the rolled one; 25 heads leave a row half empty; at head dim 128 a
# row is a head and the packed entry is the unpacked kernel.
SHAPES = {"even": (4, 256, 64, 128), "odd25": (25, 128, 64, 128),
          "rolled": (2, 1024, 64, 128), "hd128": (2, 256, 128, 128),
          "hd32": (4, 128, 32, 128)}


@pytest.mark.parametrize("causal", [True, False])
@pytest.mark.parametrize("shape", list(SHAPES))
def test_packed_forward_is_the_reference(shape, causal):
    heads, seq, hd, block = SHAPES[shape]
    q, k, v, _ = _qkv(heads, seq, hd)
    want, want_lse = A.mha_reference_with_lse(q, k, v, causal=causal)
    n = A.packed_heads_for(hd)
    assert n == 128 // hd
    o, lse = A._fwd(_rows(q, hd), _rows(k, hd), _rows(v, hd), causal,
                    hd ** -0.5, block, block, n)
    assert o.shape == (1, -(-heads // n), seq, n * hd)
    assert _gap(_heads(o, heads, hd), want) < 2e-2
    assert _gap(lse[:, :heads], want_lse) < 2e-2
    # ... and bit for bit what the unpacked kernel gives a head
    plain, plain_lse = A._fwd(q, k, v, causal, hd ** -0.5, block, block)
    assert _gap(_heads(o, heads, hd), plain) == 0.0
    assert _gap(lse[:, :heads], plain_lse) == 0.0


@pytest.mark.parametrize("causal", [True, False])
@pytest.mark.parametrize("shape", list(SHAPES))
def test_packed_gradients_are_the_reference(shape, causal):
    heads, seq, hd, block = SHAPES[shape]
    q, k, v, do = _qkv(heads, seq, hd, seed=1)
    do = do.astype(jnp.float32)

    def packed(q, k, v):
        o = A.flash_attention(_rows(q, hd), _rows(k, hd), _rows(v, hd),
                              causal=causal, block_q=block, block_k=block,
                              head_dim=hd)
        return (_heads(o, heads, hd).astype(jnp.float32) * do).sum()

    def plain(q, k, v):
        return (A.flash_attention(q, k, v, causal=causal, block_q=block,
                                  block_k=block).astype(jnp.float32)
                * do).sum()

    def reference(q, k, v):
        return (A.mha_reference(q, k, v, causal=causal).astype(jnp.float32)
                * do).sum()

    got = jax.grad(packed, (0, 1, 2))(q, k, v)
    for g, w, u in zip(got, jax.grad(reference, (0, 1, 2))(q, k, v),
                       jax.grad(plain, (0, 1, 2))(q, k, v)):
        assert _gap(g, w) < 4e-2
        assert _gap(g, u) == 0.0


def test_dispatch_takes_packed_rows_where_the_kernel_runs_and_nowhere_else():
    q, k, v, _ = _qkv(4, 128, 64, seed=2)
    rows = [_rows(t, 64) for t in (q, k, v)]
    got = A.attention(*rows, impl="flash", head_dim=64)
    assert _gap(_heads(got, 4, 64), A.attention(q, k, v, impl="flash")) == 0.0
    for impl in ("reference", "auto"):  # off the TPU "auto" has no kernel
        assert A.packed_heads_for(64, impl, 128) == 1
        with pytest.raises(ValueError, match="packed rows"):
            A.attention(*rows, impl=impl, head_dim=64)
    assert A.packed_heads_for(64, "flash", 128) == 2
    assert A.packed_heads_for(96, "flash", 128) == 1


@pytest.mark.parametrize("causal", [True, False])
@pytest.mark.parametrize("shape", ["even", "odd25", "rolled"])
def test_backward_by_layer_index_is_the_kernels_on_that_layer(shape, causal):
    """A loop over layers that owns its backward pass: ``attention_saving``
    is the forward kernel, and ``attention_of_saved`` hands back layer
    ``i``'s o and, differentiated, the backward kernel's dq, dk, dv read
    from the ``[layers, ...]`` stacks at ``i`` (a traced index): for the
    first, the middle and the last of three layers bit for bit what
    ``attention`` gives on that layer's q, k, v alone. The q, k, v it is
    called with are not read."""
    heads, seq, hd, _ = SHAPES[shape]
    layers = [[_rows(t, hd) for t in _qkv(heads, seq, hd, seed=s)]
              for s in (3, 4, 5)]
    kept = [A.attention_saving(q, k, v, causal=causal, head_dim=hd)
            for q, k, v, _ in layers]
    saved = jax.tree.map(lambda *x: jnp.stack(x), *[s for _, s in kept])

    @jax.jit
    def of_saved(i, do):
        unread = jnp.zeros_like(layers[0][0])
        o, pull = jax.vjp(lambda q, k, v: A.attention_of_saved(
            q, k, v, saved, i, causal=causal, head_dim=hd),
            unread, unread, unread)
        return o, pull(do)

    for i, ((q, k, v, do), (o, _)) in enumerate(zip(layers, kept)):
        want, pull = jax.vjp(lambda q, k, v: A.attention(
            q, k, v, causal=causal, impl="flash", head_dim=hd), q, k, v)
        got, grads = of_saved(jnp.int32(i), do)
        assert _gap(o, want) == 0.0 and _gap(got, want) == 0.0
        for g, w in zip(grads, pull(do), strict=True):
            assert _gap(g, w) == 0.0


@pytest.mark.parametrize("causal", [True, False])
@pytest.mark.parametrize("shape", ["even", "odd25", "rolled"])
def test_forward_into_layer_index_is_the_kernels_on_that_layer(shape, causal):
    """The other side of the same loop: handed the o and lse stacks and a
    layer number (a traced index), ``attention_saving`` is the forward
    kernel writing layer ``i`` of both where it lies, lse as the rows of
    lanes the backward kernel reads. For the first, the middle and the last
    of three layers o and lse are bit for bit what the kernel gives alone
    (lse through ``_lse_rows``), the o handed back is that layer of the
    stack, and every other layer keeps what it held."""
    heads, seq, hd, _ = SHAPES[shape]
    q, k, v, _ = [_rows(t, hd) for t in _qkv(heads, seq, hd, seed=6)]
    want, (_, _, _, _, want_lse) = jax.jit(lambda: A.attention_saving(
        q, k, v, causal=causal, head_dim=hd))()
    empty = A.saved_stacks(3, q.shape, q.dtype, causal, head_dim=hd)
    assert [s.shape for s in empty] == [(3,) + want.shape,
                                        (3,) + want_lse.shape]
    assert [s.dtype for s in empty] == [want.dtype, want_lse.dtype]
    held = tuple(jnp.full_like(s, 7) for s in empty)

    @jax.jit
    def into(i):
        o, saved = A.attention_saving(q, k, v, causal=causal, head_dim=hd,
                                      stacks=held, layer=i)
        return o, saved[3:]

    for i in range(3):
        o, (os, lses) = into(jnp.int32(i))
        assert _gap(o, want) == 0.0
        for stack, layer in ((os, want), (lses, want_lse)):
            assert stack.dtype == layer.dtype
            assert _gap(stack[i], layer) == 0.0
            others = jnp.delete(stack, i, axis=0)
            assert _gap(others, jnp.full_like(others, 7)) == 0.0


@pytest.mark.parametrize("n", [64, 128, 192, 512])
def test_column_as_lanes_is_the_column_turned(n):
    """What the forward kernel lays its lse out with: ``[n, 1] -> [1, n]``
    with every bit kept, runs of 128 rows or of whatever divides ``n``."""
    col = jax.random.normal(jax.random.PRNGKey(n), (n, 1), jnp.float32) * 30
    row = A._column_as_lanes(col)
    assert row.shape == (1, n) and row.dtype == col.dtype
    assert (row == col.T).all()


def test_forward_into_layer_wants_the_stacks_the_backward_reads():
    """A caller's own stack in another layout (lse a column a head, or o
    of another batch) is an error where the call is built, not a kernel
    that writes past its rows."""
    q, k, v, _ = [_rows(t, 64) for t in _qkv(4, 256, 64, seed=7)]
    o, lse = A.saved_stacks(2, q.shape, q.dtype, head_dim=64)
    for stacks in ((o, jnp.zeros((2, 1, 4, 256, 1), jnp.float32)),
                   (jnp.zeros((2, 2) + q.shape[1:], q.dtype), lse)):
        with pytest.raises(ValueError, match="stacks of o"):
            A.attention_saving(q, k, v, head_dim=64, stacks=stacks,
                               layer=jnp.int32(0))


# -- the gpt2 block -----------------------------------------------------------

def _tiny(heads, **kw):
    return gpt2.GPT2Config(
        vocab_size=512, max_seq=128, num_layers=2, num_heads=heads,
        d_model=64 * heads, attention_impl="flash", remat=True,
        remat_policy="mem2", **kw)


@pytest.mark.parametrize("heads", [4, 5])
def test_gpt2_packed_block_is_the_reference_on_stored_parameters(heads):
    """The block's projections write q, k, v packed and read o packed
    (5 heads: a zero head fills the third row); ``attention_impl=
    "reference"`` takes the stored ``[d, 3d]`` / ``[d, d]`` matrices the
    published way, a split and a transpose. Same parameters, same loss,
    same gradients: a checkpoint the parent wrote loads unchanged."""
    cfg = _tiny(heads, dtype=jnp.float32)
    ref = dataclasses.replace(cfg, attention_impl="reference")
    assert gpt2._packed_heads(cfg, 128, None) == 2
    assert gpt2._packed_heads(ref, 128, None) == 1
    params, _ = gpt2.init_params(jax.random.PRNGKey(0), cfg)
    d = cfg.d_model
    assert params["blocks"]["qkv_w"].shape == (2, d, 3 * d)
    assert params["blocks"]["proj_w"].shape == (2, d, d)
    params["blocks"]["qkv_b"] = 0.1 * jax.random.normal(
        jax.random.PRNGKey(5), params["blocks"]["qkv_b"].shape)
    batch = {"tokens": jax.random.randint(jax.random.PRNGKey(1), (2, 129),
                                          0, cfg.vocab_size)}
    loss, grads = jax.value_and_grad(
        lambda p: gpt2.loss_fn(p, batch, cfg))(params)
    want, want_grads = jax.value_and_grad(
        lambda p: gpt2.loss_fn(p, batch, ref))(params)
    assert abs(float(loss) - float(want)) < 1e-5
    scale = max(_gap(g, 0 * g) for g in jax.tree.leaves(want_grads))
    for g, w in zip(jax.tree.leaves(grads), jax.tree.leaves(want_grads)):
        assert g.shape == w.shape
        assert _gap(g, w) < 1e-5 * scale


def test_gpt2_packed_block_in_bfloat16_and_without_the_kernel():
    """bfloat16, as the cells run it: the packed block within rounding of
    the reference block. And where no kernel would run ('auto' off the
    TPU) the block does not pack."""
    cfg = _tiny(4, dtype=jnp.bfloat16)
    ref = dataclasses.replace(cfg, attention_impl="reference")
    params, _ = gpt2.init_params(jax.random.PRNGKey(0), cfg)
    batch = {"tokens": jax.random.randint(jax.random.PRNGKey(1), (2, 129),
                                          0, cfg.vocab_size)}
    assert abs(float(gpt2.loss_fn(params, batch, cfg))
               - float(gpt2.loss_fn(params, batch, ref))) < 2e-2
    auto = dataclasses.replace(cfg, attention_impl="auto")
    assert gpt2._packed_heads(auto, 128, None) == 1
    assert gpt2._packed_heads(dataclasses.replace(
        cfg, attention_impl="ring"), 128, None) == 1
