"""Ring-flash parity: the flash-block ring body must match full
attention and the einsum ring body (VERDICT r4 item 5)."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from ray_tpu.ops.attention import mha_reference, mha_reference_with_lse
from ray_tpu.parallel.mesh import MeshSpec
from ray_tpu.parallel.ring import ring_attention


def _qkv(b=2, h=4, s=256, d=32, seed=0):
    ks = jax.random.split(jax.random.PRNGKey(seed), 3)
    shape = (b, h, s, d)
    return tuple(jax.random.normal(k, shape, jnp.float32) for k in ks)


@pytest.mark.parametrize("causal", [True, False])
def test_ring_flash_matches_full_reference(causal):
    q, k, v = _qkv()
    mesh = MeshSpec(sp=4).build(jax.devices()[:4])
    out = ring_attention(q, k, v, mesh, causal=causal, batch_axes=(),
                         heads_axis=None, impl="flash")
    ref = mha_reference(q, k, v, causal=causal)
    err = float(jnp.max(jnp.abs(out - ref)))
    assert err < 1e-2, f"ring-flash vs reference max err {err}"
    assert err < 1e-4  # fp32 blocks should be much tighter than 1e-2


def test_ring_flash_matches_einsum_ring():
    q, k, v = _qkv(seed=3)
    mesh = MeshSpec(sp=4).build(jax.devices()[:4])
    flash = ring_attention(q, k, v, mesh, causal=True, batch_axes=(),
                           heads_axis=None, impl="flash")
    einsum = ring_attention(q, k, v, mesh, causal=True, batch_axes=(),
                            heads_axis=None, impl="einsum")
    np.testing.assert_allclose(np.asarray(flash), np.asarray(einsum),
                               atol=1e-4, rtol=1e-4)


def test_ring_flash_pallas_kernel_interpret():
    """Exercise the REAL pallas lse-producing kernel (interpret mode on
    CPU) inside the ring merge — impl='auto' would silently fall back
    to the reference path off-TPU and leave the kernel's lse contract
    uncovered."""
    import functools

    from ray_tpu.parallel.ring import ring_flash_attention_local
    from ray_tpu.parallel.sharding import smap
    from jax.sharding import PartitionSpec as P

    q, k, v = _qkv(b=1, h=2, s=128, d=32, seed=5)
    mesh = MeshSpec(sp=2).build(jax.devices()[:2])
    spec = P(None, None, "sp", None)
    fn = smap(
        functools.partial(ring_flash_attention_local, axis_name="sp",
                          causal=True, block_impl="flash"),
        mesh, in_specs=(spec, spec, spec), out_specs=spec)
    out = fn(q, k, v)
    ref = mha_reference(q, k, v, causal=True)
    err = float(jnp.max(jnp.abs(out - ref)))
    assert err < 1e-2, f"pallas-block ring vs reference max err {err}"


def test_reference_with_lse_consistent():
    q, k, v = _qkv(b=1, h=2, s=64, d=16, seed=7)
    o, lse = mha_reference_with_lse(q, k, v, causal=True)
    o2 = mha_reference(q, k, v, causal=True)
    np.testing.assert_allclose(np.asarray(o), np.asarray(o2), atol=1e-5)
    # lse really is logsumexp of the scaled causal logits
    scale = 1.0 / np.sqrt(q.shape[-1])
    logits = np.einsum("bhqd,bhkd->bhqk", np.asarray(q),
                       np.asarray(k)).astype(np.float64) * scale
    s = q.shape[2]
    mask = np.arange(s)[:, None] >= np.arange(s)[None, :]
    logits = np.where(mask, logits, -1e30)
    want = np.log(np.exp(logits - logits.max(-1, keepdims=True)).sum(-1)
                  ) + logits.max(-1)
    np.testing.assert_allclose(np.asarray(lse), want, atol=1e-3)


@pytest.mark.parametrize("sharded", [False, True])
def test_flash_kernel_output_and_grads_match_reference(sharded):
    """The flash forward and the fused dq/dk/dv backward kernel
    (interpret mode here) against autodiff of the reference — alone, and
    per (batch, heads) shard of an fsdp x tp mesh, which is how a sharded
    train step must run a kernel GSPMD cannot partition."""
    from jax.sharding import PartitionSpec as P

    from ray_tpu.ops.attention import attention

    q, k, v = _qkv(b=2, h=4, s=128, d=32, seed=11)
    kw = {}
    if sharded:
        kw = dict(mesh=MeshSpec(fsdp=2, tp=2).build(jax.devices()[:4]),
                  spec=P("fsdp", "tp", None, None))

    def loss(impl, q, k, v, **kw):
        o = attention(q, k, v, causal=True, impl=impl, **kw)
        return (o * jnp.cos(o)).sum(), o

    grad = lambda impl, **kw: jax.jit(jax.value_and_grad(
        lambda q, k, v: loss(impl, q, k, v, **kw), argnums=(0, 1, 2),
        has_aux=True))(q, k, v)
    (_, o_ref), g_ref = grad("reference")
    (_, o), g = grad("flash", **kw)
    np.testing.assert_allclose(np.asarray(o), np.asarray(o_ref),
                               atol=2e-5, rtol=2e-5)
    for got, want in zip(g, g_ref):
        np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                                   atol=2e-4, rtol=2e-4)
