"""Parallel layer tests on the 8-device CPU mesh: collectives, ring
attention, Ulysses, pipeline, MoE."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from ray_tpu.ops.attention import mha_reference
from ray_tpu.parallel import (
    MeshSpec,
    allgather,
    allreduce,
    broadcast,
    init_collective_group,
    moe_ffn_local,
    pipeline_apply,
    reducescatter,
    ring_attention,
    spec_for,
    ulysses_attention,
)

pytestmark = pytest.mark.skipif(
    len(jax.devices()) < 8, reason="needs 8 virtual devices"
)


@pytest.fixture(scope="module")
def mesh8():
    return MeshSpec(dp=8).build()


def test_mesh_spec_axes():
    spec = MeshSpec.for_devices(8, tp=2, sp=2)
    assert spec.dp == 2 and spec.tp == 2 and spec.sp == 2
    mesh = spec.build()
    assert mesh.devices.size == 8
    assert spec.describe() == "dp=2xsp=2xtp=2"


def test_spec_for_rules():
    assert spec_for(("batch", "seq", "embed")) == P(("dp", "fsdp"), "sp", "fsdp")
    assert spec_for((None, "heads")) == P(None, "tp")


def test_allreduce(mesh8):
    init_collective_group(mesh8, axis="dp", group_name="t_ar")
    x = jnp.arange(8 * 4, dtype=jnp.float32).reshape(8, 4)
    out = allreduce(x, "sum", group_name="t_ar")
    np.testing.assert_allclose(np.asarray(out), np.asarray(x).sum(0))


def test_allgather_broadcast(mesh8):
    init_collective_group(mesh8, axis="dp", group_name="t_ag")
    x = jnp.arange(8 * 3, dtype=jnp.float32).reshape(8, 3)
    out = allgather(x, group_name="t_ag")
    np.testing.assert_allclose(np.asarray(out), np.asarray(x))
    b = broadcast(x, src_rank=2, group_name="t_ag")
    np.testing.assert_allclose(np.asarray(b), np.asarray(x)[2])


def test_reducescatter(mesh8):
    init_collective_group(mesh8, axis="dp", group_name="t_rs")
    x = jnp.ones((8, 8, 2), jnp.float32)
    out = reducescatter(x, "sum", group_name="t_rs")
    assert out.shape == (8, 2)
    np.testing.assert_allclose(np.asarray(out), 8.0)


def test_ring_attention_matches_reference():
    mesh = MeshSpec(sp=8).build()
    B, H, S, D = 2, 4, 128, 16
    key = jax.random.PRNGKey(1)
    q, k, v = (
        jax.random.normal(jax.random.fold_in(key, i), (B, H, S, D), jnp.float32)
        for i in range(3)
    )
    ref = mha_reference(q, k, v, causal=True)
    out = ring_attention(q, k, v, mesh, axis_name="sp")
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               atol=2e-5, rtol=2e-5)


def test_ring_attention_non_causal():
    mesh = MeshSpec(sp=4, dp=2).build()
    B, H, S, D = 2, 2, 64, 8
    key = jax.random.PRNGKey(2)
    q, k, v = (
        jax.random.normal(jax.random.fold_in(key, i), (B, H, S, D), jnp.float32)
        for i in range(3)
    )
    ref = mha_reference(q, k, v, causal=False)
    out = ring_attention(q, k, v, mesh, causal=False)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               atol=2e-5, rtol=2e-5)


def test_ring_attention_grads_flow():
    mesh = MeshSpec(sp=8).build()
    B, H, S, D = 1, 2, 64, 8
    key = jax.random.PRNGKey(3)
    q, k, v = (
        jax.random.normal(jax.random.fold_in(key, i), (B, H, S, D), jnp.float32)
        for i in range(3)
    )

    def loss_ring(q, k, v):
        return (ring_attention(q, k, v, mesh) ** 2).sum()

    def loss_ref(q, k, v):
        return (mha_reference(q, k, v) ** 2).sum()

    g_ring = jax.grad(loss_ring)(q, k, v)
    g_ref = jax.grad(loss_ref)(q, k, v)
    np.testing.assert_allclose(np.asarray(g_ring), np.asarray(g_ref),
                               atol=1e-4, rtol=1e-4)


def test_ulysses_matches_reference():
    mesh = MeshSpec(sp=8).build()
    B, H, S, D = 2, 8, 128, 16  # heads divisible by sp
    key = jax.random.PRNGKey(4)
    q, k, v = (
        jax.random.normal(jax.random.fold_in(key, i), (B, H, S, D), jnp.float32)
        for i in range(3)
    )
    ref = mha_reference(q, k, v, causal=True)
    out = ulysses_attention(q, k, v, mesh, axis_name="sp")
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               atol=2e-5, rtol=2e-5)


def test_pipeline_matches_sequential():
    mesh = MeshSpec(pp=4).build(jax.devices()[:4])
    n_stage, micro, mb, dim = 4, 8, 4, 16
    key = jax.random.PRNGKey(5)
    ws = jax.random.normal(key, (n_stage, dim, dim), jnp.float32) * 0.3

    def stage_fn(w, x):
        return jnp.tanh(x @ w)

    x = jax.random.normal(jax.random.fold_in(key, 9), (micro, mb, dim))
    # Sequential reference: apply stages in order.
    ref = x
    for i in range(n_stage):
        ref = stage_fn(ws[i], ref)

    out = pipeline_apply(stage_fn, ws, x, mesh, axis_name="pp",
                         params_spec=P("pp"), data_spec=P())
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               atol=1e-5, rtol=1e-5)


def test_moe_local_no_ep():
    tokens, model, hidden, E = 64, 16, 32, 4
    key = jax.random.PRNGKey(6)
    x = jax.random.normal(key, (tokens, model))
    router_w = jax.random.normal(jax.random.fold_in(key, 1), (model, E)) * 0.1
    w_in = jax.random.normal(jax.random.fold_in(key, 2), (E, model, hidden)) * 0.1
    w_out = jax.random.normal(jax.random.fold_in(key, 3), (E, hidden, model)) * 0.1
    y, aux = moe_ffn_local(x, router_w, w_in, w_out, num_experts=E,
                           top_k=2, axis_name=None, capacity_factor=2.0)
    assert y.shape == x.shape
    assert float(aux) > 0
    assert not np.isnan(np.asarray(y)).any()


def test_moe_expert_parallel():
    from functools import partial

    mesh = MeshSpec(ep=4).build(jax.devices()[:4])
    tokens, model, hidden, E = 32, 8, 16, 4
    key = jax.random.PRNGKey(7)
    x = jax.random.normal(key, (4 * tokens, model))
    router_w = jax.random.normal(jax.random.fold_in(key, 1), (model, E)) * 0.1
    w_in = jax.random.normal(jax.random.fold_in(key, 2), (E, model, hidden)) * 0.1
    w_out = jax.random.normal(jax.random.fold_in(key, 3), (E, hidden, model)) * 0.1

    fn = jax.shard_map(
        partial(moe_ffn_local, num_experts=E, top_k=1, axis_name="ep",
                capacity_factor=4.0),
        mesh=mesh,
        in_specs=(P("ep"), P(), P("ep"), P("ep")),
        out_specs=(P("ep"), P()),
        check_vma=False,
    )
    y, aux = fn(x, router_w, w_in, w_out)
    assert y.shape == x.shape
    assert not np.isnan(np.asarray(y)).any()


def test_moe_expert_parallel_matches_local():
    """EP-sharded MoE must be numerically IDENTICAL to running each token
    shard through the local (no-ep) path — regression for the all_to_all
    slot-ordering bug that e_local=1 tests couldn't see (untiled a2a
    removes the split axis and inserts the device axis at concat)."""
    from functools import partial

    mesh = MeshSpec(ep=4).build(jax.devices()[:4])
    tokens, model, hidden, E = 32, 8, 16, 8  # e_local = 2
    key = jax.random.PRNGKey(7)
    x = jax.random.normal(key, (4 * tokens, model))
    router_w = jax.random.normal(jax.random.fold_in(key, 1), (model, E)) * 0.1
    w_in = jax.random.normal(
        jax.random.fold_in(key, 2), (E, model, hidden)) * 0.1
    w_out = jax.random.normal(
        jax.random.fold_in(key, 3), (E, hidden, model)) * 0.1

    fn = jax.shard_map(
        partial(moe_ffn_local, num_experts=E, top_k=1, axis_name="ep",
                capacity_factor=8.0),
        mesh=mesh,
        in_specs=(P("ep"), P(), P("ep"), P("ep")),
        out_specs=(P("ep"), P()),
        check_vma=False,
    )
    y, _ = fn(x, router_w, w_in, w_out)
    ref = jnp.concatenate([
        moe_ffn_local(x[i * tokens:(i + 1) * tokens], router_w, w_in,
                      w_out, num_experts=E, top_k=1, axis_name=None,
                      capacity_factor=8.0)[0]
        for i in range(4)
    ], axis=0)
    np.testing.assert_allclose(np.asarray(y), np.asarray(ref), atol=1e-5)


def test_moe_gpt_ep_train_step_decreases_loss():
    """MoE-GPT (num_experts>0) trains over an ep mesh through
    build_sharded_train: finite decreasing loss, nonzero grads."""
    from ray_tpu.models import gpt2
    from ray_tpu.parallel.sharding import prune_rules_for_mesh
    from ray_tpu.train.step import build_sharded_train

    cfg = gpt2.GPT2Config(
        vocab_size=256, max_seq=32, num_layers=2, num_heads=4, d_model=64,
        dtype=jnp.float32, attention_impl="reference", remat=False,
        num_experts=8, moe_top_k=2,
    )
    mesh = MeshSpec(dp=2, ep=4).build(jax.devices()[:8])
    over = {"batch": ("dp", "fsdp", "ep")}
    rules = prune_rules_for_mesh(mesh, over)
    sinit, sstep, _ = build_sharded_train(
        lambda key: gpt2.init_params(key, cfg),
        lambda p, b: gpt2.loss_fn(p, b, cfg, rules=rules),
        mesh, rules=over, master_fp32=False,
    )
    params, opt_state, step = sinit(jax.random.PRNGKey(0))
    tokens = jax.random.randint(jax.random.PRNGKey(1), (8, 33), 0, 256)
    losses = []
    for _ in range(4):
        params, opt_state, step, m = sstep(params, opt_state, step,
                                           {"tokens": tokens})
        losses.append(float(m["loss"]))
    assert np.isfinite(losses[-1]) and losses[-1] < losses[0]
    assert float(m["grad_norm"]) > 0


def test_gpt_pp_pipeline_train_step_decreases_loss():
    """GPT with blocks pipelined over pp ({"layers": "pp"} rules) trains
    through build_sharded_train: finite decreasing loss."""
    from ray_tpu.models import gpt2
    from ray_tpu.parallel.sharding import prune_rules_for_mesh
    from ray_tpu.train.step import build_sharded_train

    cfg = gpt2.GPT2Config(
        vocab_size=256, max_seq=32, num_layers=4, num_heads=4, d_model=64,
        dtype=jnp.float32, attention_impl="reference", remat=False,
    )
    mesh = MeshSpec(dp=2, pp=4).build(jax.devices()[:8])
    over = {"layers": "pp"}
    rules = prune_rules_for_mesh(mesh, over)
    sinit, sstep, _ = build_sharded_train(
        lambda key: gpt2.init_params(key, cfg),
        lambda p, b: gpt2.loss_fn(p, b, cfg, rules=rules),
        mesh, rules=over, master_fp32=False,
    )
    params, opt_state, step = sinit(jax.random.PRNGKey(0))
    tokens = jax.random.randint(jax.random.PRNGKey(1), (16, 33), 0, 256)
    losses = []
    for _ in range(4):
        params, opt_state, step, m = sstep(params, opt_state, step,
                                           {"tokens": tokens})
        losses.append(float(m["loss"]))
    assert np.isfinite(losses[-1]) and losses[-1] < losses[0]
    assert float(m["grad_norm"]) > 0


# -- the tied LM head, decomposed over tokens ---------------------------------

HEAD_MESHES = {
    "fsdp4": (MeshSpec(fsdp=4), "reference"),
    "fsdp2_tp2": (MeshSpec(fsdp=2, tp=2), "reference"),
    "dp2_fsdp2": (MeshSpec(dp=2, fsdp=2), "reference"),
    "sp2_ring": (MeshSpec(sp=2), "ring"),
    "one_device": (MeshSpec(), "reference"),
}


@pytest.mark.parametrize("name", list(HEAD_MESHES))
def test_gpt2_head_on_a_mesh_equals_one_device(name):
    """Loss and every gradient leaf of ``gpt2.loss_fn`` with the head cut
    by tokens (and by vocab under tp) equal the plain float32 values:
    whole logits from ``forward()`` on one device, one un-chunked CE. A
    device's tokens do not fill its chunks (the pad / ignore_id path) and
    some targets are -1."""
    from ray_tpu.models import gpt2
    from ray_tpu.models.common import cross_entropy_sums
    from ray_tpu.parallel.sharding import (prune_rules_for_mesh,
                                           shardings_for, under_mesh)

    spec, attention = HEAD_MESHES[name]
    base = dict(vocab_size=256, max_seq=150, num_layers=2, num_heads=2,
                d_model=32, dtype=jnp.float32, remat=False)
    cfg = gpt2.GPT2Config(attention_impl=attention, **base)
    plain = gpt2.GPT2Config(attention_impl="reference", **base)
    params, axes = gpt2.init_params(jax.random.PRNGKey(0), cfg)
    tokens = np.array(jax.random.randint(
        jax.random.PRNGKey(1), (8, 151), 0, 256))
    tokens[[0, 3, 5], -1] = -1  # the last column is a target only
    tokens[6, -40:] = -1  # an input too: wte[-1] on both sides
    batch = {"tokens": jnp.asarray(tokens)}

    def plain_loss(p):
        logits = gpt2.forward(p, batch["tokens"][:, :-1], plain)
        nll, count = cross_entropy_sums(logits, batch["tokens"][:, 1:])
        return nll / count

    want, want_grads = jax.value_and_grad(plain_loss)(params)

    mesh = spec.build(jax.devices()[:spec.num_devices])
    rules = prune_rules_for_mesh(mesh)
    # 1200 tokens in chunks of at most 200: one device pads 1200 to five
    # chunks of 256, a quarter of them (300) to two.
    step = under_mesh(mesh, jax.jit(
        jax.value_and_grad(lambda p, b: gpt2.loss_fn(
            p, b, cfg, rules, loss_chunk=200)),
        in_shardings=(shardings_for(mesh, axes, rules), None)))
    got, got_grads = step(params, batch)

    np.testing.assert_allclose(float(got), float(want), rtol=2e-5)
    flat_want = jax.tree_util.tree_leaves_with_path(want_grads)
    flat_got = jax.tree.leaves(got_grads)
    assert len(flat_want) == len(flat_got)
    for (path, w), g in zip(flat_want, flat_got):
        w = np.asarray(w)  # leaves' scales differ 1000-fold: 2e-5 of each
        np.testing.assert_allclose(
            np.asarray(g), w, atol=2e-5 * np.abs(w).max(), rtol=2e-5,
            err_msg=jax.tree_util.keystr(path))


def _assert_leaves_close(want_grads, got_grads, scale=1.0):
    """Every leaf of ``got_grads`` is ``scale`` x its twin in
    ``want_grads``, to 2e-5 of the leaf's largest element (leaves' scales
    differ 1000-fold)."""
    for (path, w), g in zip(jax.tree_util.tree_leaves_with_path(want_grads),
                            jax.tree.leaves(got_grads), strict=True):
        w = scale * np.asarray(w)
        assert np.abs(w).max() > 0, jax.tree_util.keystr(path)
        np.testing.assert_allclose(
            np.asarray(g), w, atol=2e-5 * np.abs(w).max(), rtol=2e-5,
            err_msg=jax.tree_util.keystr(path))


@pytest.mark.parametrize("name", ["fsdp2_tp2", "one_device"])
def test_gpt2_head_applies_the_cotangent_it_is_handed(name):
    """The head's backward rule is written by hand and only SCALES what the
    forward pass kept (``gpt2._chunk_sums``): the gradient of 3 x the loss
    is 3 x the gradient of the loss, leaf by leaf (under tp the cotangent
    also crosses the vocab axis, as the transposed psums did)."""
    from ray_tpu.models import gpt2
    from ray_tpu.parallel.sharding import (prune_rules_for_mesh,
                                           shardings_for, under_mesh)

    spec, attention = HEAD_MESHES[name]
    cfg = gpt2.GPT2Config(
        vocab_size=256, max_seq=64, num_layers=1, num_heads=2, d_model=32,
        dtype=jnp.float32, remat=False, attention_impl=attention)
    params, axes = gpt2.init_params(jax.random.PRNGKey(0), cfg)
    tokens = np.array(jax.random.randint(
        jax.random.PRNGKey(1), (8, 65), 0, 256))
    tokens[2, -9:] = -1
    batch = {"tokens": jnp.asarray(tokens)}
    mesh = spec.build(jax.devices()[:spec.num_devices])
    rules = prune_rules_for_mesh(mesh)

    def grads(scale):
        return under_mesh(mesh, jax.jit(
            jax.grad(lambda p, b: scale * gpt2.loss_fn(
                p, b, cfg, rules, loss_chunk=100)),
            in_shardings=(shardings_for(mesh, axes, rules), None)))(
                params, batch)

    _assert_leaves_close(grads(1.0), grads(3.0), scale=3.0)


def test_gpt2_head_in_bfloat16_is_as_close_as_autodiff_was():
    """``dx`` and ``d wte`` of the bfloat16 head at two chunks against the
    float32 head's, by the rms of the difference over the rms of the
    value. The parent (``jax.checkpoint`` autodiff of the chunk, PR 50's
    tree, these inputs, this CPU) read 0.001656 and 0.002380. There
    autodiff's float32 ``d logits`` entered the CPU's products unrounded,
    where the MXU's default pass rounds it to bfloat16 as the head now
    does itself before both products: ``dx`` reads 0.001979, and is given
    1.25 x the parent's; ``d wte`` (0.002265: the chunks' products are
    summed in float32 and rounded once) is held to the parent's. The loss
    is the float32 head's to the bit: these inputs are bfloat16's own."""
    from ray_tpu.models import gpt2

    tokens, chunk, d, vocab = 1024, 512, 64, 512
    kx, kw, kt = jax.random.split(jax.random.PRNGKey(0), 3)
    x = jax.random.normal(kx, (4, tokens // 4, d), jnp.bfloat16)
    wte = (0.3 * jax.random.normal(kw, (vocab, d))).astype(jnp.bfloat16)
    targets = jax.random.randint(kt, (4, tokens // 4), -1, vocab)

    def head(x, wte):
        nll, count = gpt2._ce_sums_local(x, targets, wte, chunk, (), (), ())
        return nll / count

    def grads(dtype):
        loss, g = jax.jit(jax.value_and_grad(head, argnums=(0, 1)))(
            x.astype(dtype), wte.astype(dtype))
        assert all(a.dtype == dtype for a in g)
        return float(loss), [np.asarray(a, np.float32) for a in g]

    want_loss, want = grads(jnp.float32)
    loss, got = grads(jnp.bfloat16)
    assert loss == want_loss
    limits = {"dx": 1.25 * 0.001656, "d wte": 0.002380}
    for (name, limit), w, g in zip(limits.items(), want, got):
        rms = float(np.sqrt(np.mean((g - w) ** 2) / np.mean(w ** 2)))
        assert 0 < rms <= limit, (name, rms)


def test_gpt2_head_under_a_mixture_of_experts():
    """``jax.grad`` through ``loss_fn`` with ``num_experts > 0`` (the aux
    loss joins after the head, and its gradient does not pass the head's
    hand-written rule): loss and every leaf equal plain autodiff of the
    same features through whole float32 logits and one un-chunked CE."""
    from ray_tpu.models import gpt2
    from ray_tpu.models.common import cross_entropy_sums

    cfg = gpt2.GPT2Config(
        vocab_size=128, max_seq=32, num_layers=2, num_heads=2, d_model=32,
        num_experts=4, dtype=jnp.float32, remat=False,
        attention_impl="reference")
    params, _ = gpt2.init_params(jax.random.PRNGKey(0), cfg)
    tokens = np.array(jax.random.randint(
        jax.random.PRNGKey(1), (4, 33), 0, 128))
    tokens[1, -5:] = -1
    batch = {"tokens": jnp.asarray(tokens)}

    def plain_loss(p):
        x, aux = gpt2.forward_features(p, batch["tokens"][:, :-1], cfg)
        nll, count = cross_entropy_sums(x @ p["wte"].T, batch["tokens"][:, 1:])
        return nll / count + cfg.moe_aux_weight * aux / cfg.num_layers

    want, want_grads = jax.jit(jax.value_and_grad(plain_loss))(params)
    got, got_grads = jax.jit(jax.value_and_grad(
        lambda p: gpt2.loss_fn(p, batch, cfg, loss_chunk=50)))(params)
    np.testing.assert_allclose(float(got), float(want), rtol=2e-5)
    _assert_leaves_close(want_grads, got_grads)
