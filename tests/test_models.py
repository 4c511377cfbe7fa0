"""Model tests: shapes, loss decrease, llama decode-vs-forward parity."""

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest


def _same_structure(params, axes):
    """Axes leaves are tuples (pytree nodes), so compare with is_leaf."""
    s1 = jax.tree.structure(params)
    s2 = jax.tree.structure(
        axes, is_leaf=lambda x: isinstance(x, tuple)
    )
    return s1 == s2


def test_gpt2_forward_shapes():
    from ray_tpu.models import gpt2

    cfg = gpt2.GPT2Config(vocab_size=128, max_seq=32, num_layers=2,
                          num_heads=2, d_model=32, dtype=jnp.float32,
                          attention_impl="reference")
    params, axes = gpt2.init_params(jax.random.PRNGKey(0), cfg)
    assert _same_structure(params, axes)
    tokens = jnp.zeros((2, 16), jnp.int32)
    logits = gpt2.forward(params, tokens, cfg)
    assert logits.shape == (2, 16, 128)


def test_resnet_cifar_train_step():
    from ray_tpu.models import resnet

    cfg = resnet.ResNetConfig(stage_sizes=(1, 1), width=8, num_classes=10,
                              dtype=jnp.float32)
    params, stats = resnet.init_params(jax.random.PRNGKey(0), cfg)
    images = jax.random.normal(jax.random.PRNGKey(1), (4, 32, 32, 3))
    labels = jnp.array([0, 1, 2, 3])
    batch = {"image": images, "label": labels}

    import optax

    opt = optax.adam(1e-2)
    opt_state = opt.init(params)

    @jax.jit
    def step(params, stats, opt_state):
        (loss, (new_stats, acc)), grads = jax.value_and_grad(
            resnet.loss_fn, has_aux=True)(params, stats, batch, cfg)
        updates, opt_state = opt.update(grads, opt_state)
        params = optax.apply_updates(params, updates)
        return params, new_stats, opt_state, loss

    losses = []
    for _ in range(6):
        params, stats, opt_state, loss = step(params, stats, opt_state)
        losses.append(float(loss))
    assert losses[-1] < losses[0]


def test_vit_forward_and_loss():
    from ray_tpu.models import vit

    cfg = vit.ViTConfig(image_size=32, patch_size=8, num_layers=2,
                        num_heads=2, d_model=32, d_mlp=64, num_classes=10,
                        dtype=jnp.float32, remat=False)
    params, axes = vit.init_params(jax.random.PRNGKey(0), cfg)
    assert _same_structure(params, axes)
    images = jax.random.normal(jax.random.PRNGKey(1), (2, 32, 32, 3))
    logits = vit.forward(params, images, cfg)
    assert logits.shape == (2, 10)
    loss = vit.loss_fn(params, {"image": images,
                                "label": jnp.array([1, 2])}, cfg)
    assert np.isfinite(float(loss))


def test_llama_forward_and_loss():
    from ray_tpu.models import llama

    cfg = llama.CONFIGS["llama-tiny"]
    params, axes = llama.init_params(jax.random.PRNGKey(0), cfg)
    assert _same_structure(params, axes)
    tokens = jax.random.randint(jax.random.PRNGKey(1), (2, 17), 0,
                                cfg.vocab_size)
    loss = llama.loss_fn(params, {"tokens": tokens}, cfg)
    assert np.isfinite(float(loss))


def test_llama_decode_matches_forward():
    """KV-cache decode logits must match full-forward logits."""
    from ray_tpu.models import llama

    cfg = llama.CONFIGS["llama-tiny"]
    params, _ = llama.init_params(jax.random.PRNGKey(0), cfg)
    tokens = jax.random.randint(jax.random.PRNGKey(2), (1, 8), 0,
                                cfg.vocab_size)
    full = llama.forward(params, tokens, cfg)  # [1, 8, V]

    cache = llama.init_kv_cache(cfg, 1)
    step_logits = []
    for i in range(8):
        logits, cache = llama.decode_step(params, cache, tokens[:, i],
                                          jnp.asarray(i), cfg)
        step_logits.append(logits)
    stepwise = jnp.stack(step_logits, axis=1)
    np.testing.assert_allclose(np.asarray(full), np.asarray(stepwise),
                               atol=2e-3, rtol=2e-3)


def test_llama_generate():
    from ray_tpu.models import llama

    cfg = llama.CONFIGS["llama-tiny"]
    params, _ = llama.init_params(jax.random.PRNGKey(0), cfg)
    prompt = jax.random.randint(jax.random.PRNGKey(3), (2, 4), 0,
                                cfg.vocab_size)
    out = llama.generate(params, prompt, cfg, max_new=5)
    assert out.shape == (2, 9)


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16],
                         ids=["f32", "bf16"])
@pytest.mark.parametrize("heads,t", [(32, 1), (32, 64), (4, 1), (4, 72)])
def test_llama_rope_lanes_is_rope_bit_for_bit(heads, t, dtype):
    """The serving step's rotary step on the flat lanes of a projection's
    output, [B, T, heads * hd], against the strided reference ``rope`` on
    [B, heads, T, hd]: the same bits, for the widths of
    q and of grouped K (32 and 4 heads of 64), one decode token a row and
    a prompt chunk, rows at their own positions."""
    from ray_tpu.models import llama

    b, hd, theta = 3, 64, 130000.0
    x = (jax.random.normal(jax.random.PRNGKey(heads + t), (b, t, heads, hd))
         * 3).astype(dtype)
    positions = (jnp.asarray([0, 5, 1900])[:, None] + jnp.arange(t))

    def strided(x, positions):
        return llama.rope(x.transpose(0, 2, 1, 3), positions,
                          theta).transpose(0, 2, 1, 3)

    def lanes(x, positions):
        tables = llama.rope_lane_tables(positions, heads, hd, theta)
        return llama.rope_lanes(x.reshape(b, t, heads * hd),
                                tables).reshape(x.shape)

    # Bit for bit op by op. Jitted, XLA's CPU backend contracts either
    # function's products and sum into fused multiply-adds as its fusions
    # fall, and compiles its own cos / sin: the last place moves, for
    # both alike.
    got, want = lanes(x, positions), strided(x, positions)
    assert got.dtype == want.dtype
    np.testing.assert_array_equal(np.asarray(got.astype(jnp.float32)),
                                  np.asarray(want.astype(jnp.float32)))
    ulp = 2.0 ** (-7 if dtype == jnp.bfloat16 else -20)
    np.testing.assert_allclose(
        np.asarray(jax.jit(lanes)(x, positions).astype(jnp.float32)),
        np.asarray(jax.jit(strided)(x, positions).astype(jnp.float32)),
        rtol=ulp, atol=ulp)


# -- gpt2's layer loop where it owns its backward pass --------------------------

def _checkpointed_scan(cfg, rules):
    """``gpt2._blocks_saving``'s twin as the layer loop was before it:
    ``jax.checkpoint`` of the block under the ``mem2`` policy's names and
    ``lax.scan``, differentiated by JAX."""
    from functools import partial

    from ray_tpu.models import gpt2

    block = jax.checkpoint(
        partial(gpt2._block, cfg=cfg, rules=rules),
        policy=jax.checkpoint_policies.save_only_these_names(
            "qkv", "attn_out", "attn_lse"))

    def run(blocks, x):
        def body(carry, layer):
            x, a = block(carry[0], layer)
            return (x, carry[1] + a), None

        return jax.lax.scan(body, (x, jnp.zeros((), jnp.float32)),
                            blocks)[0]

    return run


def _gpt2_loss_and_grads(cfg, params, axes, batch, mesh):
    """Loss and gradients of ``gpt2.loss_fn`` on one device (``mesh`` None)
    or with the parameters placed on ``mesh`` by the model's rules."""
    from ray_tpu.models import gpt2
    from ray_tpu.parallel.sharding import (prune_rules_for_mesh,
                                           shardings_for, under_mesh)

    if mesh is None:
        return jax.jit(jax.value_and_grad(
            lambda p, b: gpt2.loss_fn(p, b, cfg)))(params, batch)
    rules = prune_rules_for_mesh(mesh)
    return under_mesh(mesh, jax.jit(
        jax.value_and_grad(lambda p, b: gpt2.loss_fn(p, b, cfg, rules)),
        in_shardings=(shardings_for(mesh, axes, rules), None)))(params, batch)


@pytest.mark.parametrize("mesh", ["one_device", "fsdp2"])
@pytest.mark.parametrize("kind", ["dense", "experts2", "dense_5_heads"])
@pytest.mark.parametrize("layers", [2, 3])
def test_gpt2_owned_backward_is_the_checkpointed_scans(layers, kind, mesh):
    """Loss and every gradient leaf of ``gpt2.loss_fn`` under ``mem2``
    with the flash kernel (``_blocks_saving``: the forward scan stacks each
    layer's input and kernel operands, the backward scan hands the kernel
    the stacks and a layer number) against ``jax.checkpoint`` and
    ``lax.scan`` over the same block differentiated by JAX, float32. With
    two experts the router's ``aux`` is summed through both scans and its
    cotangent reaches every layer; 5 heads: a zero head fills the last
    packed row; under fsdp=2 the kernels run a shard each and the stacks
    are split by batch. The same sums in the same order: not round-off
    apart but equal."""
    from unittest import mock

    from ray_tpu.models import gpt2
    from ray_tpu.parallel.mesh import MeshSpec

    heads = 5 if kind == "dense_5_heads" else 4
    cfg = gpt2.GPT2Config(
        vocab_size=128, max_seq=128, num_layers=layers, num_heads=heads,
        d_model=64 * heads, dtype=jnp.float32, attention_impl="flash",
        remat=True, remat_policy="mem2",
        num_experts=2 if kind == "experts2" else 0)
    params, axes = gpt2.init_params(jax.random.PRNGKey(layers), cfg)
    batch = {"tokens": jax.random.randint(
        jax.random.PRNGKey(7), (2, 129), 0, cfg.vocab_size)}
    mesh = None if mesh == "one_device" \
        else MeshSpec(fsdp=2).build(jax.devices()[:2])
    with mock.patch.object(gpt2, "_blocks_saving",
                           wraps=gpt2._blocks_saving) as owned:
        loss, grads = _gpt2_loss_and_grads(cfg, params, axes, batch, mesh)
    assert owned.call_count == 1
    with mock.patch.object(gpt2, "_blocks_saving", _checkpointed_scan):
        want, want_grads = _gpt2_loss_and_grads(cfg, params, axes, batch,
                                                mesh)
    assert float(loss) == float(want)
    assert jax.tree.structure(grads) == jax.tree.structure(params)
    for (path, g), w in zip(jax.tree_util.tree_leaves_with_path(grads),
                            jax.tree.leaves(want_grads), strict=True):
        assert g.shape == w.shape and g.dtype == w.dtype, path
        assert float(jnp.abs(w).max()) > 0, path
        np.testing.assert_array_equal(
            g, w, err_msg=jax.tree_util.keystr(path))


# what keeps ``jax.checkpoint`` under ``lax.scan``: (configuration, mesh)
NOT_OWNED = {
    **{policy: (dict(remat_policy=policy), {})
       for policy in ("dots", "dots_attn", "mem", "full")},
    "none": (dict(remat_policy="none"), {}),
    "no_remat": (dict(remat=False), {}),
    "reference": (dict(attention_impl="reference"), {}),
    "auto_off_the_tpu": (dict(attention_impl="auto"), {}),
    "ring": (dict(attention_impl="ring"), dict(sp=2)),
    "ulysses": (dict(attention_impl="ulysses"), dict(sp=2)),
    "tp": ({}, dict(tp=2)),
    "fsdp_tp": ({}, dict(fsdp=2, tp=2)),
}


@pytest.mark.parametrize("setting", list(NOT_OWNED))
def test_gpt2_owned_backward_is_mem2s_over_packed_rows_alone(setting):
    """Every other policy saves or recomputes something else than the
    kernel's operands, and the reference, ring, ulysses and a mesh that
    shards the heads hand no kernel packed rows: each keeps
    ``jax.checkpoint`` under ``lax.scan``, and differentiates."""
    from unittest import mock

    from ray_tpu.models import gpt2
    from ray_tpu.parallel.mesh import MeshSpec
    from ray_tpu.parallel.sharding import prune_rules_for_mesh, under_mesh

    changed, axes = NOT_OWNED[setting]
    cfg = dataclasses.replace(gpt2.GPT2Config(
        vocab_size=128, max_seq=128, num_layers=2, num_heads=4, d_model=256,
        dtype=jnp.float32, attention_impl="flash", remat=True,
        remat_policy="mem2"), **changed)
    spec = MeshSpec(**axes)
    mesh = spec.build(jax.devices()[:spec.num_devices])
    rules = prune_rules_for_mesh(mesh)
    params, _ = gpt2.init_params(jax.random.PRNGKey(0), cfg)
    tokens = jax.random.randint(jax.random.PRNGKey(7), (2, 128), 0, 128)
    with mock.patch.object(gpt2, "_blocks_saving") as owned:
        grads = under_mesh(mesh, jax.eval_shape)(
            jax.grad(lambda p: gpt2.forward_features(
                p, tokens, cfg, rules)[0].sum()), params)
    assert owned.call_count == 0
    assert jax.tree.structure(grads) == jax.tree.structure(params)
    # ... and the configuration these were changed from does take it
    owned_cfg = dataclasses.replace(cfg, **{k: getattr(
        gpt2.GPT2Config(attention_impl="flash", remat_policy="mem2"), k)
        for k in changed})
    assert gpt2._owns_backward(owned_cfg, 128, None)
    assert not under_mesh(mesh, gpt2._owns_backward)(cfg, 128, rules)


def test_gpt2_keeps_its_stacked_parameters_shardings_and_checkpoint(
        tmp_path):
    """The loop that owns its backward pass, over two devices (the kernels
    a shard each, the saved stacks split by batch): what is stored,
    sharded, updated and saved is ``[layers, ...]`` a leaf, under the
    ``layers`` logical axis, as before."""
    from ray_tpu.models import gpt2
    from ray_tpu.parallel.mesh import MeshSpec
    from ray_tpu.train.checkpoint import restore_arrays, save_arrays
    from ray_tpu.train.step import build_sharded_train

    cfg = gpt2.GPT2Config(
        vocab_size=128, max_seq=32, num_layers=3, num_heads=2, d_model=32,
        dtype=jnp.float32, attention_impl="flash", remat_policy="mem2")
    mesh = MeshSpec(fsdp=2).build(jax.devices()[:2])
    sinit, sstep, _ = build_sharded_train(
        lambda key: gpt2.init_params(key, cfg),
        lambda p, b: gpt2.loss_fn(p, b, cfg), mesh)
    params, opt_state, step = sinit(jax.random.PRNGKey(0))
    shapes = {k: v.shape for k, v in params["blocks"].items()}
    assert shapes == {
        "ln1_scale": (3, 32), "ln1_bias": (3, 32), "qkv_w": (3, 32, 96),
        "qkv_b": (3, 96), "proj_w": (3, 32, 32), "proj_b": (3, 32),
        "ln2_scale": (3, 32), "ln2_bias": (3, 32), "mlp_in_w": (3, 32, 128),
        "mlp_in_b": (3, 128), "mlp_out_w": (3, 128, 32),
        "mlp_out_b": (3, 32)}
    specs = {k: tuple(v.sharding.spec) + (None,) * (
        v.ndim - len(v.sharding.spec)) for k, v in params["blocks"].items()}
    assert specs["qkv_w"] == (None, "fsdp", None), specs
    assert specs["mlp_out_w"] == (None, None, "fsdp"), specs
    tokens = jax.random.randint(jax.random.PRNGKey(1), (4, 33), 0, 128)
    stepped, opt_state, step, m = sstep(params, opt_state, step,
                                        {"tokens": tokens})
    assert np.isfinite(float(m["loss"]))
    assert jax.tree.structure(stepped) == jax.tree.structure(params)
    for old, new in zip(jax.tree.leaves(params), jax.tree.leaves(stepped)):
        assert (new.shape, new.dtype, new.sharding.spec) == (
            old.shape, old.dtype, old.sharding.spec)
    save_arrays(str(tmp_path / "ckpt"), stepped)
    restored = restore_arrays(str(tmp_path / "ckpt"))
    assert jax.tree.structure(restored) == jax.tree.structure(stepped)
    for saved, back in zip(jax.tree.leaves(stepped),
                           jax.tree.leaves(restored)):
        np.testing.assert_array_equal(np.asarray(saved), np.asarray(back))
