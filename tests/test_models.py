"""Model tests: shapes, loss decrease, llama decode-vs-forward parity."""

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
