"""The Pallas kernels of the main path compile for the real chip.

Interpret mode (every other test) cannot see what the TPU's compiler
refuses: a slice off the tiling, more VMEM than a kernel may take, a
kernel GSPMD cannot partition. libtpu compiles for a chip that is
described and not attached (``/opt/skills/guides/on-chip-measurement``
§2.3), so these ask it — shapes, not arrays; nothing runs, and a pass is
not a chip run. Skipped where the topology cannot be described.

Code that asks JAX for its backend still sees the CPU here, so the
kernels' own ``_on_tpu`` is steered from the test.

The flash kernels here; the serving engines' step programs in
``test_tpu_compile_serving.py`` and the gpt2 train step in
``test_tpu_compile_train.py``; what the three share (the described chip,
the steering, readers of compiled text) in ``compiled_steps.py``.
"""

import jax
import jax.numpy as jnp
import pytest
from compiled_steps import (_compiled, _kernel_shapes,  # noqa: F401
                            _kernels, compiled_for_tpu, v5e)
from jax.sharding import NamedSharding, PartitionSpec as P
from jax.sharding import SingleDeviceSharding
from test_tpu_compile_train import HEAD_CASES, _gpt2_step

from ray_tpu.ops import attention as A
from ray_tpu.parallel.mesh import DEVICE_PEAKS, MeshSpec

# [batch, heads, seq, head_dim] of the training cells (bench.py): gpt2-774m
# and gpt2-1.5b at batch 8 x seq 1024, and the 355M long-context run at 16k;
# gpt2-xl.pretrain_1k_fsdp4's shape a device (batch 24 over fsdp=4).
TRAIN_SHAPES = {
    "gpt2-774m": (8, 20, 1024, 64),
    "gpt2-1.5b": (8, 25, 1024, 64),
    "seq-16k": (1, 16, 16384, 64),
    "gpt2-xl-fsdp4": (6, 25, 1024, 64),
}


def _flash_loss(q, k, v):
    return A.flash_attention(q, k, v).astype(jnp.float32).sum()


def test_described_chip_is_the_one_in_the_peaks_table(v5e):
    assert v5e[0].platform == "tpu" and len(v5e) == 4
    assert v5e[0].device_kind in DEVICE_PEAKS


@pytest.mark.parametrize("name", list(TRAIN_SHAPES))
def test_flash_forward_and_backward_compile(v5e, name):
    x = jax.ShapeDtypeStruct(TRAIN_SHAPES[name], jnp.bfloat16,
                             sharding=SingleDeviceSharding(v5e[0]))
    assert _kernels(lambda q, k, v: A.flash_attention(q, k, v), x, x, x) == 1

    # the forward that saves (o, lse) and the fused dq/dk/dv backward
    assert _kernels(jax.grad(_flash_loss, argnums=(0, 1, 2)), x, x, x) == 2


@pytest.mark.parametrize("name", list(TRAIN_SHAPES))
def test_flash_kernels_are_what_the_benchmark_looks_for(v5e, name):
    """``kernel.flash_roofline`` and ``kernel.flash_share`` find the two
    kernels in a trace by the shapes of their operands and results
    (``benchmark/trace/opsbytes.py classify_flash``): a scalar-prefetch
    operand in front of q, a backward split in two or another layout of
    q / k / v would blind both metrics on both train cells."""
    from benchmark.trace.opsbytes import classify_flash

    b, h, sq, d = TRAIN_SHAPES[name]
    x = jax.ShapeDtypeStruct((b, h, sq, d), jnp.bfloat16,
                             sharding=SingleDeviceSharding(v5e[0]))

    text = _compiled(jax.grad(_flash_loss, argnums=(0, 1, 2)), x, x, x)
    assert sorted(classify_flash(k) for k in _kernel_shapes(text)) == [
        ("bwd", b, h, sq, sq, d), ("fwd", b, h, sq, sq, d)]


@pytest.mark.parametrize("causal", [True, False])
def test_ring_flash_block_compiles(v5e, causal):
    """The (o, lse) block ``ring_flash_attention_local`` calls per ring
    step — diagonal steps causal, earlier shards not — at the local
    shape of 16k tokens over sp=4."""
    x = jax.ShapeDtypeStruct((1, 16, 4096, 64), jnp.bfloat16,
                             sharding=SingleDeviceSharding(v5e[0]))
    assert _kernels(lambda q, k, v: A.attention_with_lse(
        q, k, v, causal=causal, impl="flash"), x, x, x) == 1


def test_ring_flash_attention_compiles_over_four_chips(v5e):
    from ray_tpu.parallel.ring import ring_attention

    mesh = MeshSpec(sp=4).build(v5e)
    x = jax.ShapeDtypeStruct(
        (1, 16, 16384, 64), jnp.bfloat16,
        sharding=NamedSharding(mesh, P(None, None, "sp", None)))
    text = jax.jit(lambda q, k, v: ring_attention(
        q, k, v, mesh, causal=True, batch_axes=(), heads_axis=None,
        impl="flash")).lower(x, x, x).compile().as_text()
    assert text.count("tpu_custom_call") == 2  # causal + full block
    assert "collective-permute" in text  # the K/V rotation


def test_flash_kernel_is_sharded_not_partitioned(v5e):
    """GSPMD refuses to partition a Mosaic kernel, so under a mesh
    ``attention`` runs it per (batch, heads) shard in a shard_map — and
    without that wrapper the compiler's refusal is an error, not a
    quiet reference run."""
    mesh = MeshSpec(fsdp=2, tp=2).build(v5e)
    spec = P("fsdp", "tp", None, None)
    x = jax.ShapeDtypeStruct(TRAIN_SHAPES["gpt2-774m"], jnp.bfloat16,
                             sharding=NamedSharding(mesh, spec))
    assert _kernels(lambda q, k, v: A.attention(
        q, k, v, impl="flash", mesh=mesh, spec=spec), x, x, x) == 1
    with pytest.raises(NotImplementedError, match="shard_map"):
        _kernels(lambda q, k, v: A.attention(q, k, v, impl="flash"),
                 x, x, x)


def test_explicit_flash_raises_on_a_shape_it_cannot_tile():
    """seq 768 is not a multiple of the 512 block: under 'flash' that is
    an error; under 'auto' it is the reference."""
    x = jnp.zeros((1, 2, 768, 64), jnp.bfloat16)
    with pytest.raises(ValueError, match="cannot tile"):
        A.attention(x, x, x, impl="flash")
    with pytest.raises(ValueError, match="cannot tile"):
        A.attention_with_lse(x, x, x, impl="flash")
    assert A.attention(x, x, x, impl="auto").shape == x.shape


@pytest.mark.parametrize("case", ["one_chip", "fsdp4"])
def test_whole_train_step_fits_its_chips(v5e, case):
    """The cells' steps at full depth with their optimizers compile for
    the described chips, which the compiler refuses over a chip's memory
    (a layout that is the kernel's but not lane-dense read 'Used 16.21G
    of 15.75G hbm'); compiled here, once, for the comparison below too."""
    mem = _gpt2_step(v5e, case, HEAD_CASES[case]["depth"]).memory_analysis()
    held = (mem.argument_size_in_bytes + mem.output_size_in_bytes
            - mem.alias_size_in_bytes + mem.temp_size_in_bytes)
    assert held <= 15.75 * 2**30, mem


@pytest.mark.parametrize("case", ["one_chip", "fsdp4"])
def test_whole_train_step_needs_no_more_memory_than_unpacked(v5e, case):
    """Temporaries a device no more than the unpacked block's, compiled
    beside it at full depth (it holds q, k, v and o in 64-lane rows padded
    to 128 beside the ``[b, s, 3d]`` save)."""
    depth = HEAD_CASES[case]["depth"]
    temp, unpacked = (
        _gpt2_step(v5e, case, depth, packed).memory_analysis()
        .temp_size_in_bytes for packed in (True, False))
    assert temp <= unpacked, (temp, unpacked)
