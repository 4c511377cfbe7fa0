"""The Pallas kernels of the main path compile for the real chip.

Interpret mode (every other test) cannot see what the TPU's compiler
refuses: a slice off the tiling, more VMEM than a kernel may take, a
kernel GSPMD cannot partition. libtpu compiles for a chip that is
described and not attached (``/opt/skills/guides/on-chip-measurement``
§2.3), so these ask it — shapes, not arrays; nothing runs, and a pass is
not a chip run. Skipped where the topology cannot be described.

Code that asks JAX for its backend still sees the CPU here, so the
kernels' own ``_on_tpu`` is steered from the test.
"""

import math
import os
import re

os.environ.setdefault("TPU_LOG_DIR", "disabled")  # else it logs under /tmp

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import NamedSharding, PartitionSpec as P
from jax.sharding import SingleDeviceSharding

from ray_tpu.llm.engine import (HostInputs, SlotEngine, build_step_programs,
                                prefill_lane)
from ray_tpu.models import granite, lfm2, llama, serving, solar
from ray_tpu.ops import attention as A
from ray_tpu.ops import delta_rule as DR
from ray_tpu.ops import grouped_matmul as GM
from ray_tpu.ops import paged_attention as PA
from ray_tpu.ops import ssm_scan as SS
from ray_tpu.parallel.mesh import DEVICE_PEAKS, MeshSpec
from ray_tpu.parallel.sharding import (prune_rules_for_mesh, shardings_for,
                                       under_mesh)

# [batch, heads, seq, head_dim] of the training cells (bench.py): gpt2-774m
# and gpt2-1.5b at batch 8 x seq 1024, and the 355M long-context run at 16k;
# gpt2-xl.pretrain_1k_fsdp4's shape a device (batch 24 over fsdp=4).
TRAIN_SHAPES = {
    "gpt2-774m": (8, 20, 1024, 64),
    "gpt2-1.5b": (8, 25, 1024, 64),
    "seq-16k": (1, 16, 16384, 64),
    "gpt2-xl-fsdp4": (6, 25, 1024, 64),
}


@pytest.fixture(scope="module")
def v5e():
    try:
        from jax.experimental import topologies

        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001 — no libtpu, no description
        pytest.skip(f"cannot describe a v5e:2x2 topology: {e}")
    return topo.devices


@pytest.fixture(autouse=True)
def compiled_for_tpu(monkeypatch):
    """Kernels take their compiled (not interpreted) branch, and the
    persistent cache stays out of it: a program compiled for a described
    chip is written there but cannot be read back without one."""
    from jax.experimental.compilation_cache import compilation_cache

    monkeypatch.setattr(A, "_on_tpu", lambda: True)
    monkeypatch.setattr(PA, "_on_tpu", lambda: True)
    monkeypatch.setattr(GM, "_on_tpu", lambda: True)
    monkeypatch.setattr(DR, "_on_tpu", lambda: True)
    monkeypatch.setattr(SS, "_on_tpu", lambda: True)
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield
    jax.config.update("jax_enable_compilation_cache", was)
    compilation_cache.reset_cache()


def _compiled(fn, *specs) -> str:
    """``fn`` compiled for the specs' (described) devices, as HLO text."""
    return jax.jit(fn).lower(*specs).compile().as_text()


def _kernels(fn, *specs) -> int:
    """How many Mosaic kernels the compiled ``fn`` holds."""
    return _compiled(fn, *specs).count("tpu_custom_call")


def _kernel_shapes(text):
    """Each Mosaic kernel of a compiled program as the benchmark's trace
    reduction hands it on (``parse_op``: outputs and operands with their
    shapes). A trace event spells the operands' shapes inside the call;
    compiled text has them in ``operand_layout_constraints``."""
    from benchmark.trace.reduce import parse_op

    out = []
    for line in text.splitlines():
        if 'custom_call_target="tpu_custom_call"' not in line:
            continue
        head, _, rest = line.strip().partition(" custom-call(")
        operands = rest.partition("operand_layout_constraints={")[2]
        out.append(parse_op(
            f"{head} custom-call({operands.partition('}}')[0]}}})"))
    return out


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


# -- the serving engine's two step programs -----------------------------------

# The published smollm2-1.7b widths (benchmark/configs/smollm2-1.7b.json)
# at two layers, with the deployment's 8 slots and its whole pool of
# 8 x 2048 / 16 + 1 pages: what a layer does to the pool does not depend
# on how many layers there are.
SMOLLM2_2L = llama.LlamaConfig(
    vocab_size=49152, max_seq=2048, num_layers=2, num_heads=32,
    num_kv_heads=32, d_model=2048, d_mlp=8192, rope_theta=130000.0,
    dtype=jnp.bfloat16, remat=False)
# llama-1b's widths (``llama.CONFIGS``): grouped KV heads, so wk and wv
# are [d, 256] beside wq's [d, d].
LLAMA1B_2L = llama.LlamaConfig(
    vocab_size=32000, max_seq=2048, num_layers=2, num_heads=32,
    num_kv_heads=4, d_model=2048, d_mlp=5632, dtype=jnp.bfloat16,
    remat=False)
ENGINE_CONFIGS = {"smollm2": SMOLLM2_2L, "llama-1b": LLAMA1B_2L}
SLOTS, PAGE, CHUNK = 8, 16, 64
_COLLECTIVE = re.compile(
    r"= (.*?) (all-reduce|all-gather|reduce-scatter|all-to-all|"
    r"collective-permute)(?:-start)?\(")


def _derived_lane(v5e, cfg):
    """The prefill lane ``SlotEngine`` gives itself on the described chip
    when its caller names none."""
    return prefill_lane(serving.model_for(cfg).one_program,
                        DEVICE_PEAKS[v5e[0].device_kind], cfg.dtype,
                        cfg.max_seq)


def _engine_program_specs(cfg, sharding, mesh_rules=None, lane=CHUNK):
    """Shapes of both programs' arguments, every one on ``sharding``;
    with ``mesh_rules`` = (mesh, rules) the params and the cache are laid
    over the mesh as ``SlotEngine`` places them. ``lane``: the fused
    program's prompt chunk."""
    def sds(shape, dtype, where=sharding):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=where)

    # params and cache as the engine gets them: from the family's record
    model = serving.model_for(cfg)
    pages = SLOTS * cfg.max_seq // PAGE + 1
    params = jax.eval_shape(
        lambda: model.init_params(jax.random.PRNGKey(0), cfg)[0])
    cache = jax.eval_shape(lambda: model.init_cache(cfg, pages, PAGE))
    if mesh_rules is None:
        placed = [jax.tree.map(lambda x: sharding, t)
                  for t in (params, cache)]
    else:
        placed = [shardings_for(mesh_rules[0], axes, mesh_rules[1])
                  for axes in (model.param_axes(), model.cache_axes)]
    params = jax.tree.map(lambda x, w: sds(x.shape, cfg.dtype, w),
                          params, placed[0])
    cache = jax.tree.map(lambda x, w: sds(x.shape, x.dtype, w),
                         cache, placed[1])
    (pool,) = jax.tree.leaves(cache)
    # params, cache, the last tokens, and the ONE packed vector of
    # everything a dispatch hands over (rows, page table, lane)
    i32 = lambda *shape: sds(shape, jnp.int32)  # noqa: E731
    common = (params, cache, i32(SLOTS))
    tables = cfg.max_seq // PAGE
    return {"block": common + (i32(HostInputs(SLOTS, tables, lane).size),),
            "decode_only": common + (i32(HostInputs(SLOTS, tables).size),)
            }, pool


_COMPILED_STEPS = {}


def _compiled_step(v5e, config, program):
    """``(compiled, pool's shape)`` of one engine program on one chip at
    a configuration's widths; compiled once for the tests that read it.
    ``block`` carries the lane the tests here have always compiled
    (``CHUNK``), ``block-derived`` the one the engine derives for this
    chip: 256, which is what a deployment that names no ``chunk`` runs."""
    if (config, program) not in _COMPILED_STEPS:
        cfg = ENGINE_CONFIGS[config]
        lane = _derived_lane(v5e, cfg) if program == "block-derived" else CHUNK
        specs, pool = _engine_program_specs(
            cfg, SingleDeviceSharding(v5e[0]), lane=lane)
        block_fn, decode_only_fn = build_step_programs(cfg, PAGE, 1, SLOTS,
                                                       lane)
        which = "decode_only" if program == "decode_only" else "block"
        fn = {"block": block_fn, "decode_only": decode_only_fn}[which]
        _COMPILED_STEPS[config, program] = jax.jit(
            fn, donate_argnums=(1,)).lower(*specs[which]).compile(), pool
    return _COMPILED_STEPS[config, program]


PROGRAMS = ["block", "decode_only", "block-derived"]


def test_derived_lane_on_the_described_chip(v5e):
    """256 prompt tokens a step for the two-program family, the 64 the
    tests below compile for the family that carries its lane always."""
    assert _derived_lane(v5e, SMOLLM2_2L) == 256
    assert _derived_lane(v5e, LLAMA1B_2L) == 256
    assert _derived_lane(v5e, lfm2.Lfm2Config()) == CHUNK


@pytest.mark.parametrize("program", PROGRAMS)
def test_engine_programs_touch_the_pool_only_in_place(v5e, program):
    """As compiled for the chip, a step holds the Mosaic kernel and no
    copy or fusion whose result is the pool or one layer's slice of it,
    and its temporaries are a small fraction of the pool's bytes: the
    pool is the layer loop's carry, aliased through the kernel, and never
    laid out again. (Before the kernel each program copied the whole pool
    several times a step and held a temporary the size of it.)"""
    compiled, pool = _compiled_step(v5e, "smollm2", program)
    text = compiled.as_text()
    # decode rows, and in the fused program the prompt chunk's lane
    assert text.count("tpu_custom_call") == (
        1 if program == "decode_only" else 2)
    shapes = {",".join(map(str, pool.shape)),          # the pool
              ",".join(map(str, (1,) + pool.shape[1:])),  # a layer of it
              ",".join(map(str, pool.shape[1:]))}
    moved = [line.strip()[:120] for line in text.splitlines()
             for m in [re.match(r"\s*(?:ROOT )?\S+ = \w+\[([\d,]+)\]\S* "
                                r"(copy|fusion|scatter|gather|"
                                r"dynamic-slice|dynamic-update-slice)\(",
                                line)]
             if m and m.group(1) in shapes]
    assert not moved, moved
    pool_bytes = pool.dtype.itemsize * math.prod(pool.shape)
    assert compiled.memory_analysis().temp_size_in_bytes < pool_bytes // 4


# The published LFM2-24B-A2B widths at one lead layer and one whole period
# (conv + dense FFN, then attention and three conv layers with 64 experts
# each): what a layer does to the pool and to the slots' state does not
# depend on how many periods there are. The cell's 64 slots: the step's
# temporaries grow with the rows of a step, not with the pool.
LFM2_1P = lfm2.Lfm2Config(
    layer_types=("conv", "full_attention", "conv", "conv", "conv"),
    num_dense_layers=1)
LFM2_SLOTS = 64


@pytest.mark.parametrize("program", ["block", "decode_only"])
def test_lfm2_programs_touch_pool_and_slot_state_only_in_place(v5e, program):
    """The second family's two step programs are held to what the first's
    are: the donated cache (the attention layers' page pool AND each conv
    layer's state a slot) is aliased to the output, no copy or fusion
    gives a pool-shaped result, and the temporaries are a small fraction
    of the pool. The experts' grouped products are Mosaic kernels too
    (``ops/grouped_matmul.py``, two a layer), fed each layer's weights
    where they lie: no un-fused copy or slice of an expert layer's
    weights."""
    cfg = LFM2_1P
    where = SingleDeviceSharding(v5e[0])
    model = serving.model_for(cfg)
    pages = LFM2_SLOTS * cfg.max_seq // PAGE + 1
    params = jax.eval_shape(
        lambda: model.init_params(jax.random.PRNGKey(0), cfg)[0])
    cache = jax.eval_shape(lambda: model.slot_state.attach(
        cfg, model.init_cache(cfg, pages, PAGE), LFM2_SLOTS))
    sds = lambda x: jax.ShapeDtypeStruct(  # noqa: E731
        x.shape, x.dtype, sharding=where)
    params, cache = (jax.tree.map(sds, t) for t in (params, cache))
    arg = lambda shape, dtype=jnp.int32: jax.ShapeDtypeStruct(  # noqa: E731
        shape, dtype, sharding=where)
    common = (params, cache, arg((LFM2_SLOTS,)))
    tables = cfg.max_seq // PAGE
    fused = common + (arg((HostInputs(LFM2_SLOTS, tables, CHUNK).size,)),)
    common += (arg((HostInputs(LFM2_SLOTS, tables).size,)),)
    block_fn, decode_only_fn = build_step_programs(cfg, PAGE, 1, LFM2_SLOTS,
                                                   CHUNK)
    fn, specs = ((block_fn, fused) if program == "block"
                 else (decode_only_fn, common))
    compiled = jax.jit(fn, donate_argnums=(1,)).lower(*specs).compile()
    text = compiled.as_text()
    # one attention layer's kernel a lane; two grouped products an
    # expert layer
    lanes = 2 if program == "block" else 1
    assert text.count("tpu_custom_call") == lanes + 2 * 4
    pool = cache["kv"]
    shapes = {",".join(map(str, pool.shape)),
              ",".join(map(str, (1,) + pool.shape[1:])),
              ",".join(map(str, pool.shape[1:]))}
    e, d, f = cfg.num_experts, cfg.d_model, cfg.d_expert
    shapes |= {"%d,%d,%d" % dims for dims in ((e, d, 2 * f), (e, f, d))}
    moved = [line.strip()[:120] for line in _unfused_lines(text)
             for m in [re.match(r"\s*(?:ROOT )?\S+ = \w+\[([\d,]+)\]\S* "
                                r"(copy|fusion|scatter|gather|transpose|"
                                r"slice|dynamic-slice|dynamic-update-slice)"
                                r"\(", line)]
             if m and m.group(1) in shapes]
    assert not moved, moved
    mem = compiled.memory_analysis()
    cache_bytes = sum(x.dtype.itemsize * math.prod(x.shape)
                      for x in jax.tree.leaves(cache))
    pool_bytes = pool.dtype.itemsize * math.prod(pool.shape)
    assert mem.alias_size_in_bytes == cache_bytes   # pool and state alike
    assert mem.temp_size_in_bytes < pool_bytes // 4


# Solar-Open2-250B at its published widths, this chip's share of the
# cell's deployment (20 of 320 routed experts, an eighth of the
# vocabulary, 1280 positions, 128 slots) and ONE whole period: what a
# layer does to the pool and to the slots' state does not depend on how
# many periods there are.
SOLAR_1P = solar.SolarConfig(
    max_seq=1280, layer_types=solar.PERIOD, experts_held=(100, 20),
    vocab_held=(0, 24576))
SOLAR_SLOTS = 128


def test_solar_step_updates_the_matrix_state_in_place(v5e):
    """The third family's one step program at the cell's geometry: the
    donated cache (the GQA layers' pages at head dim 128, the KDA layers'
    ``[3, 128, 64, 128, 128]`` float32 matrix states, the convolutions'
    windows) is aliased to the output whole; nothing but the delta-rule
    kernel has a state-shaped result (no copy, no slice of a layer, no
    scatter); the temporaries of a step stay under half a GB beside 1.6 GB
    of state (3.3 GB at the cell's two periods); and the kernels are the
    ones counted: paged attention for the decode rows and for the lane,
    ONE delta-rule call a KDA layer for both, two grouped products an
    expert layer; and under ``kda.scan`` XLA runs the step's plan, once,
    and nothing that lays an operand of the kernel out."""
    cfg = SOLAR_1P
    where = SingleDeviceSharding(v5e[0])
    model = serving.model_for(cfg)
    pages = SOLAR_SLOTS * cfg.max_seq // PAGE + 1
    params = jax.eval_shape(
        lambda: model.init_params(jax.random.PRNGKey(0), cfg)[0])
    cache = jax.eval_shape(lambda: model.slot_state.attach(
        cfg, model.init_cache(cfg, pages, PAGE), SOLAR_SLOTS))
    sds = lambda x: jax.ShapeDtypeStruct(  # noqa: E731
        x.shape, x.dtype, sharding=where)
    params, cache = (jax.tree.map(sds, t) for t in (params, cache))
    arg = lambda shape: jax.ShapeDtypeStruct(  # noqa: E731
        shape, jnp.int32, sharding=where)
    tables = cfg.max_seq // PAGE
    specs = (params, cache, arg((SOLAR_SLOTS,)),
             arg((HostInputs(SOLAR_SLOTS, tables, CHUNK).size,)))
    block_fn, _ = build_step_programs(cfg, PAGE, 1, SOLAR_SLOTS, CHUNK)
    compiled = jax.jit(block_fn, donate_argnums=(1,)).lower(*specs).compile()
    text = compiled.as_text()
    # decode rows and the lane: 1 GQA layer's paged kernel twice, 3 KDA
    # layers' delta rule once each; two grouped products in each of the 4
    # expert layers
    assert text.count("tpu_custom_call") == 2 * 1 + 1 * 3 + 2 * 4
    # between the convolutions and the kernel the rows stay as they are:
    # no float32 array with a 128 in its last two dimensions (a [..,
    # heads, 128] row tile or its transpose) is transposed, padded,
    # concatenated, sorted or copied under the scope, fused or not, and
    # the plan's sort is the step's, not a layer's
    scan = [line for line in text.splitlines() if "kda.scan" in line]
    assert sum(" sort(" in line for line in scan) <= 1
    laid_out = [line.strip()[:160] for line in scan
                for m in [re.match(r"\s*(?:ROOT )?\S+ = f32\[([\d,]+)\]\S* "
                                   r"(transpose|pad|concatenate|sort|copy)\(",
                                   line)]
                if m and "128" in m.group(1).split(",")[-2:]]
    assert not laid_out, laid_out
    # the convolutions a tap at a time on [slots, channels] tiles: no
    # result channels x slots, none with a tap beside the channels
    assert not _shaped(text.splitlines(), "kda.conv/", (
        "f32[24576,128]", "f32[128,3,24576]", "bf16[128,3,24576]"))
    state = cache["kda"]
    assert state.shape == (3, SOLAR_SLOTS, 64, 128, 128)
    assert state.dtype == jnp.float32
    shape = ",".join(map(str, state.shape))
    made = [line.strip()[:160] for line in text.splitlines()
            if re.match(r"\s*(?:ROOT )?\S+ = f32\[" + shape + r"\]", line)
            and not re.search(r" (custom-call|parameter|get-tuple-element|"
                              r"bitcast)\(", line)]
    assert not made, made
    # nor a layer's states or a slot's cut out of it
    parts = {",".join(map(str, dims)) for dims in (
        state.shape[1:], (1,) + state.shape[1:], state.shape[2:],
        (1,) + state.shape[2:], (1, 1) + state.shape[2:])}
    sliced = [line.strip()[:160] for line in _unfused_lines(text)
              for m in [re.match(r"\s*(?:ROOT )?\S+ = f32\[([\d,]+)\]\S* "
                                 r"(copy|fusion|slice|dynamic-slice|gather|"
                                 r"scatter|dynamic-update-slice)\(", line)]
              if m and m.group(1) in parts]
    assert not sliced, sliced
    mem = compiled.memory_analysis()
    cache_bytes = sum(x.dtype.itemsize * math.prod(x.shape)
                      for x in jax.tree.leaves(cache))
    assert mem.alias_size_in_bytes == cache_bytes
    assert mem.temp_size_in_bytes < 0.5e9
    assert state.dtype.itemsize * math.prod(state.shape) > 1.6e9


# Granite-4.0-H-Micro at its published widths and the cell's deployment
# (1536 positions, 64 slots, the whole vocabulary) and ONE whole period of
# its four: what a layer does to the pool and to the slots' state does not
# depend on how many periods the loop over them runs.
GRANITE_1P = granite.GraniteConfig(max_seq=1536, layer_types=granite.PERIOD)
GRANITE_SLOTS = 64


def test_granite_step_updates_the_state_in_place(v5e):
    """The fourth family's one step program at the cell's geometry (64
    slots, the lane of 128 its deployment names): the donated cache (the
    attention layer's pages at head dim 64, the mamba layers' ``[9, 64,
    32, 128, 128]`` float32 states, the convolution's windows) is aliased
    to the output whole; nothing but the state-space kernel has a
    state-shaped result (no copy, no slice of a layer or a slot, no
    scatter); the temporaries of a step stay under 0.2 GB beside 1.2 GB
    of state (4.8 GB at the cell's four periods); the kernels are the ones
    counted (paged attention for the decode rows and for the lane; ONE
    state-space call a mamba layer for both, and no XLA operation a layer
    beside it under ``ssm.scan``: the period's runs of 5 and 4 mamba
    layers compile as two loop bodies); and no matmul copies its
    layer of the stacked weights first, nor the stack (a fused [2048,
    8512] input projection did: every step copied all 36 layers of it,
    1.25 GB, into the products' layout)."""
    cfg = GRANITE_1P
    where = SingleDeviceSharding(v5e[0])
    model = serving.model_for(cfg)
    lane = 128
    assert model.one_program and _derived_lane(v5e, cfg) == 64
    pages = GRANITE_SLOTS * cfg.max_seq // PAGE + 1
    params = jax.eval_shape(
        lambda: model.init_params(jax.random.PRNGKey(0), cfg)[0])
    cache = jax.eval_shape(lambda: model.slot_state.attach(
        cfg, model.init_cache(cfg, pages, PAGE), GRANITE_SLOTS))
    sds = lambda x: jax.ShapeDtypeStruct(  # noqa: E731
        x.shape, x.dtype, sharding=where)
    params, cache = (jax.tree.map(sds, t) for t in (params, cache))
    arg = lambda shape: jax.ShapeDtypeStruct(  # noqa: E731
        shape, jnp.int32, sharding=where)
    tables = cfg.max_seq // PAGE
    layout = HostInputs(GRANITE_SLOTS, tables, lane)
    fn = build_step_programs(cfg, PAGE, 1, GRANITE_SLOTS, lane)[0]
    compiled = jax.jit(fn, donate_argnums=(1,)).lower(
        params, cache, arg((GRANITE_SLOTS,)), arg((layout.size,))).compile()
    text = compiled.as_text()
    assert text.count("tpu_custom_call") == 2 + 2
    # ONE state-space call a mamba layer (one in each of the two loop
    # bodies) on the step's rows as the layer computes them: inside the
    # layers' loop XLA runs NOTHING under ``ssm.scan`` but the kernel (no
    # gather or reordering of a burst's rows, no operand laid out again),
    # and the plan's sort is the step's, outside the loop, not a layer's
    scan = [line for line in text.splitlines() if "ssm.scan" in line]
    a_layer = [line.strip()[:160] for line in scan
               if "/while/body/" in line and not re.search(
                   r" (custom-call|get-tuple-element|bitcast)\(", line)]
    assert not a_layer, a_layer
    assert sum(" custom-call(" in line for line in scan) == 2
    assert sum(" sort(" in line for line in scan) == 1
    assert not any(" sort(" in line and "/while/body/" in line
                   for line in scan)
    # the carried convolution a tap at a time on [slots, channels] tiles:
    # in a layer, under ``ssm.conv``, no array of the convolution's 4352
    # channels is copied into another layout, no result lies channels x
    # slots (the contraction over the taps as a dot did) and none has a
    # tap beside the channels (3 rows of a sublane tile). (The copies
    # that stay lay a head's dt and decay out over the kernel's [32, 128]
    # blocks: PERF.md Findings PR 45.) The layers' windows stay in the
    # layout they arrive and leave in (the chunk's slot cut out as ONE
    # [taps - 1, channels] piece made the compiler lay the whole cache out
    # tap beside channel: two copies of all of it a step) and are written
    # by a dynamic-update-slice in place, nothing else
    conv = [line for line in _unfused_lines(text)
            if "ssm.conv/" in line and "/while/body/" in line]
    assert len(conv) > 20
    copied = [line.strip()[:160] for line in conv
              if re.match(r"\s*\S+ = \w+\[[\d,]*4352\]\S* copy\(", line)]
    assert not copied, copied
    assert not _shaped(text.splitlines(), "ssm.conv/", (
        "f32[4352,64]", "f32[64,3,4352]", "bf16[64,3,4352]"))
    windows = _shaped(_unfused_lines(text), "", ("bf16[9,3,64,4352]",))
    assert windows and all(
        "{3,2,1,0:" in line
        and re.search(r"ssm\.conv/dynamic_update_slice\"", line)
        and re.search(r" (fusion|dynamic-update-slice)\(", line)
        for line in windows), [line[:200] for line in windows]
    state = cache["ssm"]
    assert state.shape == (9, GRANITE_SLOTS, 32, 128, 128)
    assert state.dtype == jnp.float32
    shape = ",".join(map(str, state.shape))
    made = [line.strip()[:160] for line in text.splitlines()
            if re.match(r"\s*(?:ROOT )?\S+ = f32\[" + shape + r"\]", line)
            and not re.search(r" (custom-call|parameter|get-tuple-element|"
                              r"bitcast)\(", line)]
    assert not made, made
    # nor a layer's states or a slot's cut out of it
    parts = {",".join(map(str, dims)) for dims in (
        state.shape[1:], (1,) + state.shape[1:], state.shape[2:],
        (1,) + state.shape[2:], (1, 1) + state.shape[2:])}
    sliced = [line.strip()[:160] for line in _unfused_lines(text)
              for m in [re.match(r"\s*(?:ROOT )?\S+ = f32\[([\d,]+)\]\S* "
                                 r"(copy|fusion|slice|dynamic-slice|gather|"
                                 r"scatter|dynamic-update-slice)\(", line)]
              if m and m.group(1) in parts]
    assert not sliced, sliced
    # every stacked weight is read where it lies: no instruction of the
    # program's own computations has the shape of a stack or of one layer
    # of it (as stored or transposed) but the parameter itself
    weights = set()
    for kind in (granite.MAMBA, granite.ATTENTION):
        for x in jax.tree.leaves(params[kind]):
            # (``w_dt`` [64, 2048] has the shape of a step's rows)
            if x.ndim == 3 and min(x.shape[1:]) >= 128:
                n, a, b = x.shape
                for dims in ((a, b), (b, a)):
                    weights |= {"%d,%d" % dims, "1,%d,%d" % dims,
                                "%d,%d,%d" % ((n,) + dims)}
    moved = [line.strip()[:160] for line in _unfused_lines(text)
             for m in [re.match(r"\s*(?:ROOT )?\S+ = bf16\[([\d,]+)\]\S* "
                                r"(copy|transpose|fusion|slice|"
                                r"dynamic-slice)\(", line)]
             if m and m.group(1) in weights]
    assert not moved, moved
    mem = compiled.memory_analysis()
    cache_bytes = sum(x.dtype.itemsize * math.prod(x.shape)
                      for x in jax.tree.leaves(cache))
    assert mem.alias_size_in_bytes == cache_bytes
    assert mem.temp_size_in_bytes < 0.2e9
    assert state.dtype.itemsize * math.prod(state.shape) > 1.2e9


def _shaped(lines, scope, shapes):
    """The instructions among a compiled program's ``lines`` under
    ``scope`` that make a result of one of ``shapes``, given as
    ``dtype[dims]``: not parameters, tuple elements or bitcasts."""
    return [line.strip() for line in lines
            for m in [re.match(r"\s*(?:ROOT )?\S+ = \(?(\w+\[[\d,]*\])", line)]
            if m and m.group(1) in shapes and scope in line
            and not re.search(r" (parameter|get-tuple-element|bitcast|"
                              r"tuple)\(", line)]


def _unfused_lines(text):
    """The lines of a compiled program's text that are instructions of
    its own computations (the entry, loop bodies and conditions), not of
    a fusion's: what a fused computation holds is not materialised."""
    fused = set(re.findall(r" fusion\(.*?calls=%([\w.\-]+)", text))
    name, out = None, []
    for line in text.splitlines():
        head = re.match(r"(?:ENTRY )?%([\w.\-]+) \(.*\{$", line)
        if head:
            name = head.group(1)
        elif name not in fused:
            out.append(line)
    return out


def _moved_weights(text, cfg, tp=1):
    """Instructions of the compiled step that copy, slice out or
    otherwise materialise an array the shape of one layer of a stacked
    block weight (a device's shard of it under ``tp``), with or without
    the leading 1, as stored or transposed."""
    d, m = cfg.d_model, cfg.d_mlp
    kv = cfg.num_kv_heads * cfg.head_dim
    layer_shapes = set()
    for a, b in ((d, d // tp), (d // tp, d), (d, kv // tp), (d, m // tp),
                 (m // tp, d)):
        for dims in ((a, b), (b, a)):
            layer_shapes |= {"%d,%d" % dims, "1,%d,%d" % dims}
    return [line.strip()[:140] for line in _unfused_lines(text)
            for mo in [re.match(r"\s*(?:ROOT )?\S+ = \w+\[([\d,]+)\]\S* "
                                r"(copy|transpose|fusion|slice|"
                                r"dynamic-slice)\(", line)]
            if mo and mo.group(1) in layer_shapes]


# llama-1b at the derived lane is left out: its wk / wv are [2048, 256]
# and a 256-token chunk's activations [1, 256, 2048] have that shape too.
@pytest.mark.parametrize("program,config", [
    *[(p, c) for p in ("block", "decode_only") for c in ENGINE_CONFIGS],
    ("block-derived", "smollm2")])
def test_engine_programs_read_stacked_weights_where_they_lie(v5e, program,
                                                              config):
    """As compiled for the chip, the layer loop holds no ``copy`` and no
    un-fused slice (``dynamic-slice``, or a fusion that only materialises
    one) whose result is a layer of a stacked block weight: every matmul
    reads its layer out of the stacked array inside its own fusion. The
    parent of PR 30 fails this with 2 copies in ``decode_only_fn`` (the
    q and k projections) and 3 in ``block_fn`` (q, k and v), each behind
    a ``constant_dynamic-slice_fusion`` that wrote the slice first: it
    reshaped q / k / v to [.., heads, hd] for the rotary step, XLA folded
    that reshape into the projection, and the projection, now batched
    over heads, wanted its 2048 x 2048 weight transposed, every layer of
    every step (``models/llama.py rope_lanes``)."""
    text = _compiled_step(v5e, config, program)[0].as_text()
    assert text.count("tpu_custom_call") == (
        1 if program == "decode_only" else 2)
    moved = _moved_weights(text, ENGINE_CONFIGS[config])
    assert not moved, moved


# (kind, result) of every collective of the tp=2 step, SmolLM2 widths, as
# PR 30's parent compiled it: the three [tokens, d] sums of a layer (wo,
# w_down and the embedding lookup's), and the sampler's. No halo exchange
# (collective-permute), no all-to-all.
_TOKENS = {"block": "1,%d" % (SLOTS + CHUNK), "decode_only": "%d,1" % SLOTS}
TP2_COLLECTIVES = {
    "block": {("all-gather", "f32[2,1,8]"), ("all-gather", "s32[2,1,8]"),
              ("all-reduce", "(f32[2], f32[2])"),
              ("all-reduce", "(s32[2], s32[2])"),
              ("all-reduce", "bf16[%s,2048]" % _TOKENS["block"])},
    "decode_only": {("all-gather", "f32[2,1,8]"),
                    ("all-gather", "s32[2,1,8]"),
                    ("all-reduce", "bf16[%s,2048]" % _TOKENS["decode_only"])},
}


@pytest.mark.parametrize("program", ["block", "decode_only"])
def test_engine_programs_at_tp2_add_no_collective(v5e, program):
    """The whole step under ``MeshSpec(tp=2)`` with the engine's rules:
    the q / k / v lanes are sharded by whole heads and the rotary step
    shifts lanes, which GSPMD would turn into a halo exchange between the
    chips; it runs per shard instead. The collectives are the parent's
    set, the kernel is still there once a lane, and a device's half of a
    layer's weight is no more copied than the whole is on one chip."""
    mesh = MeshSpec(tp=2).build(v5e[:2])
    cfg = SMOLLM2_2L
    rules = prune_rules_for_mesh(mesh, dict(SlotEngine.SERVE_RULES))
    specs, _ = _engine_program_specs(cfg, NamedSharding(mesh, P()),
                                     (mesh, rules))
    block_fn, decode_only_fn = build_step_programs(cfg, PAGE, 1, SLOTS,
                                                   CHUNK, rules)
    fn = block_fn if program == "block" else decode_only_fn
    text = under_mesh(mesh, lambda: jax.jit(fn, donate_argnums=(1,)).lower(
        *specs[program]).compile().as_text())()
    assert text.count("tpu_custom_call") == (2 if program == "block" else 1)
    found = {(m.group(2), re.sub(r"\{[^}]*\}", "", m.group(1)))
             for m in map(_COLLECTIVE.search, text.splitlines()) if m}
    assert found == TP2_COLLECTIVES[program], found
    moved = _moved_weights(text, cfg, tp=2)
    assert not moved, moved


def test_paged_kernel_is_sharded_not_partitioned(v5e):
    """The twin of the flash test for the serving kernel: under a tp
    mesh it runs per KV-heads shard in a shard_map, pool and new K/V
    split on their lane axis and q on its heads; handed sharded operands
    without the mesh, the compiler's refusal is an error."""
    mesh = MeshSpec(tp=2).build(v5e[:2])
    cfg = SMOLLM2_2L

    def sds(shape, dtype, *spec):
        return jax.ShapeDtypeStruct(shape, dtype,
                                    sharding=NamedSharding(mesh, P(*spec)))

    f = cfg.num_kv_heads * cfg.head_dim
    args = (sds((SLOTS, 1, cfg.num_heads, cfg.head_dim), cfg.dtype,
                None, None, "tp"),
            sds((SLOTS, 1, f), cfg.dtype, None, None, "tp"),
            sds((SLOTS, 1, f), cfg.dtype, None, None, "tp"),
            sds((2, 2, 257, PAGE, f), cfg.dtype, None, None, None, None,
                "tp"),
            sds((), jnp.int32), sds((SLOTS, 128 + 2), jnp.int32))
    assert _kernels(lambda *a: PA.paged_attention(
        *a, mesh=mesh, heads_axis="tp"), *args) == 1
    with pytest.raises(NotImplementedError, match="shard_map"):
        _kernels(lambda *a: PA.paged_attention(*a), *args)


# -- the train step's tied LM head on a mesh ----------------------------------

# The published widths (XL under fsdp=4; Large under fsdp=2 x tp=2, whose
# 20 heads tp=2 divides where XL's 25 do not; Large on one chip) at two
# layers and the cells' batches. ``parent_temp``: temporaries a device of
# the same step before the head was cut by tokens (PR 28's parent, this
# compiler). ``depth`` / ``optimizer``: the cell's, for the steps compiled
# whole.
HEAD_CASES = {
    "fsdp4": dict(mesh=dict(fsdp=4), heads=25, d=1600, batch=24,
                  parent_temp=1_576_602_624, depth=48, optimizer="adamw"),
    "fsdp2_tp2": dict(mesh=dict(fsdp=2, tp=2), heads=20, d=1280, batch=8,
                      parent_temp=1_079_698_432),
    "one_chip": dict(mesh=dict(), heads=20, d=1280, batch=8, depth=36,
                     optimizer="adamw_lowmem"),
}
VOCAB = 50304
_CALLEE = re.compile(r"(?:body|condition|calls|to_apply)=%([\w.\-]+)")


def _by_computation(text):
    """(computation, line) for every instruction line of a compiled
    program's text."""
    name = None
    for line in text.splitlines():
        head = re.match(r"(?:ENTRY )?%([\w.\-]+) \(.*\{$", line)
        if head:
            name = head.group(1)
        else:
            yield name, line


def _vocab_collectives(text, vocab_dims):
    """(kind, shapes, inside a loop?) of every collective of the compiled
    program that moves arrays with a vocab-sized dimension: those arrays'
    shapes, operands and results alike."""
    calls, loops, found = {}, set(), []
    for name, line in _by_computation(text):
        calls.setdefault(name, set()).update(_CALLEE.findall(line))
        if " while(" in line:
            loops.update(re.findall(r"(?:body|condition)=%([\w.\-]+)", line))
        m = _COLLECTIVE.search(line)
        if m:
            shapes = {tuple(int(x) for x in dims.split(","))
                      for dims in re.findall(r"\w+\[([\d,]+)\]", line)}
            shapes = {s for s in shapes if vocab_dims & set(s)}
            if shapes:
                found.append((m.group(2), shapes, name))
    grew = True
    while grew:  # whatever a loop's body calls is in the loop
        inner = {c for f in loops for c in calls.get(f, ())} - loops
        grew = bool(inner)
        loops |= inner
    return [(kind, shapes, where in loops) for kind, shapes, where in found]


_STEPS: dict = {}


def _checkpointed_head(xc, tc, wte, vocab_axes):
    """``gpt2._chunk_sums`` as it was before the head wrote its own
    gradient (PR 52's parent): the chunk under ``jax.checkpoint``, its
    gradient autodiff's."""
    from ray_tpu.models.common import cross_entropy_sums

    @jax.checkpoint
    def chunk(carry, xt):
        logits = jax.lax.dot_general(
            xt[0], wte, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32)
        nll, count = cross_entropy_sums(logits, xt[1], vocab_axis=vocab_axes)
        return (carry[0] + nll, carry[1] + count), None

    zero = jnp.zeros((), jnp.float32)
    return jax.lax.scan(chunk, (zero, zero), (xc, tc))[0]


def _gpt2_step(v5e, case, layers=2, packed=True, own_gradient=True):
    """The gpt2 train step of ``HEAD_CASES[case]`` compiled for the
    described chips, as the training cells build it (``mem2``, the flash
    kernel, a float32 master); each compiled once a session. At two layers
    under ``optax.adamw``: the layer bodies do not depend on the
    optimizer. ``packed=False``: the block as it was before its
    projections wrote the kernel's packed rows (PR 50's parent: a split, a
    reshape and a transpose of [b, s, 3d], still the code of every mesh
    that shards the heads), for the same compiler to be asked about both;
    ``own_gradient=False``: likewise the loss head of PR 52's parent
    (``_checkpointed_head``)."""
    from contextlib import ExitStack
    from unittest import mock

    import optax

    from ray_tpu.models import gpt2
    from ray_tpu.train.optim import adamw_lowmem
    from ray_tpu.train.step import build_sharded_train

    if (case, layers, packed, own_gradient) in _STEPS:
        return _STEPS[case, layers, packed, own_gradient]
    c = HEAD_CASES[case]
    mesh = MeshSpec(**c["mesh"]).build(v5e)
    cfg = gpt2.GPT2Config(
        vocab_size=VOCAB, max_seq=1024, num_layers=layers,
        num_heads=c["heads"], d_model=c["d"], dtype=jnp.bfloat16,
        attention_impl="flash", remat=True, remat_policy="mem2")
    rules = prune_rules_for_mesh(mesh)
    if layers > 2 and c["optimizer"] == "adamw_lowmem":
        optimizer = adamw_lowmem(1e-5)
    else:
        optimizer = optax.chain(optax.clip_by_global_norm(1.0),
                                optax.adamw(1e-5))
    sinit, sstep, _ = build_sharded_train(
        lambda k: gpt2.init_params(k, cfg),
        lambda p, b: gpt2.loss_fn(p, b, cfg, rules), mesh,
        optimizer=optimizer, master_fp32=True)
    whole = NamedSharding(mesh, P())
    init = sinit.lower(jax.ShapeDtypeStruct((2,), jnp.uint32,
                                            sharding=whole))
    state = jax.tree.map(
        lambda s, sh: jax.ShapeDtypeStruct(s.shape, s.dtype, sharding=sh),
        init.out_info, init.compile().output_shardings)
    tokens = jax.ShapeDtypeStruct((c["batch"], 1025), jnp.int32,
                                  sharding=whole)
    with ExitStack() as patched:
        if not packed:
            patched.enter_context(mock.patch.object(
                gpt2, "_packed_heads", lambda *a: 1))
        if not own_gradient:
            patched.enter_context(mock.patch.object(
                gpt2, "_chunk_sums", _checkpointed_head))
        lowered = sstep.lower(*state, {"tokens": tokens})
    _STEPS[case, layers, packed, own_gradient] = lowered.compile()
    return _STEPS[case, layers, packed, own_gradient]


@pytest.mark.parametrize("case", ["fsdp4", "fsdp2_tp2"])
def test_lm_head_moves_no_logits_between_chips(v5e, case):
    """The gpt2 train step as compiled for four chips: no collective has
    an operand or result with a vocab-sized dimension beside a token
    dimension (the parent all-reduced f32[4096, vocab] partial logits,
    forward and in the recompute, every chunk). What crosses chips with a
    vocab-sized dimension is wte: its gather(s) and its gradient's
    reductions, outside the chunk loop, once a step."""
    c = HEAD_CASES[case]
    compiled = _gpt2_step(v5e, case)

    tp, fsdp = c["mesh"].get("tp", 1), c["mesh"]["fsdp"]
    table = {(v, d) for v in (VOCAB, VOCAB // tp)
             for d in (c["d"], c["d"] // fsdp)}
    moved = _vocab_collectives(compiled.as_text(), {VOCAB, VOCAB // tp})
    assert not [m for m in moved if m[1] - table], moved  # no logits
    assert not [m for m in moved if m[2]], moved  # none a chunk
    kinds = [kind for kind, _, _ in moved]
    assert kinds.count("all-gather") >= 1
    # d wte is reduced twice a step: the lookup's all-reduce, which the
    # parent had too, and the head's own (under fsdp=4 the compiler makes
    # it an all-reduce and a slice: a 400-lane shard is off the tiling)
    assert len(kinds) - kinds.count("all-gather") == 2, moved
    # (under tp the step reads 0.16 MB over the parent's)
    assert (compiled.memory_analysis().temp_size_in_bytes
            <= c["parent_temp"] + 2**20)


def _head_products(text):
    """(instruction, op_name) of every matrix product of a compiled step
    that the benchmark's scope reader (``trace/program.py scope_of``)
    gives to ``ce`` and that has a vocab-sized dimension, in an operand
    or in its result. The text names an operand without its shape, so
    shapes are looked up by the operand's name."""
    from benchmark.trace.program import scope_of

    shapes, found = {}, []
    for line in text.splitlines():
        m = re.match(r"\s*(?:ROOT )?%([\w.\-]+) = (\S+) ([\w\-]+)"
                     r"\(([^)]*)\)", line)
        if not m:
            continue
        name, result, opcode, operands = m.groups()
        shapes[name] = re.findall(r"\w+\[([\d,]*)\]", result)
        if opcode not in ("convolution", "dot"):
            continue
        op_name = re.search(r'op_name="([^"]*)"', line)
        op_name = op_name.group(1) if op_name else ""
        dims = shapes[name] + [d for o in re.findall(r"%([\w.\-]+)", operands)
                               for d in shapes.get(o, [])]
        if scope_of(op_name)[0] == "ce" and any(
                str(VOCAB) in d.split(",") for d in dims):
            found.append((name, op_name))
    return found


@pytest.mark.parametrize("case", ["one_chip", "fsdp4"])
def test_lm_head_multiplies_by_the_vocab_three_times_a_chunk(v5e, case):
    """``gpt2-large`` on one chip and ``gpt2-xl`` under fsdp=4: the loss
    head forms its gradient where it has the logits, so under ``ce`` the
    chunk loop holds THREE products with a ``[chunk, vocab]`` operand or
    result (the logits, ``dx = g @ wte``, ``d wte += g.T @ x``) and none
    is a recomputation. The parent's head (the chunk under
    ``jax.checkpoint``), compiled beside it at the cell's depth, holds
    four, the logits twice; and the whole step needs no more temporaries
    than that one plus what the head now keeps from its forward to its
    backward pass, ``dx`` and ``d wte`` in the activations' dtype."""
    c = HEAD_CASES[case]
    products = _head_products(_gpt2_step(v5e, case).as_text())
    assert len(products) == 3, products
    assert not [p for p in products if "rematted_computation" in p[1]]

    parent = _gpt2_step(v5e, case, c["depth"], own_gradient=False)
    was = _head_products(parent.as_text())
    assert len(was) == 4, was
    assert len([p for p in was if "rematted_computation" in p[1]]) == 1
    tokens = c["batch"] // c["mesh"].get("fsdp", 1) * 1024
    kept = 2 * c["d"] * (tokens + VOCAB)  # bfloat16
    temp, parent_temp = (
        step.memory_analysis().temp_size_in_bytes
        for step in (_gpt2_step(v5e, case, c["depth"]), parent))
    assert temp <= parent_temp + kept, (temp, parent_temp, kept)


# -- the train step's layer bodies: q, k, v and o between matmul and kernel ---

_RELAYOUT = re.compile(r"attn/(split|reshape|transpose|squeeze)$")


def _layer_bodies(text):
    """The lines of each loop body of a compiled train step that holds a
    flash kernel: the layers' forward pass and their backward pass."""
    wanted = set(re.findall(r" while\(.*?body=%([\w.\-]+)", text))
    bodies = {}
    for name, line in _by_computation(text):
        if name in wanted:
            bodies.setdefault(name, []).append(line)
    return [b for b in bodies.values()
            if any("tpu_custom_call" in line for line in b)]


def _relayouts(lines, elements):
    """(instruction, op_name) of every ``copy`` of ``lines``, and every
    fusion traced from a split, reshape, transpose or squeeze of the
    attention half, whose result has ``elements`` elements or more: an
    activation relaid between a projection's matmul and a kernel."""
    found = []
    for line in lines:
        m = re.match(r"\s*(?:ROOT )?%([\w.\-]+) = (\(?\w+\[[\d,]*\]).*? "
                     r"(copy|fusion)\(", line)
        if not m:
            continue
        dims = re.search(r"\[([\d,]*)\]", m.group(2)).group(1)
        op_name = re.search(r'op_name="([^"]*)"', line)
        op_name = op_name.group(1) if op_name else ""
        if math.prod(int(x) for x in dims.split(",") if x) >= elements and (
                m.group(3) == "copy" or _RELAYOUT.search(op_name)):
            found.append((m.group(1), op_name.rpartition("/while/body/")[2]))
    return found


@pytest.mark.parametrize("case", ["one_chip", "fsdp4"])
def test_train_step_relays_no_activation_between_matmul_and_kernel(v5e,
                                                                   case):
    """``gpt2-large`` on one chip and ``gpt2-xl`` under fsdp=4, two
    layers: the projections' matmuls write q, k and v where the flash
    kernels read them and read o, dq, dk and dv where the kernels wrote
    them. The parent's two layer bodies held 9 + 12 and 6 + 8 copies and
    split fusions of ``b * s * d``-sized arrays; what may stay is the one
    relayout of the saved o for the gradient of ``proj_w``."""
    c = HEAD_CASES[case]
    text = _gpt2_step(v5e, case).as_text()
    bodies = _layer_bodies(text)
    assert len(bodies) == 2
    local = c["batch"] // c["mesh"].get("fsdp", 1) * 1024 * c["d"]
    moved = [r for body in bodies for r in _relayouts(body, local)]
    assert len(moved) <= 1, moved
    # ... which this reading does find in the unpacked block's bodies
    unpacked = _layer_bodies(_gpt2_step(v5e, case, packed=False).as_text())
    assert sum(len(_relayouts(b, local)) for b in unpacked) >= 14
    assert all(name.endswith("dynamic_slice") for _, name in moved), moved
    # the kernels take packed rows: two heads to a row, XL's 25 as 13 rows
    kernels = [k["operands"][0][1] for k in _kernel_shapes(text)]
    rows = -(-c["heads"] // 2)
    assert kernels and all(tuple(k[1:]) == (rows, 1024, 128)
                           for k in kernels), kernels


def _collective_kinds(text):
    kinds = {}
    for m in filter(None, map(_COLLECTIVE.search, text.splitlines())):
        kinds[m.group(2)] = kinds.get(m.group(2), 0) + 1
    return kinds


@pytest.mark.parametrize("case", ["fsdp4", "fsdp2_tp2"])
def test_packed_projections_add_no_collective(v5e, case):
    """Against the unpacked block compiled beside it: no kind of
    collective it lacks, none in a layer's body on an array with a
    sequence's tokens (the weights' shards are gathered and their
    gradients reduced, as before; under fsdp=4 each of the three
    projections gathers its own third, the same bytes in more pieces),
    and under tp, where the stored ``qkv`` axis is cut across q, k and v
    and a pair of heads would straddle shards, the heads stay whole: the
    unpacked block's collectives, kind by kind."""
    c = HEAD_CASES[case]
    text = _gpt2_step(v5e, case).as_text()
    kinds = _collective_kinds(text)
    parent = _collective_kinds(_gpt2_step(v5e, case, packed=False).as_text())
    assert set(kinds) <= set(parent), (kinds, parent)
    for kind in ("all-to-all", "collective-permute"):
        assert kinds.get(kind, 0) <= parent.get(kind, 0), (kinds, parent)
    if "tp" in c["mesh"]:
        assert kinds == parent, (kinds, parent)
        return
    for body in _layer_bodies(text):  # 1024: the tokens of a sequence
        for m in filter(None, map(_COLLECTIVE.search, body)):
            dims = re.findall(r"\w+\[([\d,]*)\]", m.group(1))
            assert not [d for d in dims if "1024" in d.split(",")], dims


@pytest.mark.parametrize("case", ["one_chip", "fsdp4"])
def test_whole_train_step_needs_no_more_memory_than_unpacked(v5e, case):
    """The cells' steps at full depth with their optimizers: temporaries a
    device no more than the unpacked block's, compiled beside it (it holds
    q, k, v and o in 64-lane rows padded to 128 beside the ``[b, s, 3d]``
    save; a layout that is the kernel's but not lane-dense reads 'Used
    16.21G of 15.75G hbm')."""
    depth = HEAD_CASES[case]["depth"]
    temp, unpacked = (
        _gpt2_step(v5e, case, depth, packed).memory_analysis()
        .temp_size_in_bytes for packed in (True, False))
    assert temp <= unpacked, (temp, unpacked)
