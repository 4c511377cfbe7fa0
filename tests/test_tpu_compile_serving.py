"""The serving engines' step programs compile for the real chip: what a
step does to the page pool, the slots' state and the stacked weights, as
``tests/test_tpu_compile.py`` says of the kernels (a described ``v5e:2x2``,
shapes and not arrays; nothing runs, and a pass is not a chip run)."""

import math
import re

import jax
import jax.numpy as jnp
import pytest
from compiled_steps import (_COLLECTIVE, _kernels,  # noqa: F401
                            compiled_for_tpu, v5e)
from jax.sharding import NamedSharding, PartitionSpec as P
from jax.sharding import SingleDeviceSharding

from ray_tpu.llm.engine import (HostInputs, SlotEngine, build_step_programs,
                                prefill_lane)
from ray_tpu.models import granite, lfm2, llama, serving, solar
from ray_tpu.ops import paged_attention as PA
from ray_tpu.parallel.mesh import DEVICE_PEAKS, MeshSpec
from ray_tpu.parallel.sharding import (prune_rules_for_mesh, shardings_for,
                                       under_mesh)

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
