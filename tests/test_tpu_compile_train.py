"""The gpt2 train step compiles for the real chips: the tied LM head on
a mesh and the layer bodies between matmul and kernel, as
``tests/test_tpu_compile.py`` says of the kernels (a described ``v5e:2x2``,
shapes and not arrays; nothing runs, and a pass is not a chip run)."""

import collections
import math
import re

import jax
import jax.numpy as jnp
import pytest
from compiled_steps import (_COLLECTIVE, _by_computation,  # noqa: F401
                            _kernel_shapes, compiled_for_tpu, v5e)
from jax.sharding import NamedSharding, PartitionSpec as P

from ray_tpu.parallel.mesh import MeshSpec
from ray_tpu.parallel.sharding import prune_rules_for_mesh

# -- the train step's tied LM head on a mesh ----------------------------------

# The published widths (XL under fsdp=4; Large under fsdp=2 x tp=2, whose
# 20 heads tp=2 divides where XL's 25 do not; Large on one chip) at two
# layers and the cells' batches. ``parent_temp``: temporaries a device of
# the same step before the head was cut by tokens (PR 28's parent, this
# compiler). ``depth`` / ``optimizer``: the cell's, for the tests that need
# them (``test_tpu_compile.py test_whole_train_step_*``: what every layer
# saves; in that file so that its four whole compiles run beside this
# file's, not after them); all else reads a layer's body or the head, which
# two layers have as 36 or 48 do.
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


def _stacked_after_the_call(q, k, v, stacks, layer, **where):
    """``attention_saving`` for the loop of PR 57's parent: the kernel's
    results are buffers of its own, lse a column a head, and o and lse are
    put into their stacks after the call, as ``lax.scan`` stacks its
    ``ys``."""
    from ray_tpu.ops.attention import attention_saving

    o, (q, k, v, o, lse) = attention_saving(q, k, v, **where)
    stacks = [jax.lax.dynamic_update_index_in_dim(stack, x, layer, 0)
              for stack, x in zip(stacks, (o, lse))]
    return o, (q, k, v, *stacks)


def _gpt2_step(v5e, case, layers=2, packed=True, own_gradient=True,
               owned=True, in_place=True):
    """The gpt2 train step of ``HEAD_CASES[case]`` compiled for the
    described chips, as the training cells build it (``mem2``, the flash
    kernel, a float32 master); each compiled once a session. At two layers
    under ``optax.adamw``: the layer bodies do not depend on the
    optimizer. ``packed=False``: the block as it was before its
    projections wrote the kernel's packed rows (PR 50's parent: a split, a
    reshape and a transpose of [b, s, 3d], still the code of every mesh
    that shards the heads), for the same compiler to be asked about both;
    ``own_gradient=False``: likewise the loss head of PR 52's parent
    (``_checkpointed_head``); ``owned=False``: likewise the layer loop of
    PR 55's parent, ``jax.checkpoint`` under ``lax.scan`` differentiated
    by JAX (still the loop of every policy but ``mem2``);
    ``in_place=False``: likewise the owned loop of PR 57's parent, whose
    forward kernel wrote buffers of its own (``_stacked_after_the_call``)."""
    from contextlib import ExitStack
    from unittest import mock

    import optax

    from ray_tpu.models import gpt2
    from ray_tpu.train.optim import adamw_lowmem
    from ray_tpu.train.step import build_sharded_train

    key = (case, layers, packed, own_gradient, owned, in_place)
    if key in _STEPS:
        return _STEPS[key]
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
        if not owned:
            patched.enter_context(mock.patch.object(
                gpt2, "_owns_backward", lambda *a: False))
        if not in_place:
            patched.enter_context(mock.patch.object(
                gpt2, "attention_saving", _stacked_after_the_call))
        lowered = sstep.lower(*state, {"tokens": tokens})
    _STEPS[key] = lowered.compile()
    return _STEPS[key]


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
    ``jax.checkpoint``), compiled beside it, holds four, the logits
    twice; and the step needs no more temporaries than that one plus what
    the head now keeps from its forward to its backward pass, ``dx`` and
    ``d wte`` in the activations' dtype. All at two layers: neither the
    head's products nor what it adds to the step's peak (the head runs
    at the peak, over whatever the layers saved) depend on the depth."""
    c = HEAD_CASES[case]
    step, parent = (_gpt2_step(v5e, case, own_gradient=own)
                    for own in (True, False))
    products, was = (_head_products(s.as_text()) for s in (step, parent))
    assert len(products) == 3, products
    assert not [p for p in products if "rematted_computation" in p[1]]
    assert len(was) == 4, was
    assert len([p for p in was if "rematted_computation" in p[1]]) == 1
    tokens = c["batch"] // c["mesh"].get("fsdp", 1) * 1024
    kept = 2 * c["d"] * (tokens + VOCAB)  # bfloat16
    temp, parent_temp = (
        s.memory_analysis().temp_size_in_bytes for s in (step, parent))
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
    for name, _ in moved:  # o, cut from the stack of every layer's
        assert re.search(rf"%{re.escape(name)} = \S+ \w+\(%dynamic-slice",
                         text), moved
    # the kernels take packed rows: two heads to a row, XL's 25 as 13 rows
    # (the backward kernel after a layer index, in the layers' stacks)
    kernels = [next(dims for _, dims in k["operands"] if len(dims) > 3)
               for k in _kernel_shapes(text)]
    rows = -(-c["heads"] // 2)
    assert kernels and all(tuple(k[-3:]) == (rows, 1024, 128)
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


# -- the backward kernel reads what its layer saved where the scan stacked it --

_INSTRUCTION = re.compile(
    r"\s*(?:ROOT )?%([\w.\-]+) = \(?\w+\[([\d,]*)\]\S* ([\w\-]+)\(")
# what a fusion that only moves is made of (``dynamic-slice_bitcast_fusion``)
_FREE = {"parameter", "constant", "bitcast"}
_MOVES = _FREE | {"copy", "dynamic-slice", "slice", "reshape", "transpose",
                  "dynamic-update-slice"}


def _fused_opcodes(text):
    """computation -> the opcodes of its instructions: what a fusion that
    calls it is made of."""
    fused = collections.defaultdict(set)
    for name, line in _by_computation(text):
        m = _INSTRUCTION.match(line)
        if m:
            fused[name].add(m.group(3))
    return fused


def _results(lines, opcodes, dims, fused):
    """The instructions of ``lines`` with one of ``opcodes`` (a fusion
    counts by what it is made of, ``fused``, less parameters, constants
    and bitcasts: inside a fusion that computes, an operand is read, or a
    result written, where it lies) whose result's dims, ones dropped, are
    among ``dims``."""
    found = []
    for line in lines:
        m = _INSTRUCTION.match(line)
        if not m or tuple(int(x) for x in m.group(2).split(",")
                          if x not in ("", "1")) not in dims:
            continue
        inner = {m.group(3)}
        if m.group(3) == "fusion":
            inner = fused[re.search(r"calls=%([\w.\-]+)", line).group(1)]
            inner = inner - _FREE
        if inner and inner <= set(opcodes):
            found.append(m.group(1))
    return found


def _operand_copies(text, operand):
    """Every instruction of the layers' bodies that is a ``copy``, a
    slice, a ``dynamic-update-slice`` or a fusion of nothing but those and
    whose result is ``operand``, the dims of a flash kernel's or of a stack
    of them."""
    lines = [line for body in _layer_bodies(text) for line in body]
    return _results(lines, _MOVES - _FREE, [operand], _fused_opcodes(text))


def _forward_body(text):
    """The lines of the layers' forward loop body."""
    body, = [body for body in _layer_bodies(text)
             if not any("transpose(jvp(" in line for line in body)]
    return body


@pytest.mark.parametrize("case", ["one_chip", "fsdp4"])
def test_backward_kernel_reads_its_layer_of_the_saves_in_place(v5e, case):
    """``gpt2-large`` on one chip and ``gpt2-xl`` under fsdp=4, two
    layers (a layer's body is what is asserted). ``lax.scan`` stacks what
    a layer saves ``[layers, ...]`` and its transpose hands the backward
    body slices; a Mosaic call takes no slice of a buffer, so q, k, v and
    o were each copied out of ``bf16[layers, b, rows, 1024, 128]`` a layer
    (4 x 67-69 us of each of gpt2-large's 36, PERF.md Findings PR 55). The
    loop that owns its backward pass (``gpt2._blocks_saving``) hands the
    kernel the stacks and a layer number: no such copy in either body, the
    kernel's operands ARE the stacks, the projections still write q, k
    and v into them in place, the step needs no more memory, and the
    layers are still two ``while``s."""
    c = HEAD_CASES[case]
    step = _gpt2_step(v5e, case)
    text = step.as_text()
    b, rows = c["batch"] // c["mesh"].get("fsdp", 1), -(-c["heads"] // 2)
    assert not _operand_copies(text, (b, rows, 1024, 128))
    backward = [k["operands"] for k in _kernel_shapes(text)
                if len(k["outputs"]) == 3]
    assert len(backward) == 1
    stack, rows_of_lanes = (2, b, rows, 1024, 128), (b, 2 * rows, 4, 256)
    assert [(t, tuple(d)) for t, d in backward[0]] == [
        ("s32", (1,)),  # the layer
        ("bf16", stack), ("bf16", stack), ("bf16", stack),  # q, k, v
        ("bf16", stack[1:]),  # dO
        ("f32", (2,) + rows_of_lanes),  # lse, a row of lanes a query block
        ("f32", rows_of_lanes)], backward  # delta
    # each projection's matmul has the stack it writes its result into as
    # an output of its own fusion: q, k and v are not stacked by a copy
    forward = [body for body in _layer_bodies(text)
               if not any("transpose(jvp(" in line for line in body)]
    assert len(forward) == 1
    stacked = "bf16[" + ",".join(map(str, stack)) + "]"
    in_place = [line for line in forward[0]
                if "bsd,drl->brsl/dot_general" in line
                and " fusion(" in line and "kind=kOutput" in line
                and stacked in line.partition(" fusion(")[0]]
    assert len(in_place) == 3, in_place
    # ... and this reading does find the copies of the scan's own transpose
    parent = _gpt2_step(v5e, case, owned=False)
    assert len(_operand_copies(parent.as_text(), (b, rows, 1024, 128))) >= 4
    assert step.memory_analysis().temp_size_in_bytes \
        <= parent.memory_analysis().temp_size_in_bytes
    assert text.count(" while(") == parent.as_text().count(" while(") == 3


@pytest.mark.parametrize("owned", [True, False])
@pytest.mark.parametrize("case", ["one_chip", "fsdp4"])
def test_backward_kernel_is_what_the_benchmark_looks_for(v5e, case, owned):
    """``kernel.flash_bwd_roofline`` finds the backward call in a trace by
    its results and the last four dims of its operands
    (``benchmark/readers/flash_bwd_roofline.py backward_call``): the call
    that reads its layer of the stacks and the parent's on a layer sliced
    out are one call doing one layer's work to it, so the step's and the
    parent's rooflines are read by one reader. ``kernel.flash_roofline``
    (three 4-d operands first) sees the parent's alone."""
    from benchmark.readers.flash_bwd_roofline import backward_call
    from benchmark.trace.opsbytes import classify_flash

    c = HEAD_CASES[case]
    b, rows = c["batch"] // c["mesh"].get("fsdp", 1), -(-c["heads"] // 2)
    kernels = _kernel_shapes(_gpt2_step(v5e, case, owned=owned).as_text())
    assert len(kernels) == 2
    assert sorted(map(backward_call, kernels), key=bool) == [
        None, (b, rows, 1024, 1024, 128)]
    assert [k[0] for k in map(classify_flash, kernels) if k] == \
        ([] if owned else ["bwd"])


# -- the forward kernel writes what its layer saves where the backward reads it

def _fills(text, dims):
    """The instructions of the ENTRY computation that fill an array of
    ``dims`` with one value: a ``broadcast``, bare or all a fusion does."""
    entry = re.search(r"^ENTRY %([\w.\-]+)", text, re.M).group(1)
    lines = [line for name, line in _by_computation(text) if name == entry]
    return _results(lines, ["broadcast"], [dims], _fused_opcodes(text))


@pytest.mark.parametrize("case", ["one_chip", "fsdp4"])
def test_forward_kernel_writes_its_layer_of_the_saves_in_place(v5e, case):
    """``gpt2-large`` on one chip and ``gpt2-xl`` under fsdp=4, two layers
    (a layer's body is what is asserted). XLA cannot point a Mosaic call's
    result into a slice of a larger buffer: the forward kernel's o was
    copied into its ``[layers, ...]`` stack after the call, and its lse,
    ``[b, heads, 1024, 1]`` columns, made dense by a ``reduce`` and then
    stacked (109 + 62 us of each of gpt2-large's 36 layers, PERF.md Findings
    PR 57). The forward scan of ``gpt2._blocks_saving`` CARRIES the two
    stacks and hands them to the kernel with a layer number: they are the
    call's operands and its results, lse as the rows of lanes the backward
    kernel reads; nothing in the forward body only moves o, a stack or
    lse; the stacks begin as buffers nothing fills; the step needs no more
    memory, and the layers are still two ``while``s."""
    c = HEAD_CASES[case]
    step, parent = _gpt2_step(v5e, case), _gpt2_step(v5e, case,
                                                     in_place=False)
    text = step.as_text()
    b, rows = c["batch"] // c["mesh"].get("fsdp", 1), -(-c["heads"] // 2)
    o, lse = (b, rows, 1024, 128), (b, 2 * rows, 4, 256)
    forward = [k for k in _kernel_shapes(text) if len(k["outputs"]) == 2]
    assert len(forward) == 1
    stacks = [("bf16", (2,) + o), ("f32", (2,) + lse)]
    assert [(t, tuple(d)) for t, d in forward[0]["operands"]] == [
        ("s32", (1,)),  # the layer
        ("bf16", o), ("bf16", o), ("bf16", o),  # q, k, v
        *stacks], forward
    assert [(t, tuple(d)) for t, d in forward[0]["outputs"]] == stacks
    # neither body copies or slices a layer's o or a whole stack of them
    assert not _operand_copies(text, o)
    assert not _operand_copies(text, (2,) + o)
    # ... and the forward body makes no lse dense and moves none
    layouts = [(b, 2 * rows, 1024), lse, (2,) + lse]
    assert not _results(_forward_body(text), _MOVES - _FREE | {"reduce"},
                        layouts, _fused_opcodes(text))
    # (this reading does find the parent's ``reduce`` of the columns, and
    # the copy of o into its stack)
    was = parent.as_text()
    assert _results(_forward_body(was), ["reduce"], layouts,
                    _fused_opcodes(was))
    assert _operand_copies(was, (2,) + o)
    # the stacks begin uninitialised (``jax.lax.empty``): no pass over
    # 2 x 755 MB that the parent's scan did not make either
    for dims in ((2,) + o, (2,) + lse):
        assert not _fills(text, dims) and not _fills(was, dims)
    for other in (parent, _gpt2_step(v5e, case, owned=False)):
        assert step.memory_analysis().temp_size_in_bytes \
            <= other.memory_analysis().temp_size_in_bytes
        assert text.count(" while(") == other.as_text().count(" while(") == 3


@pytest.mark.parametrize("owned", [True, False])
@pytest.mark.parametrize("case", ["one_chip", "fsdp4"])
def test_forward_kernel_is_what_the_benchmark_looks_for(v5e, case, owned):
    """``kernel.flash_fwd_roofline`` finds the forward call in a trace by
    its two results and the last four dims of its first three operands
    (``benchmark/readers/flash_fwd_roofline.py forward_call``): the call
    that writes its layer of the stacks and the checkpointed scan's, whose
    results are its own buffers and whose lse is a column a head, are one
    call doing one layer's work to it. ``kernel.flash_roofline`` sees
    neither (packed rows: lse has twice the operands' rows)."""
    from benchmark.readers.flash_fwd_roofline import forward_call
    from benchmark.trace.opsbytes import classify_flash

    c = HEAD_CASES[case]
    b, rows = c["batch"] // c["mesh"].get("fsdp", 1), -(-c["heads"] // 2)
    kernels = _kernel_shapes(_gpt2_step(v5e, case, owned=owned).as_text())
    assert len(kernels) == 2
    assert sorted(map(forward_call, kernels), key=bool) == [
        None, (b, rows, 1024, 1024, 128)]
    assert "fwd" not in [k[0] for k in map(classify_flash, kernels) if k]
