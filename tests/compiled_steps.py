"""Did a refactor change a compiled step? (no test: a script, run by hand)

    python tests/compiled_steps.py dump <checkout> <out dir> [name,...]
    python tests/compiled_steps.py compare <out dir A> <out dir B>

``dump`` compiles, for the described v5e and from the tree at
``<checkout>`` (a ``git archive`` copy of the parent, or this one), the
serving families' step programs at their cells' widths and slots, as
``tests/test_tpu_compile.py`` builds them but at the cells' depth, and
writes each ``compiled.as_text()``. One ``dump`` at a time: the TPU's
compiler is one process's. ``compare`` holds two such directories against
each other, three ways (``.claude/skills/verify/SKILL.md`` says why each):

* the text with ``, metadata={...}``, the source-location tables and the
  debug locations inside each Mosaic kernel's body taken out: equal string
  for string where the ORDER operations were traced in is the same;
* the same with every instruction and computation renamed by its order of
  appearance: equal where only the numbering moved (then the scheduled
  order of every computation's lines, every shape and layout, every
  fusion's body and every kernel's body are the same);
* the multiset of ``(opcode, op_name)``: the scope path of every
  instruction, which ``benchmark/trace/program.py`` sums shares over.
"""

import base64
import collections
import os
import re
import sys
import time

os.environ.setdefault("TPU_LOG_DIR", "disabled")  # else it logs under /tmp
os.environ["JAX_PLATFORMS"] = "cpu"  # before anything imports jax

import pytest  # noqa: E402

PAGE = 16
# name -> (configuration, slots, lane, 0 block | 1 decode_only)
CASES = {
    "llama.block64": ("smollm2", 8, 64, 0),
    "llama.block256": ("smollm2", 8, 256, 0),
    "llama.decode_only": ("smollm2", 8, 64, 1),
    "lfm2.block": ("lfm2", 64, 64, 0),
    "lfm2.decode_only": ("lfm2", 64, 64, 1),
    "solar.block": ("solar", 128, 64, 0),
    "granite.block": ("granite", 64, 128, 0),
}


def dump(root, out, only=None):
    sys.path.insert(0, root)
    import jax
    import jax.numpy as jnp
    from jax.experimental import topologies
    from jax.sharding import SingleDeviceSharding

    import ray_tpu
    assert os.path.realpath(ray_tpu.__file__).startswith(
        os.path.realpath(root) + os.sep), ray_tpu.__file__
    from ray_tpu.llm.engine import HostInputs, build_step_programs
    from ray_tpu.models import granite, lfm2, llama, serving, solar

    for ops in _kernel_modules():
        ops._on_tpu = lambda: True     # the kernels, not their references
    jax.config.update("jax_enable_compilation_cache", False)
    topo = topologies.get_topology_desc(platform="tpu",
                                        topology_name="v5e:2x2")
    where = SingleDeviceSharding(topo.devices[0])
    # tests/test_tpu_compile.py's SMOLLM2_2L (a scan over layers: two are
    # as good as 24) and, at the depth of their cells (benchmark/configs:
    # the layers are unrolled, or looped by period with another program
    # for one period than for several), LFM2_1P, SOLAR_1P, GRANITE_1P
    configs = {
        "smollm2": llama.LlamaConfig(
            vocab_size=49152, max_seq=2048, num_layers=2, num_heads=32,
            num_kv_heads=32, d_model=2048, d_mlp=8192, rope_theta=130000.0,
            dtype=jnp.bfloat16, remat=False),
        "lfm2": lfm2.Lfm2Config(
            layer_types=("conv",) + lfm2.PERIOD * 2, num_dense_layers=1),
        "solar": solar.SolarConfig(
            max_seq=1280, layer_types=solar.PERIOD * 2,
            experts_held=(100, 20), vocab_held=(0, 24576)),
        "granite": granite.GraniteConfig(max_seq=1536),
    }
    os.makedirs(out, exist_ok=True)
    for name, (config, slots, lane, which) in CASES.items():
        if only and not any(name.startswith(o) for o in only):
            continue
        started, cfg = time.time(), configs[config]
        model = serving.model_for(cfg)
        params = jax.eval_shape(
            lambda: model.init_params(jax.random.PRNGKey(0), cfg)[0])
        if config == "smollm2":        # the engine serves it in cfg.dtype
            params = jax.tree.map(
                lambda x: jax.ShapeDtypeStruct(x.shape, cfg.dtype), params)

        def make_cache():
            cache = model.init_cache(cfg, slots * cfg.max_seq // PAGE + 1,
                                     PAGE)
            if model.slot_state is not None:
                cache = model.slot_state.attach(cfg, cache, slots)
            return cache

        params, cache = (jax.tree.map(lambda x: jax.ShapeDtypeStruct(
            x.shape, x.dtype, sharding=where), t)
            for t in (params, jax.eval_shape(make_cache)))
        tables = cfg.max_seq // PAGE
        host = (HostInputs(slots, tables) if which
                else HostInputs(slots, tables, lane))
        arg = lambda n: jax.ShapeDtypeStruct(  # noqa: E731
            (n,), jnp.int32, sharding=where)
        fn = build_step_programs(cfg, PAGE, 1, slots, lane)[which]
        text = jax.jit(fn, donate_argnums=(1,)).lower(
            params, cache, arg(slots), arg(host.size)).compile().as_text()
        with open(os.path.join(out, name + ".hlo"), "w") as f:
            f.write(text)
        print(f"{name}: {len(text)} bytes, {time.time() - started:.1f} s",
              flush=True)


# -- what tests/test_tpu_compile*.py share: the described chip, the kernels'
# compiled branch, and readers of a compiled program's text ------------------

def _kernel_modules():
    from ray_tpu.ops import (attention, delta_rule, grouped_matmul,
                             paged_attention, ssm_scan)

    return attention, paged_attention, grouped_matmul, delta_rule, ssm_scan


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
    import jax
    from jax.experimental.compilation_cache import compilation_cache

    for ops in _kernel_modules():
        monkeypatch.setattr(ops, "_on_tpu", lambda: True)
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield
    jax.config.update("jax_enable_compilation_cache", was)
    compilation_cache.reset_cache()


def _compiled(fn, *specs) -> str:
    """``fn`` compiled for the specs' (described) devices, as HLO text."""
    import jax

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


_COLLECTIVE = re.compile(
    r"= (.*?) (all-reduce|all-gather|reduce-scatter|all-to-all|"
    r"collective-permute)(?:-start)?\(")


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


_TABLES = ("FileNames", "FunctionNames", "FileLocations", "StackFrames")
_META = re.compile(r', metadata=\{[^}]*\}')
_BODY = re.compile(r'\\?"body\\?": ?\\?"([A-Za-z0-9+/=]+)\\?"')
_DEFINED = re.compile(r'^\s*(?:ROOT )?%?([\w.\-]+) = (.*)')
_OPCODE = re.compile(r'.*? ([a-z][\w\-]*)\(')
_COMPUTATION = re.compile(r'^(?:ENTRY )?%?([\w.\-]+) [({]')


def _mosaic_asm(encoded):
    from jax._src.lib.mlir import ir

    ctx = ir.Context()
    ctx.allow_unregistered_dialects = True
    module = ir.Module.parse(base64.b64decode(encoded), ctx)
    return module.operation.get_asm(enable_debug_info=False)


def stripped(text):
    """-> (text without source locations, Mosaic bodies, Counter of
    (opcode, op_name))."""
    pairs, lines, skipping = collections.Counter(), [], False
    for line in text.splitlines():
        if line.strip() in _TABLES:
            skipping = True
        elif skipping and (not line.strip() or re.match(r"\d+ ", line)):
            continue
        else:
            skipping = False
            lines.append(line)
            defined = _DEFINED.match(line)
            opcode = defined and _OPCODE.match(defined.group(2))
            if opcode:
                name = re.search(r'op_name="([^"]*)"', line)
                pairs[opcode.group(1), name.group(1) if name else ""] += 1
    bodies = []

    def body(m):
        bodies.append(_mosaic_asm(m.group(1)))
        return f'"body": <mosaic {len(bodies) - 1}>'

    return _BODY.sub(body, _META.sub("", "\n".join(lines))), bodies, pairs


def renamed(text):
    """Every instruction and computation named by its order of appearance."""
    names = {}
    for line in text.splitlines()[1:]:
        m = _DEFINED.match(line) or _COMPUTATION.match(line)
        if m:
            names.setdefault(m.group(1), f"n{len(names)}")
    known = re.compile(r'(?<![\w.\-])%?(' + "|".join(
        map(re.escape, sorted(names, key=len, reverse=True)))
        + r')(?![\w\-]|\.\d)')
    return known.sub(lambda m: names[m.group(1)], text)


def compare(a, b):
    same_all = True
    for name in sorted(set(os.listdir(a)) & set(os.listdir(b))):
        (ta, ba, pa), (tb, bb, pb) = (
            stripped(open(os.path.join(d, name)).read()) for d in (a, b))
        text = ta == tb
        order = text or renamed(ta) == renamed(tb)
        print(f"{name}: text {'EQUAL' if text else 'differs'}; renamed by "
              f"order {'EQUAL' if order else 'DIFFERS'}; {len(ba)} Mosaic "
              f"bodies {'EQUAL' if ba == bb else 'DIFFER'}; "
              f"{sum(pa.values())} (opcode, op_name) pairs "
              f"{'EQUAL' if pa == pb else 'DIFFER'}")
        for pair in sorted(set(pa) | set(pb)):
            if pa[pair] != pb[pair]:
                print("   ", pair, pa[pair], "->", pb[pair])
        if not order:
            shown = 0
            for x, y in zip(renamed(ta).splitlines(),
                            renamed(tb).splitlines()):
                if x != y and shown < 4:
                    shown += 1
                    print("    -", x.strip()[:200])
                    print("    +", y.strip()[:200])
        same_all = same_all and order and ba == bb and pa == pb
    print("THE SAME PROGRAMS" if same_all else "NOT THE SAME PROGRAMS")
    return same_all


if __name__ == "__main__":
    if sys.argv[1] == "dump":
        dump(sys.argv[2], sys.argv[3],
             sys.argv[4].split(",") if len(sys.argv) > 4 else None)
    else:
        sys.exit(0 if compare(sys.argv[2], sys.argv[3]) else 1)
