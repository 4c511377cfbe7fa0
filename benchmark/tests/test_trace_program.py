"""The second reduction (``trace/program.py``) on a trace recorded on the
v5e by ``tools/program_probe.py``, its readers, and the overlay that wires
it in (``tools/program_overlay.py``): the program's own spans and scopes
become numbers, a program without them becomes no number and no error,
and nothing the first reduction returns changes."""

import json
import os
import shutil
import subprocess
import sys

import pytest

from benchmark.manifest import Manifest, compute_metrics
from benchmark.trace import program
from benchmark.trace import reduce as trace_reduce

from conftest import FIXTURES, ROOT

RECORDED = os.path.join(FIXTURES, "program_1chip.xplane.pb.gz")


@pytest.fixture(scope="module")
def reduced():
    return program.reduce(program.load(RECORDED))


def test_scope_of_reads_the_innermost_scope_and_the_half():
    assert program.scope_of(
        "jit(block_fn)/layers/while/body/closed_call/prefill_lane/"
        "kv_gather/gather") == ("kv_gather", "forward")
    assert program.scope_of(
        "jit(block_fn)/layers/while/body/dynamic_slice") == (
            "layers", "forward")
    assert program.scope_of(
        "jit(sharded_step)/fwd_bwd/transpose(jvp(ce))/while/body/"
        "closed_call/checkpoint/rematted_computation/dot_general") == (
            "ce", "backward")
    assert program.scope_of("jit(sharded_step)/fwd_bwd/jvp()/while/body/"
                            "closed_call/mlp/dot_general") == (
                                "mlp", "forward")
    # the last component is the primitive, never a scope; no scope at all
    assert program.scope_of("jit(f)/while/body/attn") == (None, "forward")
    assert program.scope_of("") == (None, "forward")


def test_an_instruction_without_metadata_takes_its_fusion_or_operand():
    cls = program._messages()
    module = cls["HloModule"]()
    fused = module.computations.add(id=2, name="fused_computation")
    for i, name in enumerate(("a/attn/slice", "a/attn/convert", "a/x/add")):
        fused.instructions.add(id=20 + i, name=f"f{i}", opcode="add") \
            .metadata.op_name = name
    main = module.computations.add(id=1, name="main")
    loop = main.instructions.add(id=10, name="while.1", opcode="while")
    loop.metadata.op_name = "jit(f)/layers/while"
    gte = main.instructions.add(id=11, name="gte.1",
                                opcode="get-tuple-element")
    gte.operand_ids.append(10)
    main.instructions.add(id=12, name="copy.90", opcode="copy") \
        .operand_ids.append(11)
    main.instructions.add(id=13, name="fusion.2", opcode="fusion") \
        .called_computation_ids.append(2)
    main.instructions.add(id=14, name="param.1", opcode="parameter")
    main.instructions.add(id=15, name="convert.1", opcode="convert") \
        .operand_ids.append(14)
    got = program.program_scopes(module)
    assert got["while.1"] == ("layers", "forward", "own")
    assert got["copy.90"] == ("layers", "forward", "operand")
    assert got["fusion.2"] == ("attn", "forward", "fused")
    assert "convert.1" not in got and "param.1" not in got


def test_recorded_trace_gives_spans_scopes_and_idle_by_span(reduced):
    old = trace_reduce.reduce  # the same denominators as the first one
    assert reduced["window_s"] > reduced["busy_s"] > 0
    steps = list(program.spans_named(reduced, "rt.llm.step"))
    assert len(steps) >= 10
    for sp in steps:
        a = sp["attrs"]
        assert a["slots"] == 4 and a["block"] == 1
        assert a["program"] in ("block", "decode_only", "none")
        assert a["active"] + a["prefill_waiting"] <= 4
        assert 0 <= sp["start_s"] <= reduced["window_s"]
    # the probe's five prompts, less the two 16-token pages of the
    # shared prefix that the radix cache matched
    assert sum(sp["attrs"]["prefill_tokens"] for sp in steps) == sum(
        29 + 7 * i for i in range(5)) - 32
    # two pulls interleaved on one thread keep their own bounds
    pulls = {sp["attrs"]["first"]: sp
             for sp in program.spans_named(reduced, "rt.serve.next_chunks")}
    assert 0.009 < pulls[1]["duration_s"] < 0.03
    assert 0.003 < pulls[0]["duration_s"] < pulls[1]["duration_s"]
    assert pulls[0]["attrs"]["items"] == 8
    scopes = reduced["scopes"]
    for scope in ("qkv", "kv_write", "kv_gather", "attn", "mlp", "embed",
                  "lm_head", "sample", "prefill_lane", program.CARRY,
                  "fwd_bwd", "optimizer", "grad_norm", "ce"):
        assert scopes.get(scope, 0) > 0, scope
    assert abs(sum(scopes.values()) - reduced["busy_s"]) \
        < 0.02 * reduced["busy_s"]  # device operations do not overlap
    assert scopes[program.UNSCOPED] < 0.1 * reduced["busy_s"]
    assert sum(s for _, s in reduced["unscoped_ops"]) \
        <= scopes[program.UNSCOPED] * 1.0001
    assert reduced["scope_phases"]["ce:backward"] > 0
    assert "optimizer:backward" not in reduced["scope_phases"]
    idle = reduced["idle_by_span"]
    assert abs(sum(idle.values())
               - (reduced["window_s"] - reduced["busy_s"])) < 1e-6
    assert idle["rt.llm.dispatch"] > idle.get("host idle", 0)
    assert old is trace_reduce.reduce


def test_a_program_without_spans_or_scopes_reduces_to_nothing_to_read():
    """The parent of the PR that added them: empty tables, every reader
    None, no error."""
    got = program.reduce(program.load(
        os.path.join(FIXTURES, "1chip.xplane.pb")))
    assert got["spans"] == [] and set(got["scopes"]) == {program.UNSCOPED}
    first = trace_reduce.reduce(trace_reduce.load_xplane(
        os.path.join(FIXTURES, "1chip.xplane.pb")))
    assert got["window_s"] == pytest.approx(first["window_s"], rel=1e-6)
    assert got["busy_s"] == pytest.approx(first["busy_s"], rel=1e-3)
    m = Manifest(ROOT)
    for ctx in ({"trace": dict(first, program=got)}, {"trace": first},
                {"trace": None}, {}):
        for name in json.load(open(os.path.join(
                ROOT, "benchmark", "tools", "program_metrics.json")))[
                    "per_layer"]:
            spec = m.metric_file(name["name"])
            reader = m.load_module("readers", spec["reader"])
            assert reader.read(ctx, **spec["args"]) is None, name["name"]


def test_readers_on_the_recorded_trace(reduced):
    m = Manifest(ROOT)
    ctx = {"trace": {"program": reduced}}

    def read(name):
        spec = m.metric_file(name)
        return m.load_module("readers", spec["reader"]).read(
            ctx, **spec["args"])

    steps = [sp["attrs"] for sp in program.spans_named(reduced,
                                                       "rt.llm.step")
             if sp["attrs"]["program"] != "none"]
    active = read("engine.active_slot_share.batch")
    waiting = read("engine.prefill_wait_share.chat")
    assert active == pytest.approx(
        100 * sum(a["active"] for a in steps) / (4 * len(steps)))
    assert 0 < active < 100 and 0 < waiting < 100
    assert active + waiting <= 100
    assert read("engine.prefill_wait_share.batch") == waiting
    assert read("serve.first_pull_p50_ms") == pytest.approx(10.5, abs=1.0)
    kv = read("step.kv_move_share.chat")
    assert kv == pytest.approx(100 * sum(
        reduced["scopes"][s] for s in program.KV_MOVE) / reduced["busy_s"])
    fwd, bwd, opt = (read(f"train.{h}_share")
                     for h in ("forward", "backward", "optimizer"))
    assert fwd > 0 and bwd > 0 and opt > 0
    assert kv + fwd + bwd + opt < 100


def test_overlay_wires_the_reduction_in_and_changes_no_old_key(tmp_path):
    """``tools/program_overlay.py`` makes, in a copy, the one edit a
    ``benchmark`` PR has to make: the copy's ``reduce_and_remove`` returns
    every key the first reduction returns, byte for byte, plus
    ``program``; the copy's manifest takes the new entries."""
    dest = str(tmp_path / "overlay")
    subprocess.run([sys.executable, os.path.join(
        ROOT, "benchmark", "tools", "program_overlay.py"), ROOT, dest],
        check=True, timeout=120, capture_output=True)
    code = (
        "import json, sys\n"
        "from benchmark.trace import capture\n"
        "print(json.dumps(capture.reduce_and_remove(sys.argv[1]), "
        "sort_keys=True))\n")
    for name in ("1chip", "4chip"):
        tdir = str(tmp_path / name)
        os.makedirs(tdir)
        shutil.copy(os.path.join(FIXTURES, f"{name}.xplane.pb"), tdir)
        out = subprocess.run([sys.executable, "-c", code, tdir], cwd=dest,
                             env=dict(os.environ, PYTHONPATH=dest),
                             check=True, timeout=120, capture_output=True,
                             text=True)
        got = json.loads(out.stdout)
        first = trace_reduce.reduce(trace_reduce.load_xplane(
            os.path.join(FIXTURES, f"{name}.xplane.pb")))
        assert not os.path.exists(tdir)
        assert set(got) == set(first) | {"program"}
        got.pop("program")
        assert json.dumps(got, sort_keys=True) == json.dumps(
            json.loads(json.dumps(first)), sort_keys=True)
        assert os.path.isfile(tdir + ".program.json")
    m = Manifest(dest)
    cell = m.cell("smollm2-1.7b.batch_closed")
    names = {x["name"] for x in cell["metrics"]["per_layer"]}
    assert {"engine.active_slot_share.batch",
            "engine.prefill_wait_share.batch",
            "step.kv_move_share.batch"} <= names
    assert "step.kv_move_share.chat" not in names
    ctx = {"trace": {"program": program.reduce(program.load(RECORDED))}}
    got = compute_metrics(m, [x for x in cell["metrics"]["per_layer"]
                              if x["name"].startswith("step.kv_move")], ctx)
    assert got["step.kv_move_share.batch"]["unit"] == "%"
