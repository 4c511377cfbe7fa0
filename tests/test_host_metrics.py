"""The per-layer metrics that read the host's half of an engine step
(PERF.md section 3): the two readers this adds, on a hand-made reduction
of the program's spans, and every new metric file through the manifest of
the cell that reports it. Nothing runs a model here."""

import json
import os
import sys

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

from benchmark.manifest import Manifest, compute_metrics  # noqa: E402

CELL = "lfm2-24b-a2b.decode_heavy_closed"
HOST = ("engine.dispatch_p50_ms", "engine.launch_p50_ms",
        "engine.dispatch_off_cpu_share", "engine.deliver_off_cpu_share",
        "engine.idle_dispatch_share", "engine.idle_unnamed_share",
        "engine.gc_pause_ms", "engine.compiles_in_trace")
NEW = tuple(f"{m}.lfm2" for m in HOST + ("engine.active_slot_share",
                                         "engine.prefill_wait_share"))


def _span(name, start, dur, **attrs):
    return {"name": name, "thread": "llm-engine", "start_s": start,
            "duration_s": dur, "attrs": attrs}


def _program(extra=(), idle=None):
    """Two engine steps of 10 ms in a 100 ms window, as
    ``benchmark/trace/program.py reduce`` would return them."""
    spans = []
    for i, (off, built) in enumerate(((1000.0, 0), (3000.0, 2))):
        t = 0.010 * i
        spans += [
            _span("rt.llm.step", t, 0.010, slots=4, active=3,
                  prefill_waiting=1, program="block", wall_us=10000.0,
                  off_cpu_us=4000.0),
            _span("rt.llm.dispatch", t + 0.001, 0.004, wall_us=4000.0,
                  off_cpu_us=off),
            _span("rt.llm.dispatch.launch", t + 0.003, 0.002,
                  program="block", compiled=built),
            _span("rt.llm.deliver", t + 0.008, 0.001, wall_us=1000.0,
                  off_cpu_us=100.0)]
    return {"window_s": 0.1, "busy_s": 0.08, "spans": spans + list(extra),
            "idle_by_span": idle if idle is not None else {
                "rt.llm.dispatch": 0.002, "rt.llm.dispatch.upload": 0.003,
                "rt.llm.fetch": 0.004, "rt.llm.step": 0.001,
                "host idle": 0.0015, "rt.llm.acquire": 0.0005}}


@pytest.fixture(scope="module")
def manifest():
    return Manifest(ROOT)


@pytest.fixture(scope="module")
def readers(manifest):
    return {name: manifest.load_module("readers", name).read
            for name in ("idle_by_span_share", "span_sum")}


@pytest.mark.parametrize("trace", [None, {}, {"program": None},
                                   {"program": {"spans": [], "window_s": 1.0,
                                                "idle_by_span": {}}}],
                         ids=["untraced", "no-program", "program-none",
                              "no-steps"])
def test_new_readers_leave_the_metric_out_without_an_engine_loop(readers,
                                                                 trace):
    """None only where there is nothing of the program to read: no trace,
    a reduction without the program part (a driver that does not put it
    there), or a program part the engine's loop left no step in."""
    ctx = {"trace": trace}
    assert readers["idle_by_span_share"](ctx, names=["host idle"]) is None
    assert readers["span_sum"](ctx, span="rt.gc") is None
    assert readers["span_sum"](ctx, span="rt.llm.dispatch.launch",
                               attr="compiled") is None


def test_idle_by_span_share_adds_names_and_prefixes(readers):
    read = readers["idle_by_span_share"]
    ctx = {"trace": {"program": _program()}}
    # dispatch and its children, not a name that merely starts alike
    assert read(ctx, prefixes=["rt.llm.dispatch"]) == pytest.approx(5.0)
    assert read(ctx, names=["host idle", "rt.llm.step"]) \
        == pytest.approx(2.5)
    assert read(ctx, names=["rt.llm.acquire"],
                prefixes=["rt.llm.fetch"]) == pytest.approx(4.5)
    # the engine stepped and the device never waited under such a span
    assert read(ctx, names=["rt.gc"]) == 0.0
    quiet = {"trace": {"program": _program(idle={})}}
    assert read(quiet, names=["host idle"], prefixes=["rt.llm."]) == 0.0


def test_span_sum_totals_durations_or_an_attribute(readers):
    read = readers["span_sum"]
    ctx = {"trace": {"program": _program()}}
    assert read(ctx, span="rt.gc") == 0.0            # no collection
    assert read(ctx, span="rt.llm.dispatch.launch", attr="compiled") == 2.0
    # a span of a program that does not say `compiled` counts 0
    assert read(ctx, span="rt.llm.deliver", attr="compiled") == 0.0
    gcs = [_span("rt.gc", 0.004, 0.0015, generation=0, collected=3),
           _span("rt.gc", 0.015, 0.0200, generation=2, collected=90)]
    ctx = {"trace": {"program": _program(extra=gcs)}}
    assert read(ctx, span="rt.gc") == pytest.approx(21.5)
    assert read(ctx, span="rt.gc", attr="collected") == 93.0


@pytest.mark.parametrize("name", NEW)
def test_a_new_metric_resolves_and_reads_the_cells_program_part(manifest,
                                                                name):
    """Each entry this PR adds to ``BENCHMARK.json`` is found by the
    cell's manifest with its metric file and reader, and reads a number
    from a program part that holds engine steps (never a hole there)."""
    cell = manifest.cell(CELL)
    (spec,) = [m for m in cell["metrics"]["per_layer"] if m["name"] == name]
    assert spec["workloads"] == [CELL] and spec["source"] == "program_span"
    assert spec["moves"] == "out_tokens_per_s"
    ctx = {"trace": {"program": _program()}}
    got = compute_metrics(manifest, [spec], ctx)[name]
    want = {"engine.dispatch_p50_ms.lfm2": 4.0,
            "engine.launch_p50_ms.lfm2": 2.0,
            "engine.dispatch_off_cpu_share.lfm2": 50.0,
            "engine.deliver_off_cpu_share.lfm2": 10.0,
            "engine.idle_dispatch_share.lfm2": 5.0,
            "engine.idle_unnamed_share.lfm2": 2.5,
            "engine.gc_pause_ms.lfm2": 0.0,
            "engine.compiles_in_trace.lfm2": 2.0,
            "engine.active_slot_share.lfm2": 75.0,
            "engine.prefill_wait_share.lfm2": 25.0}[name]
    assert got["value"] == pytest.approx(want) and got["unit"] == spec["unit"]
    assert compute_metrics(manifest, [spec], {"trace": None}) == {}


@pytest.mark.parametrize("suffix,cell,moves", [
    ("batch", "smollm2-1.7b.batch_closed", "out_tokens_per_s"),
    ("chat", "smollm2-1.7b.chat_steady", "tpot_p90_ms")])
def test_the_twins_wait_in_host_metrics_json_with_their_files(
        manifest, tmp_path, suffix, cell, moves):
    """The two ``smollm2-1.7b`` cells' drivers do not put the program
    part into the trace's reduction yet, so their twins of the first
    eight are not in ``BENCHMARK.json``: the entries wait in
    ``benchmark/tools/host_metrics.json``, whole, and appended to a copy
    of ``BENCHMARK.json`` each resolves through its cell, with the
    ``lfm2`` metric's own file."""
    with open(os.path.join(ROOT, "benchmark/tools/host_metrics.json")) as fh:
        waiting = json.load(fh)["per_layer"]
    mine = [m for m in waiting if m["name"].endswith("." + suffix)]
    assert [m["name"] for m in mine] == [f"{m}.{suffix}" for m in HOST]
    assert all(m["workloads"] == [cell] and m["moves"] == moves
               for m in mine)
    assert not {m["name"] for m in waiting} & set(manifest.metrics)
    with open(os.path.join(ROOT, "BENCHMARK.json")) as fh:
        bench = json.load(fh)
    bench["per_layer"] += waiting
    os.symlink(os.path.join(ROOT, "benchmark"), tmp_path / "benchmark")
    with open(tmp_path / "BENCHMARK.json", "w") as fh:
        json.dump(bench, fh)
    overlay = Manifest(str(tmp_path))
    found = {m["name"]: m for m in overlay.cell(cell)["metrics"]["per_layer"]}
    for m in mine:
        twin = manifest.metric_file(m["name"][:-len(suffix)] + "lfm2")
        assert {k: found[m["name"]][k] for k in twin} == twin
