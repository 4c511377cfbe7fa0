"""The engine loop's own time account (``llm/engine.py``): every stage's
count, seconds and longest run through ``step_span(.., into=)``, and the
HOLES, steps ``HOLE_S`` or more over their kind's typical, each kept with
the stage it lay under and what the engine's thread and the submitting
thread did meanwhile. All of it with no profiler and no ring tracer on;
the same numbers ride on the step's span where one records; three
per-layer metrics read them. CPU, tiny model; every wait has a bound.

No test here asserts that the host made no hole of its own."""

import gc
import json
import os
import sys
import threading
import time

import jax
import numpy as np
import pytest

from ray_tpu.llm import engine as engine_mod
from ray_tpu.llm.engine import HOLE_S, HOLES_KEPT, SlotEngine, _Typical
from ray_tpu.models import llama
from ray_tpu.observability import tracing
from ray_tpu.observability.event_stats import EventStats

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

from benchmark.manifest import Manifest, compute_metrics  # noqa: E402

CFG = llama.CONFIGS["llama-tiny"]
PROMPT = list(range(1, 20))
STALL_S = 0.060
CELLS = ("lfm2-24b-a2b.decode_heavy_closed",
         "solar-open2-250b.reasoning_closed_1k",
         "granite-4.0-h-micro.rag_closed_1k")
METRICS = ("engine.hole_ms", "engine.caller_cpu_share",
           "engine.submit_p90_ms")
LOOP = ("acquire", "schedule", "dispatch", "dispatch.pack",
        "dispatch.upload", "dispatch.launch", "fetch", "deliver", "step")


@pytest.fixture(scope="module")
def params():
    p, _ = llama.init_params(jax.random.PRNGKey(0), CFG)
    return p


@pytest.fixture
def untraced():
    """The account's own conditions: no ring, no profiler."""
    assert not tracing.get_tracer().enabled
    yield
    assert not tracing.get_tracer().enabled


@pytest.fixture
def tracer():
    t = tracing.get_tracer()
    t.clear()
    tracing.enable()
    yield t
    tracing.disable()
    t.clear()


def _counts(eng):
    return {r["handler"][len("rt.llm."):]: r
            for r in eng.loop_account()["stages"]}


# -- (a) every stage, counted -------------------------------------------------

@pytest.mark.parametrize("threaded", [False, True],
                         ids=["stepped", "engine-thread"])
def test_every_stage_is_counted_with_no_tracer_on(params, untraced,
                                                  threaded):
    """After N steps the account holds ``rt.llm.step`` N times and each
    stage as often as a step ran it: the schedule every step, the
    dispatch and its three parts once a dispatched program, the fetch and
    the deliver once a block (every block dispatched is fetched once the
    engine has drained), ``rt.llm.submit`` once a request, on the
    caller's side. Total and longest run come with each."""
    eng = SlotEngine(params, CFG, num_slots=3, chunk=16, decode_block=2,
                     prefix_cache=False)
    assert isinstance(eng.account, EventStats)
    assert eng.account is not SlotEngine(
        params, CFG, num_slots=1, chunk=16).account  # one an engine
    n = 0
    if threaded:
        eng.start()
    handles = [eng.submit(PROMPT + [i], max_new=9) for i in range(5)]
    if threaded:
        for h in handles:
            h.result(timeout=120)
        # the last block in flight is fetched after its requests ended
        deadline = time.monotonic() + 60
        while _counts(eng)["fetch"]["count"] < eng.steps_block \
                + eng.steps_decode_only and time.monotonic() < deadline:
            time.sleep(0.01)
        eng.stop()
    else:
        while eng.step():
            n += 1
            assert n < 4000
    got = _counts(eng)
    assert set(LOOP) | {"submit"} <= set(got)
    dispatched = eng.steps_block + eng.steps_decode_only
    assert dispatched > 8
    steps = got["step"]["count"]
    if not threaded:
        assert steps == n
        assert got["acquire"]["count"] == n + 1  # the call that found none
        assert "wait_work" not in got
    assert got["schedule"]["count"] == steps >= dispatched
    for stage in ("dispatch", "dispatch.pack", "dispatch.upload",
                  "dispatch.launch", "fetch", "deliver"):
        assert got[stage]["count"] == dispatched, stage
    assert got["submit"]["count"] == len(handles)
    for row in got.values():
        assert 0 < row["max_ms"] <= row["total_ms"]
        assert row["mean_us"] == pytest.approx(
            row["total_ms"] * 1e3 / row["count"], rel=1e-3, abs=0.2)
    # a stage lies inside its step: the parts add up to no more
    assert got["dispatch"]["total_ms"] + got["fetch"]["total_ms"] \
        + got["deliver"]["total_ms"] <= got["step"]["total_ms"]
    account = eng.loop_account()
    assert account["holes"] == len(account["last_holes"]) <= HOLES_KEPT
    assert json.loads(json.dumps(account)) == account  # plain data


# -- (b) a hole, the stage it lay under, and who was on the CPU ---------------

class _Stall:
    """Something the engine's thread calls once a token or once a fetch
    that stalls ``STALL_S`` at call number ``at`` (well after the kind's
    typical has settled) and never again."""

    def __init__(self, how, at=20):
        self.how, self.at, self.calls = how, at, 0
        self.go, self.back = threading.Event(), threading.Event()

    def __call__(self, *_):
        self.calls += 1
        if self.calls != self.at:
            return
        if self.how == "spin":
            # off the CPU until the CALLER's thread has burned STALL_S:
            # what waiting for an interpreter the caller holds reads
            self.go.set()
            assert self.back.wait(30)
            return
        if self.how == "gc":
            gc.collect()
        time.sleep(STALL_S)


@pytest.mark.parametrize("case", ["fetch", "deliver", "deliver-spin",
                                  "deliver-gc", "step"])
def test_a_stall_is_one_hole_under_its_stage(params, untraced,
                                             monkeypatch, case):
    """60 ms lost once, under ``fetch`` (the device's answer late: a
    patched ``np.asarray``) or under ``deliver`` (an ``on_token`` that
    sleeps), is one hole: ``stage`` names it, ``over_ms`` is the stall,
    ``off_cpu_ms`` about as much, and ``caller_cpu_ms`` tells the two
    off-CPU cases apart: small where everyone slept, about the stall
    where the submitting thread spun on the interpreter meanwhile. A
    collection inside the step shows in the hole's ``gc_ms``. A stall that
    none of the step's six stages covers (on the chip: the fetched
    array's release) lies under ``step``, the step's own remainder."""
    stage, _, how = case.partition("-")
    stall = _Stall(how or "sleep")
    eng = SlotEngine(params, CFG, num_slots=2, chunk=16,
                     prefix_cache=False).start()
    try:
        eng.submit(PROMPT, max_new=12).result(timeout=120)  # compiles
        before = eng.loop_account()
        if stage == "fetch":
            real = np.asarray

            def late(a, *args, **kw):
                if isinstance(a, jax.Array):
                    stall()
                return real(a, *args, **kw)

            monkeypatch.setattr(engine_mod.np, "asarray", late)
        elif stage == "step":  # between the schedule and the dispatch
            pages_read = eng._pages_read
            eng._pages_read = lambda *a: (stall(), pages_read(*a))[1]
        h = eng.submit(PROMPT, max_new=40,
                       on_token=stall if stage == "deliver" else None)
        if how == "spin":
            assert stall.go.wait(60)
            end = time.thread_time() + STALL_S
            while time.thread_time() < end:
                pass
            stall.back.set()
        h.result(timeout=120)
    finally:
        monkeypatch.undo()
        eng.stop()
    after = eng.loop_account()
    assert stall.calls >= stall.at
    new = [x for x in after["last_holes"]
           if x["step"] > max([0] + [y["step"]
                                     for y in before["last_holes"]])]
    assert after["holes"] - before["holes"] == len(new) >= 1
    assert after["hole_s"] - before["hole_s"] == pytest.approx(
        sum(x["over_ms"] for x in new) / 1e3, abs=1e-4)
    mine = [x for x in new if x["stage"] == stage
            and x["over_ms"] >= STALL_S * 1e3 - 15]
    assert len(mine) == 1, new
    (hole,) = mine
    assert hole["program"] == "decode_only" and hole["active"] == 1
    assert hole["over_ms"] == pytest.approx(
        hole["wall_ms"] - hole["typical_ms"], abs=0.01)
    if how != "gc":  # a loaded host oversleeps; a collection takes long
        assert hole["over_ms"] < STALL_S * 1e3 + 150
    assert 0 < hole["typical_ms"] < 50
    assert set(hole["stages_ms"]) == {"schedule", "pack", "upload",
                                      "launch", "fetch", "deliver", "step"}
    # "step" is what none of the six covers: they add up to the wall
    assert sum(hole["stages_ms"].values()) == pytest.approx(
        hole["wall_ms"], abs=0.01)
    assert hole["stages_ms"][stage] == max(hole["stages_ms"].values()) \
        >= STALL_S * 1e3 - 5
    assert hole["stages_ms"][stage] <= hole["wall_ms"]
    assert hole["off_cpu_ms"] >= STALL_S * 1e3 - 25
    assert hole["compiled"] == 0 and hole["admitted"] == 0
    assert abs(hole["t_unix"] - time.time()) < 300
    if how == "spin":
        assert hole["caller_cpu_ms"] >= STALL_S * 1e3 - 25
    else:
        assert hole["caller_cpu_ms"] <= 25
    if how == "gc":
        assert hole["gc_ms"] > 0
    elif stage == "deliver":
        assert hole["callbacks"] == 1
    # the account's longest run of that stage is the stall
    row = next(r for r in after["stages"]
               if r["handler"] == "rt.llm." + stage)
    assert row["max_ms"] >= STALL_S * 1e3 - 5


def test_a_long_wait_for_the_engines_lock_is_a_hole_of_its_own(params,
                                                               untraced):
    """``rt.llm.acquire`` lies between steps: a wait of ``HOLE_S`` or
    more for the lock (here held 60 ms by another thread, asleep) is a
    hole with ``stage`` ``acquire`` and a typical of zero, whatever the
    steps around it took; ``rt.llm.wait_work`` never is one."""
    eng = SlotEngine(params, CFG, num_slots=2, chunk=16,
                     prefix_cache=False).start()
    taken = threading.Event()

    def on_token(tok):
        if not taken.is_set():
            taken.set()
            time.sleep(0.005)  # let the holder reach the lock first

    def holder():
        assert taken.wait(60)
        with eng._lock:
            time.sleep(STALL_S)

    t = threading.Thread(target=holder)
    t.start()
    try:
        time.sleep(3 * HOLE_S)  # an idle engine: wait_work, no hole
        assert eng.loop_account()["holes"] == 0
        eng.submit(PROMPT, max_new=30, on_token=on_token).result(
            timeout=120)
    finally:
        t.join(60)
        eng.stop()
    account = eng.loop_account()
    waits = [x for x in account["last_holes"] if x["stage"] == "acquire"]
    assert len(waits) == 1, account["last_holes"]
    (hole,) = waits
    assert hole["typical_ms"] == 0.0 and hole["program"] == "none"
    assert STALL_S * 1e3 - 10 <= hole["over_ms"] == hole["stages_ms"][
        "acquire"] < STALL_S * 1e3 + 150
    assert hole["off_cpu_ms"] >= STALL_S * 1e3 - 25
    rows = {r["handler"]: r for r in account["stages"]}
    assert rows["rt.llm.wait_work"]["max_ms"] >= 3 * HOLE_S * 1e3 - 5
    assert rows["rt.llm.acquire"]["max_ms"] >= STALL_S * 1e3 - 10


# -- (c) the typical ----------------------------------------------------------

def test_a_hole_never_enters_the_typical(params, untraced):
    """Ten holes in a row leave the typical where it was: every one of
    them is measured against the same number."""
    stall = _Stall("sleep")

    def on_token(tok):
        stall.calls += 1
        if 20 <= stall.calls < 30:
            time.sleep(1.5 * HOLE_S)

    eng = SlotEngine(params, CFG, num_slots=2, chunk=16,
                     prefix_cache=False).start()
    try:
        eng.submit(PROMPT, max_new=12).result(timeout=120)
        eng.submit(PROMPT, max_new=40, on_token=on_token).result(
            timeout=120)
    finally:
        eng.stop()
    mine = [x for x in eng.loop_account()["last_holes"]
            if x["stage"] == "deliver"]
    assert len(mine) >= 10
    run = mine[-10:]
    assert [x["step"] for x in run] == list(range(run[0]["step"],
                                                  run[0]["step"] + 10))
    assert len({x["typical_ms"] for x in run}) == 1
    assert all(x["over_ms"] >= HOLE_S * 1e3 for x in run)


@pytest.mark.parametrize("first,want", [
    ([5.0] * 8, 5.0),
    ([9000.0, 0.4] + [5.0] * 6, 5.0),   # a compile, an empty pipeline
    ([4.0, 6.0] * 4, 6.0)],
    ids=["steady", "outliers", "two-levels"])
def test_the_typical_settles_on_a_median_and_follows_the_load(first, want):
    ms = 1e-3
    typical = _Typical()
    for wall in first:
        assert typical.over(wall * ms) == 0.0  # not settled: no hole yet
    assert typical.mean == pytest.approx(want * ms)
    # under HOLE_S over: enters; the mean follows within a few dozen steps
    for _ in range(64):
        assert typical.over((want + 10.0) * ms) < HOLE_S
    assert typical.mean == pytest.approx((want + 10.0) * ms, rel=0.05)
    held = typical.mean
    for _ in range(_Typical.RESEED - 1):
        assert typical.over(held + 2 * HOLE_S) == pytest.approx(2 * HOLE_S)
    assert typical.mean == held
    # a step under the typical ends the run of holes and pulls it down
    assert typical.over(held - 16 * ms) == pytest.approx(-16 * ms)
    assert typical.mean == pytest.approx(held - ms)
    # RESEED holes in a row are the new load: the estimate starts again
    for _ in range(_Typical.RESEED):
        typical.over(1.0)
    assert typical.mean is None


# -- (d) the same numbers on the spans ----------------------------------------

def test_the_hole_rides_on_the_steps_span_and_submit_is_the_callers(params,
                                                                    tracer):
    stall = _Stall("sleep")
    eng = SlotEngine(params, CFG, num_slots=2, chunk=16,
                     prefix_cache=False).start()
    try:
        eng.submit(PROMPT, max_new=12).result(timeout=120)
        tracer.clear()
        with tracing.span("caller") as mine:
            h = eng.submit(PROMPT, max_new=40, on_token=stall)
        h.result(timeout=120)
    finally:
        eng.stop()
    steps = tracer.spans("rt.llm.step")
    assert steps
    for s in steps:
        assert {"hole_ms", "caller_cpu_us", "wall_us",
                "off_cpu_us"} <= set(s.attributes)
        assert ("hole_stage" in s.attributes) == (
            s.attributes["hole_ms"] > 0)
        assert s.attributes["caller_cpu_us"] >= 0
    holes = [s.attributes for s in steps if s.attributes["hole_ms"] > 0
             and s.attributes["hole_stage"] == "deliver"]
    assert len(holes) == 1
    kept = [x for x in eng.loop_account()["last_holes"]
            if x["stage"] == "deliver"][-1]
    assert holes[0]["hole_ms"] == pytest.approx(kept["over_ms"], abs=0.01)
    assert holes[0]["wall_us"] == pytest.approx(kept["wall_ms"] * 1e3,
                                                abs=1.0)
    assert holes[0]["caller_cpu_us"] == pytest.approx(
        kept["caller_cpu_ms"] * 1e3, abs=1.0)
    # rt.llm.submit: on the caller's thread, under the caller's span
    (submit,) = tracer.spans("rt.llm.submit")
    assert submit.parent_id == mine.span_id
    assert submit.trace_id == mine.trace_id
    assert all(s.trace_id != mine.trace_id for s in steps)
    assert mine.start_s <= submit.start_s <= submit.end_s <= mine.end_s


# -- (e) LLMServer.stats() ----------------------------------------------------

def test_stats_loop_agrees_with_the_engines_counters(untraced):
    from ray_tpu.llm.serve import LLMServer

    server = LLMServer(model="llama-tiny", num_slots=2, chunk=16)
    try:
        hs = [server.engine.submit(PROMPT + [i], max_new=6)
              for i in range(3)]
        for h in hs:
            h.result(timeout=120)
        # the engine's thread leaves its last step before it waits again
        deadline = time.monotonic() + 30
        while time.monotonic() < deadline:
            stats = server.stats()
            rows = {r["handler"]: r for r in stats["loop"]["stages"]}
            if rows["rt.llm.step"]["count"] \
                    == rows["rt.llm.schedule"]["count"] \
                    and rows["rt.llm.fetch"]["count"] \
                    == stats["steps_block"] + stats["steps_decode_only"]:
                break
            time.sleep(0.01)
    finally:
        server.engine.stop()
    loop = stats["loop"]
    assert set(loop) == {"stages", "holes", "hole_s", "last_holes"}
    assert loop["holes"] == server.engine.holes
    assert loop["hole_s"] == pytest.approx(server.engine.hole_s, abs=1e-6)
    # the engine's own table (stopping it cost one more wait and acquire)
    table = {r["handler"]: r for r in server.engine.account.snapshot()}
    for stage in LOOP[1:]:
        assert rows["rt.llm." + stage] == table["rt.llm." + stage]
    dispatched = stats["steps_block"] + stats["steps_decode_only"]
    assert rows["rt.llm.dispatch"]["count"] == dispatched
    assert rows["rt.llm.fetch"]["count"] == dispatched
    assert rows["rt.llm.submit"]["count"] == len(hs) + 1  # the warm-up's
    assert {"rt.llm." + s for s in LOOP} <= set(rows)
    json.dumps(stats["loop"])


def test_the_token_counter_is_one_increment_a_deliver(params, untraced):
    """``rt_llm_tokens_generated_total`` (``/metrics``) holds what
    ``tokens_generated`` holds, incremented once a deliver by the tokens
    delivered; ``rt_llm_ttft_seconds`` stays one observation a request."""
    from ray_tpu.llm.paged import llm_metrics

    family = llm_metrics()
    assert family is not None  # telemetry is on by default

    def total(metric):
        _, data = metric.collect()
        return sum(v["count"] if isinstance(v, dict) else v
                   for v in data.values())

    incs = []
    inc = family["tokens"].inc
    tokens0, ttft0 = total(family["tokens"]), total(family["ttft"])
    eng = SlotEngine(params, CFG, num_slots=3, chunk=16, decode_block=2,
                     prefix_cache=False)
    family["tokens"].inc = lambda v=1.0, **kw: (incs.append(v),
                                                inc(v, **kw))[1]
    try:
        handles = [eng.submit(PROMPT + [i], max_new=9) for i in range(4)]
        for _ in range(4000):
            if not eng.step():
                break
    finally:
        del family["tokens"].inc
    assert eng.tokens_generated == 36
    assert total(family["tokens"]) - tokens0 == eng.tokens_generated
    assert total(family["ttft"]) - ttft0 == len(handles)
    delivers = eng.account.snapshot()
    delivers = next(r["count"] for r in delivers
                    if r["handler"] == "rt.llm.deliver")
    assert len(incs) <= delivers < eng.tokens_generated
    assert sum(incs) == eng.tokens_generated and max(incs) > 1


# -- (f) the three metrics ----------------------------------------------------

def _span(name, start, dur, thread="llm-engine", **attrs):
    return {"name": name, "thread": thread, "start_s": start,
            "duration_s": dur, "attrs": attrs}


def _program(hole=True, submits=True):
    """Four engine steps of 10 ms, one of them (where asked) 40 ms with a
    30 ms hole under ``fetch``; ten ``rt.llm.submit`` on the replica's
    event-loop thread of 0.1 .. 1.0 ms."""
    spans, t = [], 0.0
    for i in range(4):
        over = 30.0 if hole and i == 2 else 0.0
        attrs = dict(slots=4, active=3, program="decode_only",
                     wall_us=10000.0 + over * 1e3, off_cpu_us=6000.0,
                     caller_cpu_us=2500.0 + (7500.0 if over else 0.0),
                     hole_ms=over)
        if over:
            attrs["hole_stage"] = "fetch"
        spans.append(_span("rt.llm.step", t, 0.010 + over / 1e3, **attrs))
        t += 0.010 + over / 1e3
    if submits:
        spans += [_span("rt.llm.submit", 0.002 * i, 0.0001 * (i + 1),
                        thread="asyncio_0") for i in range(10)]
    return {"window_s": 0.1, "busy_s": 0.06, "spans": spans,
            "idle_by_span": {"rt.llm.fetch": 0.03}}


@pytest.fixture(scope="module")
def manifest():
    return Manifest(ROOT)


@pytest.mark.parametrize("cell", CELLS)
@pytest.mark.parametrize("name", METRICS)
def test_a_metric_resolves_in_its_three_cells_and_reads_the_program_part(
        manifest, name, cell):
    """ONE file and ONE entry a metric, listing the three cells whose
    replica puts the program part where readers look; each reads the
    expected number from a fabricated program reduction, 0.0 where the
    engine stepped and no hole lay in the window, nothing with no program
    part (an untraced run, the parent's trace)."""
    (spec,) = [m for m in manifest.cell(cell)["metrics"]["per_layer"]
               if m["name"] == name]
    assert spec["workloads"] == list(CELLS)
    assert spec["source"] == "program_span" and spec["better"] == "lower"
    assert spec["moves"] == "out_tokens_per_s"
    assert spec["layer"] == manifest.metrics[
        "engine.gc_pause_ms.lfm2"]["layer"]

    def read(program):
        got = compute_metrics(manifest, [spec], {"trace": program and {
            "program": program}})
        return got[name]["value"] if got else None

    want = {"engine.hole_ms": 30.0,
            # (4 x 2.5 + 7.5) ms of caller CPU over (4 x 10 + 30) ms
            "engine.caller_cpu_share": 100.0 * 17.5 / 70.0,
            "engine.submit_p90_ms": 0.9}[name]
    assert read(_program()) == pytest.approx(want)
    quiet = {"engine.hole_ms": 0.0, "engine.caller_cpu_share": 25.0,
             "engine.submit_p90_ms": 0.9}[name]
    assert read(_program(hole=False)) == pytest.approx(quiet)
    assert read(None) is None
    assert read({"spans": [], "window_s": 1.0, "idle_by_span": {}}) is None
    # the parent's program: steps without the new attributes, no submit
    parent = _program(hole=False, submits=False)
    for sp in parent["spans"]:
        del sp["attrs"]["hole_ms"], sp["attrs"]["caller_cpu_us"]
    assert read(parent) == (0.0 if name == "engine.hole_ms" else None)


def test_the_three_are_the_only_entries_added_and_sit_at_the_end(manifest):
    with open(os.path.join(ROOT, "BENCHMARK.json")) as fh:
        per_layer = json.load(fh)["per_layer"]
    # the end of the list as PR 56 found it, 56 entries: what later PRs
    # add comes after the three (PR 57: ``kernel.flash_fwd_roofline``)
    added = per_layer[56:59]
    assert [m["name"] for m in added] == list(METRICS)
    for m in added:
        assert set(m) == {"name", "unit", "better", "source", "layer",
                          "moves", "workloads"}
    assert {m["unit"] for m in added} == {"ms", "%"}
    for other in ("smollm2-1.7b.batch_closed", "smollm2-1.7b.chat_steady",
                  "gpt2-large.pretrain_1k"):
        assert not {m["name"] for m in manifest.cell(other)["metrics"][
            "per_layer"]} & set(METRICS)


# -- the probe that reads it, through a cell's own driver ---------------------

def test_hole_probe_reads_the_account_through_the_cells_driver(tmp_path):
    """``benchmark/tools/hole_probe.py`` on the CPU at a tiny size: the
    llama cells' driver (``drivers/serve.py`` itself; the families' bind
    their class to it, ``tests/test_solar_serving.py``'s tiny root rehearses
    that path by hand), a real replica through ``serve.run`` and HTTP with
    no tracer on, two seeds one process each. The run is the benchmark's:
    ``correct``, its end-to-end metrics; beside them the holes and every
    stage's untraced mean, kept a seed."""
    import shutil
    import subprocess

    fixtures = os.path.join(ROOT, "benchmark", "tests", "fixtures")
    shutil.copytree(os.path.join(ROOT, "benchmark"), tmp_path / "benchmark",
                    ignore=shutil.ignore_patterns("__pycache__", "tests",
                                                  "tools"))
    os.mkdir(tmp_path / "benchmark/tools")
    shutil.copy(os.path.join(ROOT, "benchmark/tools/hole_probe.py"),
                tmp_path / "benchmark/tools/hole_probe.py")
    shutil.copy(os.path.join(fixtures, "tiny-llama.json"),
                tmp_path / "benchmark/configs/tiny-llama.json")
    shutil.copy(os.path.join(fixtures, "tiny_chat.json"),
                tmp_path / "benchmark/traffic/tiny_chat.json")
    with open(os.path.join(ROOT, "BENCHMARK.json")) as fh:
        bench = json.load(fh)
    bench["configs"] = [{"name": "tiny-llama", "source": "test fixture",
                         "file": "benchmark/configs/tiny-llama.json",
                         "reduced": [], "why": "tiny"}]
    bench["workloads"] = [{"name": "tiny.chat", "config": "tiny-llama",
                           "traffic": "tiny_chat", "chips": 1,
                           "why": "tiny"}]
    for kind in ("end_to_end", "per_layer"):
        bench[kind] = [dict(m, workloads=["tiny.chat"]) for m in bench[kind]
                       if "workloads" not in m
                       or "smollm2-1.7b.chat_steady" in m["workloads"]]
    with open(tmp_path / "BENCHMARK.json", "w") as fh:
        json.dump(bench, fh)
    seeds = [2**31 + 56, 7]
    env = dict(os.environ, JAX_PLATFORMS="cpu", PYTHONPATH=ROOT)
    p = subprocess.run(
        [sys.executable, str(tmp_path / "benchmark/tools/hole_probe.py"),
         "--root", str(tmp_path), "--workload", "tiny.chat", "--seconds",
         "2", "--rehearsal", "--seed"] + [str(s) for s in seeds],
        env=env, capture_output=True, text=True, timeout=280)
    assert p.returncode == 0, p.stdout[-3000:] + p.stderr[-3000:]
    table = p.stdout.strip().splitlines()[-len(seeds) - 1:]
    assert table[0].split()[:4] == ["seed", "ttft_p90_ms", "tpot_p90_ms",
                                    "setup_s"]
    assert [int(row.split()[0]) for row in table[1:]] == seeds
    for seed in seeds:
        with open(tmp_path / f"chiprun_out/holes/tiny.chat.{seed}.json") as fh:
            kept = json.load(fh)
        assert kept["correct"] and kept["failed"] == 0
        assert kept["metrics"]["ttft_p90_ms"] > 0
        assert kept["holes"] >= kept["in_window"] >= 0
        assert kept["holes"] >= kept["holes_kept"] == len(
            kept["window_holes"]) + len(kept["other_holes"])
        assert kept["hole_s"] == pytest.approx(sum(
            h["over_ms"] for h in kept["window_holes"]
            + kept["other_holes"]) / 1e3, abs=1e-3) or \
            kept["holes"] > kept["holes_kept"]
        stages = kept["stages"]
        assert {"rt.llm." + s for s in LOOP + ("submit",)} <= set(stages)
        # twelve requests a window of two seconds, each submitted once
        assert stages["rt.llm.submit"]["count"] >= 10
        assert stages["rt.llm.step"]["mean_ms"] > 0
        lo, hi = kept["window_unix"]
        assert hi - lo == pytest.approx(2.0)
        assert all(lo <= h["t_unix"] <= hi for h in kept["window_holes"])
    assert not os.path.exists(tmp_path / "chiprun_out/holes/account.json")
