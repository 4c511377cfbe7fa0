"""Step-level spans, counters and scope names inside the program.

What the benchmark's trace reduction reads comes from here: the
``rt.llm.*`` spans with their counts, the engine's cumulative step
counters, ``request_timings()``, and the ``jax.named_scope`` names on the
compiled programs. Nothing starts a cluster; every wait has a bound.
"""

import glob
import os
import re
import subprocess
import sys
import time

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from ray_tpu.llm.engine import HostInputs, SlotEngine
from ray_tpu.models import gpt2, llama
from ray_tpu.observability import tracing

CFG = llama.CONFIGS["llama-tiny"]
STEP_CHILDREN = {"rt.llm.schedule", "rt.llm.dispatch", "rt.llm.fetch",
                 "rt.llm.deliver"}
# where a dispatch's work happens, each once a dispatched step
DISPATCH_CHILDREN = {"rt.llm.dispatch.pack", "rt.llm.dispatch.upload",
                     "rt.llm.dispatch.launch"}
# the spans a metric or PERF.md's stated operator's read takes the
# thread's clock on; the sleep / spin test below says what the two mean
CPU_SPANS = {"rt.llm.step", "rt.llm.dispatch", "rt.llm.dispatch.upload",
             "rt.llm.deliver"}
COUNTERS = SlotEngine.STEP_COUNTERS
# The parent commit's greedy tokens for PROMPT on the CPU (scopes are
# metadata: the same program, the same tokens).
PROMPT = list(range(1, 40))
PARENT_GREEDY = [38, 38, 38, 38, 38, 38, 38, 38, 38]
PARENT_SAMPLED = [289, 355, 304, 199, 227, 496, 219, 464, 59]


@pytest.fixture(scope="module")
def params():
    p, _ = llama.init_params(jax.random.PRNGKey(0), CFG)
    return p


@pytest.fixture
def tracer():
    t = tracing.get_tracer()
    t.clear()
    tracing.enable()
    yield t
    tracing.disable()
    t.clear()


def _drain(eng, limit=4000):
    for _ in range(limit):
        if not eng.step():
            return
    raise AssertionError("engine did not drain")


@pytest.mark.parametrize("eos", [None, "third"], ids=["length", "eos"])
def test_step_spans_children_counts_and_token_invariant(params, tracer,
                                                        eos):
    """Every ``rt.llm.step`` holds its children inside its bounds (all
    four once the lag-1 pipeline is full); the spans' counts add up to
    the engine's counters; and tokens delivered + overshot == slot-steps
    that decoded + one first token per finished prefill, with and
    without an EOS that cuts requests short mid-block."""
    block = 2
    # no prefix cache: every prompt token is prefilled and no page is
    # shared, so the spans' token and page counts are plain sums
    eng = SlotEngine(params, CFG, num_slots=4, chunk=16,
                     decode_block=block, prefix_cache=False)
    eos_id = None
    if eos:
        probe = eng.submit(PROMPT, max_new=9, temperature=0.7, seed=5)
        _drain(eng)
        eos_id = probe.result(timeout=0).tokens[2]
        tracer.clear()
    before = {k: getattr(eng, k) for k in COUNTERS + ("tokens_generated",)}
    handles = [eng.submit(list(range(1, 30 + 5 * i)), max_new=9,
                          eos_id=eos_id) for i in range(6)]
    handles.append(eng.submit(PROMPT, max_new=9, eos_id=eos_id,
                              temperature=0.7, seed=5))
    _drain(eng)
    results = [h.result(timeout=0) for h in handles]
    if eos:
        assert results[-1].finish_reason == "stop"
        assert len(results[-1].tokens) == 3
    delta = {k: getattr(eng, k) - v for k, v in before.items()}

    spans = tracer.spans("rt.llm.")
    steps = [s for s in spans if s.name == "rt.llm.step"]
    assert steps and all(s.end_s is not None for s in spans)
    full = 0
    for st in steps:
        kids = [s for s in spans if s.parent_id == st.span_id]
        assert {k.name for k in kids} <= STEP_CHILDREN
        assert len(kids) == len({k.name for k in kids})
        assert all(st.start_s <= k.start_s and k.end_s <= st.end_s
                   for k in kids)
        a = st.attributes
        assert a["slots"] == 4 and a["block"] == block
        assert a["active"] + a["prefill_waiting"] <= a["slots"]
        assert a["pages_written"] <= a["pages_allocated"]
        names = {k.name for k in kids}
        assert ("rt.llm.dispatch" in names) == (a["program"] != "none")
        full += names == STEP_CHILDREN
        for d in (k for k in kids if k.name == "rt.llm.dispatch"):
            parts = [s for s in spans if s.parent_id == d.span_id]
            assert sorted(p.name for p in parts) == sorted(DISPATCH_CHILDREN)
            assert all(d.start_s <= p.start_s and p.end_s <= d.end_s
                       for p in parts)
            launch = next(p.attributes for p in parts
                          if p.name == "rt.llm.dispatch.launch")
            # a step with no prompt chunk calls the pure-decode program
            assert launch["program"] == a["program"]
    for s in spans:
        # no bound on one span's off_cpu_us: a kernel that charges CPU
        # time a tick at a time reads below 0 or all of the wall (PERF.md)
        assert (s.name in CPU_SPANS) == ("wall_us" in s.attributes) \
            == ("off_cpu_us" in s.attributes), s.name
        if s.name in CPU_SPANS:
            assert s.attributes["wall_us"] > 0
    assert full >= len(steps) // 2  # the steady state has all four
    ran = [s.attributes for s in steps if s.attributes["program"] != "none"]
    assert sum(a["active"] for a in ran) * block \
        == delta["slot_steps_active"]
    assert sum(a["prefill_waiting"] for a in ran) * block \
        == delta["slot_steps_prefill_wait"]
    assert len(ran) * 4 * block == delta["slot_steps"]
    assert sum(a["prefill_tokens"] for a in ran) == delta["prefill_tokens"]
    assert sum(a["program"] == "block" for a in ran) == delta["steps_block"]
    assert sum(a["program"] == "decode_only" for a in ran) \
        == delta["steps_decode_only"]
    assert delta["prefill_tokens"] == sum(
        r.prompt_len for r in results)
    deliver = [s.attributes for s in spans if s.name == "rt.llm.deliver"]
    assert sum(a["delivered"] for a in deliver) == delta["tokens_generated"]
    assert sum(a["finished"] for a in deliver) == len(handles)
    assert sum(a["overshoot"] for a in deliver) == delta["overshoot_tokens"]
    # nothing in flight: the token invariant
    assert delta["tokens_generated"] == sum(len(r.tokens) for r in results)
    assert delta["tokens_generated"] + delta["overshoot_tokens"] \
        == delta["slot_steps_active"] + len(handles)
    # lag-1 dispatch computes one block for nobody per request, at least
    assert delta["overshoot_tokens"] >= len(handles)


@pytest.mark.parametrize("lane", [8, 16, 64])
def test_prefill_lane_fill_is_the_spans_tokens_over_their_lanes(
        params, tracer, lane):
    """``prefill_lane_fill`` is ``prefill_tokens / (steps_block x lane)``;
    a step span that dispatched the fused program says ``lane``, any
    other 0, so the same ratio comes out of the spans alone."""
    eng = SlotEngine(params, CFG, num_slots=2, chunk=lane,
                     prefix_cache=False)
    assert eng.prefill_lane_fill == 0.0   # before any step, not 0 / 0
    lengths = (5, lane, lane + 3, 39)
    handles = [eng.submit(list(range(1, 1 + n)), max_new=4)
               for n in lengths]
    _drain(eng)
    assert all(h.result(timeout=0).tokens for h in handles)
    steps = [s.attributes for s in tracer.spans("rt.llm.")
             if s.name == "rt.llm.step"]
    assert all(a["lane"] == (lane if a["program"] == "block" else 0)
               for a in steps)
    chunks = sum(-(-n // lane) for n in lengths)
    assert eng.steps_block == chunks
    assert sum(a["lane"] for a in steps) == eng.steps_block * lane
    assert sum(a["prefill_tokens"] for a in steps) == sum(lengths) \
        == eng.prefill_tokens
    assert eng.prefill_lane_fill == sum(lengths) / (chunks * lane)


def test_request_timings_keep_streamed_requests_and_forget(params,
                                                           monkeypatch):
    """A streamed request (tokens through ``on_token``, as the replica's
    stream takes them) leaves its timing in ``request_timings()``; the
    deque forgets beyond ``TIMINGS_KEPT``."""
    monkeypatch.setattr(SlotEngine, "TIMINGS_KEPT", 3)
    eng = SlotEngine(params, CFG, num_slots=2, chunk=16)
    t0 = time.time()
    streamed = []
    h = eng.submit(PROMPT, max_new=4, seed=77, on_token=streamed.append,
                   trace_ctx=("req-1", "span-1"))
    _drain(eng)
    assert streamed[-1] is None and len(streamed) == 5
    (kept,) = eng.request_timings()
    assert kept["seed"] == 77 and kept["request_id"] == "req-1"
    assert kept["produced_tokens"] == 4
    assert t0 - 1 <= kept["submit_unix_s"] <= time.time()
    assert {k: v for k, v in kept.items() if k in h.timing} == h.timing
    assert eng.request_timings(since_unix_s=time.time() + 1) == []
    for i in range(4):
        eng.submit([1, 2, 3], max_new=2, seed=100 + i)
    _drain(eng)
    assert [t["seed"] for t in eng.request_timings()] == [101, 102, 103]


def test_pages_read_is_the_dispatched_rows_live_pages(params, tracer):
    """``pages_read`` on ``rt.llm.step`` is the sum of ceil(length /
    page_size) over what the device is handed — every decode row not
    parked, in each of the block's steps, and the prefill lane's slot —
    and ``kv_pages_read`` (``LLMServer.stats()`` returns every name in
    ``STEP_COUNTERS``) is its running sum."""
    ps, block = 8, 2
    eng = SlotEngine(params, CFG, num_slots=3, chunk=16, page_size=ps,
                     decode_block=block, prefix_cache=False)
    assert "kv_pages_read" in SlotEngine.STEP_COUNTERS
    handed = []

    def watching(fn, lane):
        layout = HostInputs(3, CFG.max_seq // ps, 16 if lane else None)

        def call(*args):
            h = layout.views(np.asarray(args[3]))
            pages = sum(-(-(int(p) + k + 1) // ps) for p in h["pos"]
                        for k in range(block) if p + k < CFG.max_seq)
            if lane:
                pages += -(-(int(h["p0"][0]) + int(h["n_valid"][0])) // ps)
            handed.append(pages)
            return fn(*args)
        return call

    eng._decode_only = watching(eng._decode_only, lane=False)
    eng._block = watching(eng._block, lane=True)
    for i in range(5):
        eng.submit(list(range(1, 12 + 9 * i)), max_new=7)
    _drain(eng)
    ran = [s.attributes for s in tracer.spans("rt.llm.step")
           if s.attributes["program"] != "none"]
    assert [a["pages_read"] for a in ran] == handed and len(handed) > 10
    assert eng.kv_pages_read == sum(handed)
    # against gathering every table entry of every row, every step
    assert 0 < eng.kv_pages_read < eng.slot_steps * eng._pages_per_seq


def test_upload_says_the_arrays_and_bytes_the_step_handed_over(params,
                                                               tracer):
    """``rt.llm.dispatch.upload`` counts what the dispatch moved to the
    device: ONE packed vector — five words a row, the page table
    (``[num_slots, pages_per_seq]`` int32 and most of the bytes) and, in
    the fused program's, the lane's chunk and five scalars. The weights,
    the cache and the last tokens never left the device."""
    eng = SlotEngine(params, CFG, num_slots=3, chunk=16, page_size=8,
                     prefix_cache=False)
    handed = []

    def watching(fn):
        def call(*args):
            moved = args[3:]    # params, cache, _last_dev, then host_in
            handed.append((len(moved), sum(a.nbytes for a in moved)))
            return fn(*args)
        return call

    eng._decode_only = watching(eng._decode_only)
    eng._block = watching(eng._block)
    for i in range(4):
        eng.submit(list(range(1, 12 + 9 * i)), max_new=5)
    _drain(eng)
    up = [s.attributes for s in tracer.spans("rt.llm.dispatch.upload")]
    assert [(a["arrays"], a["bytes"]) for a in up] == handed
    table = 3 * (CFG.max_seq // 8) * 4
    rows = 3 * 5 * 4
    assert set(handed) == {(1, table + rows),
                           (1, table + rows + 16 * 4 + 5 * 4)}


def test_deliver_counts_the_callbacks_it_made(params, tracer):
    """``callbacks`` on ``rt.llm.deliver``: one ``on_token`` call a token
    and one more at the end of a request that asked for them — the
    wake-ups of the caller's thread a step. A request is among
    ``requests_completed`` BEFORE its caller is told it ended: the
    callback wakes the caller's thread, which may read ``stats()`` before
    the engine thread runs again (the benchmark's counter check did, on a
    loaded host)."""
    eng = SlotEngine(params, CFG, num_slots=2, chunk=16)
    got, counted_at_end = [], []

    def on_token(tok):
        got.append(tok)
        if tok is None:
            counted_at_end.append(eng.requests_completed)

    eng.submit(PROMPT, max_new=5, on_token=on_token)
    eng.submit(PROMPT[:7], max_new=3)            # no callback asked for
    _drain(eng)
    deliver = [s.attributes for s in tracer.spans("rt.llm.deliver")]
    assert sum(a["callbacks"] for a in deliver) == len(got) == 6
    assert counted_at_end == [2]   # the shorter request, then this one
    assert sum(a["delivered"] for a in deliver) == 8


@pytest.mark.parametrize("body", ["sleeps", "spins"])
def test_cpu_span_tells_a_blocked_thread_from_a_busy_one(tracer, body):
    """``step_span(cpu=True)``: ``off_cpu_us`` is the part of ``wall_us``
    this thread was not on a CPU. A body that sleeps 0.2 s reads nearly
    all of it; one that spins until ITS OWN clock has moved 0.2 s reads
    that much less than its wall time, however long other processes kept
    it from its core. A span without ``cpu`` reads no clock. The room is
    two 10 ms ticks: the machines the chip is measured on charge a thread
    its CPU time a tick at a time, so ONE short span there reads all of
    its wall time or less than none (not clamped: sums stay true)."""
    with tracing.step_span("rt.test.body", cpu=True):
        if body == "sleeps":
            time.sleep(0.2)
        else:
            t0 = time.thread_time()
            while time.thread_time() - t0 < 0.2:
                pass
    with tracing.step_span("rt.test.plain"):
        pass
    (sp,) = tracer.spans("rt.test.body")
    wall, off = sp.attributes["wall_us"], sp.attributes["off_cpu_us"]
    assert wall >= 0.19e6
    on_cpu = wall - off
    if body == "sleeps":
        assert -0.02e6 <= on_cpu <= 0.02e6
    else:
        assert 0.18e6 <= on_cpu <= 0.24e6
    assert tracer.spans("rt.test.plain")[0].attributes == {}


def test_gc_is_a_span_and_a_count(tracer):
    """``watch_gc`` (idempotent): a collection is one ``rt.gc`` span with
    its generation and what it collected, and moves ``gc_pauses`` and
    ``gc_pause_s``."""
    import gc

    hooks = len(gc.callbacks)
    tracing.watch_gc()
    tracing.watch_gc()
    assert len(gc.callbacks) <= hooks + 1
    events = tracing.process_events()
    gc.collect()    # what was lying about goes here, not into the count
    tracer.clear()
    before = events.counters()
    ring = [[]]
    ring[0].append(ring)   # one cycle for the collector to find
    del ring
    gc.collect()
    spans = [s for s in tracer.spans("rt.gc")
             if s.attributes["generation"] == 2]
    assert len(spans) == 1 and spans[0].attributes["collected"] >= 1
    assert spans[0].end_s >= spans[0].start_s
    after = events.counters()
    assert after["gc_pauses"] >= before["gc_pauses"] + 1
    assert after["gc_pause_s"] > before["gc_pause_s"]


def test_compiles_are_counted_and_a_launch_says_what_it_built(params,
                                                              tracer):
    """``watch_compiles``: jitting a new function moves ``compiles`` and
    ``compile_s``, calling it again does not; the ``rt.llm.dispatch.launch``
    that first calls a step program says it built one, and no later one
    does — a compile under load would show as ``compiled`` in a trace."""
    tracing.watch_compiles()
    tracing.watch_compiles()
    events = tracing.process_events()
    x = jnp.arange(7.0)
    salt = time.time()   # a constant no earlier test compiled

    @jax.jit
    def fresh(v):
        return v * salt + 3.0

    before = events.counters()
    fresh(x).block_until_ready()
    built = events.counters()
    assert built["compiles"] == before["compiles"] + 1
    assert built["compile_s"] > before["compile_s"]
    fresh(x).block_until_ready()
    assert events.counters()["compiles"] == built["compiles"]

    # a geometry no other test of this file builds: both programs are new
    eng = SlotEngine(params, CFG, num_slots=5, chunk=8, page_size=8)
    tracer.clear()
    eng.submit(PROMPT, max_new=6)
    _drain(eng)
    launches = [s.attributes for s in tracer.spans("rt.llm.dispatch.launch")]
    first = {}
    for a in launches:
        first.setdefault(a["program"], a["compiled"])
    assert first["block"] >= 1 and first["decode_only"] >= 1
    assert sum(a["compiled"] for a in launches) \
        == first["block"] + first["decode_only"]


@pytest.mark.parametrize("how", ["hook_called", "collection_forced"])
def test_a_collection_inside_the_tracers_lock_does_not_deadlock(tracer, how):
    """The interpreter runs a scheduled collection wherever the thread
    stops next, inside ``Tracer._lock``'s blocks too (``record`` holds it
    round the ring's append on every span of the engine loop). The hook
    therefore takes no lock: its ``rt.gc`` span waits in a lock-free queue
    for the next record or read from ordinary code."""
    import gc
    import threading

    tracing.watch_gc()
    events = tracing.process_events()
    gc.collect()
    tracer.clear()
    before = events.gc_pauses

    def inside_the_lock():
        with tracer._lock:
            if how == "hook_called":
                events._on_gc("start", {"generation": 2, "collected": 0,
                                        "uncollectable": 0})
                events._on_gc("stop", {"generation": 2, "collected": 3,
                                       "uncollectable": 0})
            else:
                gc.collect()

    th = threading.Thread(target=inside_the_lock, daemon=True)
    th.start()
    th.join(timeout=20)
    assert not th.is_alive(), "the gc hook waited for the tracer's lock"
    assert events.gc_pauses >= before + 1
    # nothing reached the ring from the hook itself; a read moves it there
    assert not [s for s in tracer._spans if s.name == "rt.gc"]
    (sp,) = [s for s in tracer.spans("rt.gc")
             if s.attributes["generation"] == 2]
    assert sp.end_s >= sp.start_s
    # ... and so does the next span that ordinary code records
    events._on_gc("start", {"generation": 0, "collected": 0,
                            "uncollectable": 0})
    events._on_gc("stop", {"generation": 0, "collected": 0,
                           "uncollectable": 0})
    with tracing.step_span("rt.test.after"):
        pass
    names = [s.name for s in tracer._spans]
    assert names[-1] == "rt.test.after" and names[-2] == "rt.gc"


def test_a_long_queue_of_collections_is_drained_in_a_loop(tracer):
    """A traced process that records nothing for a while queues one
    ``rt.gc`` a collection (the newest 1024 are kept); the next record
    moves them all into the ring without recursing once a span."""
    events = tracing.process_events()
    info = {"generation": 0, "collected": 0, "uncollectable": 0}
    for _ in range(1500):
        events._on_gc("start", info)
        events._on_gc("stop", info)
    with tracing.step_span("rt.test.after"):
        pass
    assert not tracer._late or len(tracer._late) < 5  # a collection since
    assert len([s for s in tracer._spans if s.name == "rt.gc"]) >= 1024
    assert tracer.spans("rt.test.after")


def test_the_engine_thread_names_its_waits_for_the_lock(params, tracer):
    """Between two steps the engine thread takes the engine's lock twice
    (to look for work, to step): each wait is an ``rt.llm.acquire`` span
    (a wait by its nature: no clock but the wall's), so no stretch of the
    loop is without a name.
    Starting the thread watches collections and compiles."""
    import gc

    eng = SlotEngine(params, CFG, num_slots=2, chunk=16).start()
    try:
        assert eng.submit(PROMPT, max_new=6).result(timeout=120).tokens
    finally:
        eng.stop()
    steps = tracer.spans("rt.llm.step")
    acquires = tracer.spans("rt.llm.acquire")
    assert len(acquires) >= 2 * len(steps) > 0
    assert all(a.end_s >= a.start_s for a in acquires)
    pauses = tracing.process_events().gc_pauses
    gc.collect()
    assert tracing.process_events().gc_pauses > pauses


def _engine_lowered(eng):
    rows = eng.num_slots
    i32 = lambda *s: jax.ShapeDtypeStruct(s, jnp.int32)  # noqa: E731
    common = (eng._params, eng._cache, i32(rows))
    pages = eng._pages_per_seq
    return (eng._block.lower(
                *common, i32(HostInputs(rows, pages, eng.chunk).size)),
            eng._decode_only.lower(
                *common, i32(HostInputs(rows, pages).size)))


def _train_step():
    import optax

    from ray_tpu.parallel.mesh import MeshSpec
    from ray_tpu.train.step import build_sharded_train

    gcfg = gpt2.GPT2Config(vocab_size=256, max_seq=32, num_layers=2,
                           num_heads=2, d_model=32, remat=True,
                           remat_policy="mem2")
    mesh = MeshSpec(fsdp=2).build(jax.devices()[:2])
    sinit, sstep, _ = build_sharded_train(
        lambda k: gpt2.init_params(k, gcfg),
        lambda p, b: gpt2.loss_fn(p, b, gcfg), mesh,
        optimizer=optax.adamw(1e-3), master_fp32=True)
    state = sinit(jax.random.PRNGKey(1))
    batch = {"tokens": jnp.asarray(np.random.default_rng(0).integers(
        0, 256, (4, 33)), jnp.int32)}
    return sstep, state, batch


@pytest.mark.parametrize("program", ["block", "decode_only", "train"])
def test_programs_keep_their_names_and_carry_the_scopes(params, program):
    """The trace reduction finds the engine's programs by
    ``jit_block_fn`` / ``jit_decode_only_fn`` and splits device time by
    scope name: a refactor that renames either fails here instead of
    blanking a metric."""
    if program == "train":
        sstep, state, batch = _train_step()
        lowered = sstep.lower(*state, batch)
        name, scopes = "jit_sharded_step", (
            "fwd_bwd", "optimizer", "grad_norm", "ce", "attn", "mlp")
    else:
        eng = SlotEngine(params, CFG, num_slots=2, chunk=8, page_size=8)
        fused, decode_only = _engine_lowered(eng)
        lowered = fused if program == "block" else decode_only
        name = f"jit_{program}_fn"
        scopes = ("layers", "qkv", "kv_write", "kv_gather", "attn", "mlp",
                  "embed", "lm_head", "sample") + (
                      ("prefill_lane",) if program == "block" else ())
    text = lowered.as_text(debug_info=True)
    assert re.search(r"module @(\S+)", text).group(1) == name
    for scope in scopes:  # a word of some location's name stack
        assert re.search(rf'loc\("[^"]*\b{scope}\b[^"]*"', text), scope


def test_scopes_change_no_token_and_no_loss(params, monkeypatch):
    """Scopes are metadata: the tokens of a fixed greedy and a fixed
    seeded request are the parent commit's, and tokens and three steps'
    losses are bit-identical with every ``jax.named_scope`` turned into
    nothing."""
    import contextlib

    def run():
        eng = SlotEngine(params, CFG, num_slots=2, chunk=16,
                         decode_block=2)
        greedy = eng.submit(PROMPT[:38], max_new=9)
        sampled = eng.submit(PROMPT, max_new=9, temperature=0.7, seed=5)
        _drain(eng)
        sstep, state, batch = _train_step()
        losses = []
        for _ in range(3):
            *state, metrics = sstep(*state, batch)
            losses.append((float(metrics["loss"]).hex(),
                           float(metrics["grad_norm"]).hex()))
        return (greedy.result(timeout=0).tokens,
                sampled.result(timeout=0).tokens, losses)

    scoped = run()
    assert scoped[0] == PARENT_GREEDY and scoped[1] == PARENT_SAMPLED
    monkeypatch.setattr(jax, "named_scope",
                        lambda name: contextlib.nullcontext())
    assert run() == scoped


def test_step_spans_reach_a_profiler_trace(params, tmp_path):
    """The only guard that the spans reach a trace at all: a short
    ``jax.profiler`` trace round a few engine steps has ``rt.llm.step``
    with its attributes in the host plane, on the profiler's clock."""
    from jax.profiler import ProfileData

    eng = SlotEngine(params, CFG, num_slots=2, chunk=16)
    eng.warmup()
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    opts.host_tracer_level = 2
    jax.profiler.start_trace(str(tmp_path), profiler_options=opts)
    try:
        eng.submit(PROMPT, max_new=4)
        _drain(eng)
    finally:
        jax.profiler.stop_trace()
    (path,) = glob.glob(os.path.join(str(tmp_path), "**", "*.xplane.pb"),
                        recursive=True)
    found = {}
    for plane in ProfileData.from_file(path).planes:
        if plane.name != "/host:CPU":
            continue
        for line in plane.lines:
            for ev in line.events:
                if ev.name.startswith("rt.llm."):
                    found.setdefault(ev.name, []).append(
                        (ev.start_ns, ev.duration_ns, dict(ev.stats)))
    assert STEP_CHILDREN | DISPATCH_CHILDREN | {"rt.llm.step"} <= set(found)
    for name in CPU_SPANS:
        assert all(st["wall_us"] > 0 and "off_cpu_us" in st
                   for _, _, st in found[name]), name
    upload = found["rt.llm.dispatch.upload"][0][2]
    assert upload["arrays"] == 1 and upload["bytes"] > 0
    assert {st["program"] for _, _, st in found["rt.llm.dispatch.launch"]} \
        == {"block", "decode_only"}
    steps = found["rt.llm.step"]
    assert all(d > 0 for _, d, _ in steps)
    stats = steps[0][2]
    assert stats["slots"] == 2 and stats["block"] == 1
    assert stats["program"] == "block" and stats["prefill_tokens"] == 16
    assert sum(s["prefill_tokens"] for _, _, s in steps) == len(PROMPT)
    # a child lies inside its step on the trace's own clock
    s0, d0, _ = steps[1]
    assert any(s0 <= s and s + d <= s0 + d0
               for s, d, _ in found["rt.llm.fetch"])


def test_step_span_without_jax_and_tracer_off_allocates_no_span():
    """The head and the proxy never import JAX: there ``step_span`` is a
    no-op that makes no annotation and no ``Span``, and imports nothing."""
    code = (
        "import sys\n"
        "from ray_tpu.observability import tracing\n"
        "made = []\n"
        "init = tracing.Span.__init__\n"
        "def counting(self, *a, **k):\n"
        "    made.append(1); init(self, *a, **k)\n"
        "tracing.Span.__init__ = counting\n"
        "with tracing.step_span('rt.x', a=1) as sp:\n"
        "    sp.set(b=2)\n"
        "    assert not sp.recording\n"
        "with tracing.step_span('rt.y', interleaved=True):\n"
        "    pass\n"
        "assert 'jax' not in sys.modules, 'jax was imported'\n"
        "assert not made and not tracing.get_tracer().spans()\n"
        "tracing.enable()\n"
        "with tracing.step_span('rt.x', a=1) as sp:\n"
        "    sp.set(b=2)\n"
        "with tracing.step_span('rt.y', interleaved=True, c=3):\n"
        "    pass\n"
        "got = {s.name: s.attributes for s in tracing.get_tracer().spans()}\n"
        "assert got == {'rt.x': {'a': 1, 'b': 2}, 'rt.y': {'c': 3}}, got\n"
        "print('ok')\n")
    out = subprocess.run([sys.executable, "-c", code], capture_output=True,
                         text=True, timeout=120,
                         cwd=os.path.dirname(os.path.dirname(
                             os.path.abspath(__file__))))
    assert out.returncode == 0 and out.stdout.strip() == "ok", out.stderr
