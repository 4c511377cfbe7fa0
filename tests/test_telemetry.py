"""Cluster telemetry plane: worker->head metric/span shipping.

Covers the ISSUE-13 acceptance criteria: a task executed in a WORKER
process must be visible on the head — as node-tagged counters plus a
latency histogram in ``/metrics``, and (with ``tracing_enabled``) as a
span on the worker's own pid row in the merged ``rt timeline`` output,
including the exit-flush path (worker exits before the dump).
"""

import json
import os
import re
import time
import urllib.request

import pytest

_PROM_SAMPLE = re.compile(
    r'^(?P<name>[a-zA-Z_:][a-zA-Z0-9_:]*)'
    r'(?:\{(?P<labels>[^}]*)\})? (?P<value>\S+)$')
_PROM_LABEL = re.compile(r'([a-zA-Z_][a-zA-Z0-9_]*)="((?:[^"\\]|\\.)*)"')


def _scrape(port: int) -> str:
    with urllib.request.urlopen(
            f"http://127.0.0.1:{port}/metrics", timeout=10) as r:
        return r.read().decode()


def _samples(text: str):
    """Parse exposition text -> [(name, {label: value}, float)]."""
    out = []
    for line in text.splitlines():
        if not line or line.startswith("#"):
            continue
        m = _PROM_SAMPLE.match(line)
        assert m is not None, f"malformed exposition line: {line!r}"
        labels = dict(_PROM_LABEL.findall(m.group("labels") or ""))
        out.append((m.group("name"), labels, float(m.group("value"))))
    return out


def test_runtime_metrics_visible_in_cluster_scrape(rt_shared):
    """N tasks + an actor -> head /metrics shows rt_tasks_submitted /
    rt_tasks_finished and a nonzero node-tagged latency histogram."""
    import ray_tpu as rt
    from ray_tpu.observability import start_dashboard, stop_dashboard

    @rt.remote
    def f(x):
        return x + 1

    assert rt.get([f.remote(i) for i in range(8)]) == list(range(1, 9))

    @rt.remote
    class Counter:
        def __init__(self):
            self.n = 0

        def add(self):
            self.n += 1
            return self.n

    counter = Counter.remote()
    assert rt.get([counter.add.remote() for _ in range(3)]) == [1, 2, 3]

    start_dashboard(port=18361)
    try:
        # Each worker ships its series on its own flush interval (1s
        # default), so the eleven calls arrive in as many shipments as
        # workers ran them: poll until every one has, not until the first.
        deadline = time.monotonic() + 20
        while True:
            rows = _samples(_scrape(18361))
            arrived = [sum(v for name, labels, v in rows
                           if name == series and "node" in labels
                           and labels.get("state", "DONE") == "DONE")
                       for series in ("rt_task_latency_seconds_count",
                                      "rt_tasks_finished")]
            if min(arrived) >= 11:
                break
            assert time.monotonic() < deadline, \
                f"node-tagged series of 11 calls: {arrived}; rows={rows[:40]}"
            time.sleep(0.25)

        by_name = {}
        for name, labels, v in rows:
            by_name.setdefault(name, []).append((labels, v))
        submitted = {r[0].get("type"): r[1]
                     for r in by_name["rt_tasks_submitted"]}
        assert submitted.get("task", 0) >= 8
        assert submitted.get("actor", 0) >= 3
        assert submitted.get("actor_creation", 0) >= 1
        # Cluster gauges refreshed at scrape time.
        assert by_name["rt_workers_alive"][0][1] >= 1
        assert by_name["rt_actors_alive"][0][1] >= 1
        assert any(labels.get("node")
                   for labels, _ in by_name["rt_object_store_bytes"])
    finally:
        stop_dashboard()


def test_llm_prefix_metrics_visible_in_cluster_scrape(rt_shared):
    """The rt_llm_* family (ISSUE-15): an engine admission that misses
    then hits the radix prefix cache must show both counter series in
    the dashboard /metrics scrape, alongside the page gauges and a
    nonzero TTFT histogram."""
    import jax

    from ray_tpu.llm.engine import SlotEngine
    from ray_tpu.models import llama
    from ray_tpu.observability import start_dashboard, stop_dashboard

    cfg = llama.CONFIGS["llama-tiny"]
    params, _ = llama.init_params(jax.random.PRNGKey(0), cfg)
    eng = SlotEngine(params, cfg, num_slots=2, chunk=8, page_size=8)
    prompt = list(range(1, 20))
    for _ in range(2):  # first admission misses, second hits
        h = eng.submit(prompt, max_new=4)
        while not h._done.is_set():
            eng.step()
    assert eng.prefix_hits >= 1 and eng.prefix_misses >= 1

    start_dashboard(port=18365)
    try:
        rows = _samples(_scrape(18365))
    finally:
        stop_dashboard()
    prefix = {labels.get("result"): v for name, labels, v in rows
              if name == "rt_llm_prefix_hit"}
    assert prefix.get("hit", 0) >= 1, rows[:40]
    assert prefix.get("miss", 0) >= 1, rows[:40]
    by_name = {name: v for name, labels, v in rows}
    assert by_name.get("rt_llm_prefix_tokens_saved", 0) >= 16
    assert by_name.get("rt_llm_pages_used", -1) >= 1  # scratch at least
    assert by_name.get("rt_llm_pages_free", -1) >= 0
    assert by_name["rt_llm_pages_used"] + by_name["rt_llm_pages_free"] \
        == eng.pages_total
    assert by_name.get("rt_llm_ttft_seconds_count", 0) >= 2


import contextlib


@contextlib.contextmanager
def _traced_runtime(interval_ms: int):
    """Fresh runtime with tracing on and the given flush interval set
    BEFORE any worker spawns; restores config/env/tracer after (other
    modules expect the defaults)."""
    import ray_tpu as rt
    from ray_tpu.core.config import Config
    from ray_tpu.observability import telemetry, tracing

    if rt.is_initialized():
        rt.shutdown()
    overrides = {"RT_TRACING_ENABLED": "1",
                 "RT_METRICS_REPORT_INTERVAL_MS": str(interval_ms)}
    saved = {k: os.environ.get(k) for k in overrides}
    os.environ.update(overrides)
    Config.reset()
    telemetry.clear()
    rt.init(num_cpus=2)
    try:
        yield rt
    finally:
        rt.shutdown()
        for k, v in saved.items():
            if v is None:
                os.environ.pop(k, None)
            else:
                os.environ[k] = v
        Config.reset()
        tracing.disable()
        tracing.get_tracer().clear()
        telemetry.clear()


@pytest.fixture
def rt_traced():
    with _traced_runtime(200) as rt:
        yield rt


@pytest.fixture
def rt_traced_slow_flush():
    # Periodic flushes pushed out of reach (10 min): only the exit
    # flush can deliver a worker's spans.
    with _traced_runtime(600_000) as rt:
        yield rt


def _worker_exec_spans(events, pid=None):
    spans = [e for e in events
             if e.get("ph") == "X" and "task.execute" in str(e.get("name"))
             and e.get("pid") != os.getpid()]
    if pid is not None:
        spans = [e for e in spans if e.get("pid") == pid]
    return spans


def test_cross_process_trace_in_merged_timeline(rt_traced, tmp_path):
    """A task executed in a worker appears in `rt timeline` output on
    its own pid row, with a process_name metadata row naming it."""
    import ray_tpu as rt
    from ray_tpu.observability import timeline

    @rt.remote
    def traced(x):
        return x * 2

    assert rt.get(traced.remote(21)) == 42
    deadline = time.monotonic() + 15
    while True:
        path = timeline(str(tmp_path / "tl.json"))
        events = json.load(open(path))
        spans = _worker_exec_spans(events)
        if spans:
            break
        assert time.monotonic() < deadline, "worker span never shipped"
        time.sleep(0.2)
    worker_pids = {e["pid"] for e in spans}
    named = {e["pid"]: e["args"]["name"] for e in events
             if e.get("ph") == "M"}
    assert any(str(named.get(pid, "")).startswith("worker ")
               for pid in worker_pids)
    # Driver pid row exists too (one merged trace, per-process rows).
    assert named.get(os.getpid()) == "driver"


def test_exit_flush_ships_spans_before_worker_dies(rt_traced_slow_flush):
    """Exit-flush path: with the periodic interval pushed out of reach,
    a worker that finishes and exits must still deliver its spans (the
    final flush in run_task_loop), so `rt timeline` sees it."""
    import gc

    rt = rt_traced_slow_flush
    from ray_tpu.observability import list_workers, timeline

    @rt.remote
    class OneShot:
        def work(self):
            return "done"

    actor = OneShot.remote()
    assert rt.get(actor.work.remote()) == "done"
    worker_pids = {w["pid"] for w in list_workers()
                   if w["state"] == "DEDICATED"}
    assert worker_pids
    # No span from that worker can have arrived yet (interval is 10min).
    assert not _worker_exec_spans(timeline())
    # Handle out of scope -> graceful drain_exit -> final flush.
    del actor
    gc.collect()
    deadline = time.monotonic() + 20
    while True:
        spans = _worker_exec_spans(timeline())
        if any(e["pid"] in worker_pids and "actor.work" in e["name"]
               for e in spans):
            break
        assert time.monotonic() < deadline, \
            f"exit flush never arrived; pids={worker_pids}"
        time.sleep(0.2)
