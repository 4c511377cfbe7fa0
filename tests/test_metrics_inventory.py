"""Metrics inventory: every emitted ``rt_*`` series is documented.

Satellite of ISSUE 16: run a smoke workload that touches the task,
actor, serve-free LLM, and flight-recorder instrumentation, scrape the
dashboard's ``/metrics``, and assert every ``rt_*`` base name appearing
in the exposition is listed in COMPONENTS.md's "Metrics inventory"
table — so a new metric cannot ship undocumented (and a renamed one
cannot leave a stale table row pointing at nothing).
"""

import os
import re
import time
import urllib.request

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

_HIST_SUFFIXES = ("_bucket", "_sum", "_count")


def _documented_metrics() -> set:
    text = open(os.path.join(REPO, "COMPONENTS.md")).read()
    try:
        section = text.split("### Metrics inventory", 1)[1]
        section = section.split("\n## ", 1)[0]
    except IndexError:  # pragma: no cover - doc structure regression
        section = ""
    return set(re.findall(r"`(rt_[a-z0-9_]+)`", section))


def _emitted_base_names(text: str) -> set:
    names = set()
    for line in text.splitlines():
        if not line or line.startswith("#"):
            continue
        name = line.split("{", 1)[0].split(" ", 1)[0]
        if not name.startswith("rt_"):
            continue
        for suffix in _HIST_SUFFIXES:
            if name.endswith(suffix):
                name = name[:-len(suffix)]
                break
        names.add(name)
    return names


def test_every_emitted_metric_is_documented(rt_init):
    rt = rt_init

    @rt.remote
    def inv_task(x):
        return x + 1

    @rt.remote
    class InvActor:
        def ping(self):
            return 1

    assert rt.get([inv_task.remote(i) for i in range(4)],
                  timeout=60) == [1, 2, 3, 4]
    a = InvActor.remote()
    assert rt.get(a.ping.remote(), timeout=60) == 1
    # LLM family: a series only appears in the exposition once touched
    # — publish the page gauges the way an idle engine would.
    from ray_tpu.llm.paged import llm_metrics

    m = llm_metrics()
    assert m is not None
    m["pages_used"].set(0.0)
    # One telemetry flush so worker-side series reach the head.
    from ray_tpu.core.config import config

    time.sleep(config().metrics_report_interval_ms / 1000.0 + 0.5)

    from ray_tpu.observability import start_dashboard, stop_dashboard

    start_dashboard(port=18277)
    try:
        with urllib.request.urlopen(
                "http://127.0.0.1:18277/metrics", timeout=15) as r:
            text = r.read().decode()
    finally:
        stop_dashboard()

    emitted = _emitted_base_names(text)
    documented = _documented_metrics()
    assert documented, "COMPONENTS.md metrics inventory table missing"
    # The workload above must actually exercise the planes under test.
    for required in ("rt_tasks_submitted", "rt_task_latency_seconds",
                     "rt_task_stage_seconds", "rt_llm_pages_used"):
        assert required in emitted, sorted(emitted)
    undocumented = emitted - documented
    assert not undocumented, (
        f"emitted metrics missing from COMPONENTS.md inventory: "
        f"{sorted(undocumented)}")
