"""End-to-end LLM serving: engine replica behind serve.run + the HTTP
proxy, with streamed tokens (VERDICT r4 item 1, SURVEY §7.2 step 9)."""

import json
import urllib.request

import jax
import numpy as np
import pytest


@pytest.fixture()
def serve_instance(rt_shared):
    from ray_tpu import serve

    serve.start(http_port=18571)
    yield serve
    serve.shutdown()


def _reference(prompt, max_new):
    from ray_tpu.models import llama

    cfg = llama.CONFIGS["llama-tiny"]
    params, _ = llama.init_params(jax.random.PRNGKey(0), cfg)
    out = llama.generate(params, np.asarray([prompt], dtype=np.int32),
                         cfg, max_new=max_new)
    return [int(t) for t in np.asarray(out)[0, len(prompt):]]


@pytest.mark.parametrize("given,want", [({}, 30.0),
                                        ({"health_check_timeout_s": 2.0},
                                         2.0)])
def test_llm_app_tolerates_a_deaf_replica_longer_than_serve_does(given,
                                                                 want):
    """An engine replica can hold the interpreter lock for tens of
    seconds in a device-runtime call and be healthy (PERF.md, PR 25), so
    the LLM app asks for probes of 30 s unless the caller says otherwise;
    every other deployment keeps Serve's 5 s."""
    from ray_tpu.llm import build_llm_app

    app = build_llm_app(**given)
    assert app.deployment._opts["health_check_timeout_s"] == want
    assert app.deployment._opts["health_check_failure_threshold"] == 3


def test_llm_app_http_and_stream(serve_instance):
    from ray_tpu.llm import build_llm_app

    app = build_llm_app(model="llama-tiny", num_slots=4, chunk=8,
                        seed=0, name="llm")
    serve_instance.run(app)
    prompt = [3, 141, 59, 26, 5]
    ref = _reference(prompt, 10)

    body = json.dumps({"prompt": prompt, "max_tokens": 10}).encode()
    req = urllib.request.Request("http://127.0.0.1:18571/llm", data=body,
                                 headers={"Content-Type":
                                          "application/json"})
    with urllib.request.urlopen(req, timeout=120) as r:
        out = json.loads(r.read())
    assert out["tokens"] == ref
    assert out["finish_reason"] == "length"
    assert out["prompt_len"] == len(prompt)

    # streamed: chunked transfer, one JSON token per line, same tokens
    body = json.dumps({"prompt": prompt, "max_tokens": 10,
                       "stream": True}).encode()
    req = urllib.request.Request("http://127.0.0.1:18571/llm", data=body,
                                 headers={"Content-Type":
                                          "application/json"})
    with urllib.request.urlopen(req, timeout=120) as r:
        lines = [ln for ln in r.read().decode().splitlines() if ln]
    assert [json.loads(ln) for ln in lines] == ref


def test_llm_burst_sheds_with_503(serve_instance):
    """A burst beyond slot + pending capacity must shed with typed 503
    ("overloaded") responses while admitted requests complete normally
    — not stall, not 500, not grow the queue without bound."""
    import threading
    import urllib.error

    from ray_tpu.llm import build_llm_app

    app = build_llm_app(model="llama-tiny", num_slots=1, chunk=8,
                        seed=0, name="llmshed", max_pending=1,
                        queue_timeout_s=30.0)
    serve_instance.run(app)
    prompt = [3, 141, 59, 26, 5]
    ref = _reference(prompt, 8)
    results = {}

    def call(i):
        body = json.dumps({"prompt": prompt, "max_tokens": 8}).encode()
        req = urllib.request.Request(
            "http://127.0.0.1:18571/llmshed", data=body,
            headers={"Content-Type": "application/json"})
        try:
            with urllib.request.urlopen(req, timeout=120) as r:
                results[i] = ("ok", json.loads(r.read()))
        except urllib.error.HTTPError as e:
            results[i] = (e.code, json.loads(e.read()))

    threads = [threading.Thread(target=call, args=(i,))
               for i in range(8)]
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    shed = [v for v in results.values() if v[0] == 503]
    ok = [v for v in results.values() if v[0] == "ok"]
    assert len(results) == 8
    assert shed, f"burst of 8 into 1 slot + 1 pending never shed: " \
                 f"{sorted(k for k, _ in results.values())}"
    assert ok, "every request shed — resident sessions starved"
    for _, body in shed:
        assert body.get("overloaded") is True, body
        assert "overloaded" in body["error"].lower(), body
    for _, body in ok:
        assert body["tokens"] == ref
    assert not any(v[0] == 500 for v in results.values()), results


def test_llm_concurrent_http_requests(serve_instance):
    """Several in-flight HTTP generations share the slot pool."""
    import threading

    from ray_tpu.llm import build_llm_app

    app = build_llm_app(model="llama-tiny", num_slots=4, chunk=8,
                        seed=0, name="llm2")
    serve_instance.run(app)
    rng = np.random.default_rng(5)
    prompts = [[int(t) for t in rng.integers(1, 512, size=n)]
               for n in (4, 9, 6, 12, 5, 7)]
    outs = {}

    def call(i):
        body = json.dumps({"prompt": prompts[i],
                           "max_tokens": 8}).encode()
        req = urllib.request.Request(
            "http://127.0.0.1:18571/llm2", data=body,
            headers={"Content-Type": "application/json"})
        with urllib.request.urlopen(req, timeout=120) as r:
            outs[i] = json.loads(r.read())["tokens"]

    threads = [threading.Thread(target=call, args=(i,))
               for i in range(len(prompts))]
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    for i, p in enumerate(prompts):
        assert outs[i] == _reference(p, 8), f"request {i} diverged"
