"""Driver of the serving cells: the program is reached as its users reach
it — ``rt.init`` -> ``serve.start`` -> ``serve.run`` -> HTTP — and loaded
from this process by the cell's traffic generator.

Set-up (all inside ``setup_s``): the runtime, the replica (weights from the
seed by the program's own ``init_params``, both engine programs compiled),
and warm-up requests that cover the shortest and the longest prompt. Then
the window. Then, outside both, the counters, the repeated request and the
float32 reference comparison that decide ``correct``.
"""

from __future__ import annotations

import asyncio
import os
import socket
import threading
import time

import numpy as np

from benchmark.drivers import http_client
from benchmark.drivers.serve_replica import BenchLLMServer

TRACE_SECONDS = 5.0
# The largest reference logit minus the reference logit of the token the
# engine chose, at any generated position of the sampled requests. The
# engine computes in bfloat16 (8 bits of mantissa), the reference in
# float32 on the same weights; where the two disagree about the argmax the
# reference's top two logits lie closer than the engine's rounding error.
# Measured on the chip (PERF.md Findings): the largest gap seen was 0.031.
# A precision one step lower (8-bit floats, 3-4 bits of mantissa) has
# sixteen times the rounding error and fails this.
REFERENCE_MAX_GAP = 0.125


def _free_port() -> int:
    with socket.socket() as s:
        s.bind(("127.0.0.1", 0))
        return s.getsockname()[1]


def deploy(cell: dict, seed: int, rehearsal: bool, root: str):
    """``build_llm_app`` as the program defines it at this commit — its own
    defaults for every scheduling option — with the deployment class
    swapped for the benchmark's subclass of it."""
    from ray_tpu import serve
    from ray_tpu.llm import build_llm_app
    from ray_tpu.serve.api import Deployment

    cfg = cell["config"]
    sizes = {k: v for k, v in cfg["deployment"].items() if v is not None}
    app = build_llm_app(model=cfg["name"], seed=seed % (2**31 - 1),
                        ray_actor_options={"num_tpus": cell["chips"]},
                        **sizes)
    dep = Deployment(BenchLLMServer, app.deployment.name,
                     dict(app.deployment._opts))
    return serve.run(dep.bind(
        *app.args, **app.kwargs,
        bench_root=root, bench_config=cfg["name"], bench_chips=cell["chips"], bench_rehearsal=rehearsal))


def _check_result(res: dict, vocab: int) -> str:
    """'' if the response is what was asked for, else what is wrong."""
    if res["error"] or res["status"] != 200:
        return f"status {res['status']} {res['error']}"
    if res["reply"] is not None:
        toks = res["reply"].get("tokens")
        if res["reply"].get("finish_reason") != "length":
            return f"finish_reason {res['reply'].get('finish_reason')!r}"
    else:
        toks = res["tokens"]
    if not isinstance(toks, list) or len(toks) != res["want"]:
        return f"wanted {res['want']} tokens, got {len(toks or [])}"
    if not all(isinstance(t, int) and 0 <= t < vocab for t in toks):
        return "token id out of range"
    return ""


def _tokens(res: dict) -> list:
    return res["reply"]["tokens"] if res["reply"] is not None \
        else res["tokens"]


def run(manifest, cell: dict, seed: int, seconds: float, trace: bool,
        t0: float, log, rehearsal: bool = False) -> dict:
    import ray_tpu as rt
    from ray_tpu import serve

    cfg, traffic = cell["config"], cell["traffic"]
    vocab = cfg["vocab_size"]
    gen = manifest.load_module("traffic", traffic["generator"])
    port = _free_port()
    path = "/llm"
    notes = []
    rt.init(num_cpus=4, resources={"TPU": float(cell["chips"])})
    try:
        serve.start(http_port=port)
        handle = deploy(cell, seed, rehearsal, manifest.root)
        base = rt.get(handle.stats.remote(), timeout=1100)
        device = dict(base["device"])
        log(f"replica ready; set-up inside it {base['startup_s']}; "
            f"device {device}")

        def call(method, *a, timeout=300):
            return rt.get(getattr(handle, method).remote(*a),
                          timeout=timeout)

        plan = gen.plan(traffic, seed, seconds, vocab,
                        deployment=cfg["deployment"])
        warm = gen.warmup(traffic, seed, vocab)
        res = asyncio.run(http_client.run_open(
            "127.0.0.1", port, path, warm, time.monotonic(), 0.0, 300.0))
        bad = [_check_result(r, vocab) for r in res]
        if any(bad):
            raise RuntimeError(f"warm-up request failed: {bad}")
        if trace:
            call("collect_timing", True)
        before = call("stats")

        # ---- the window ---------------------------------------------------
        tracer, traced = None, {}
        if plan["mode"] == "closed":
            # The clients start inside set-up and run for ramp_s before the
            # window opens, so the window sees a full batch throughout.
            t_first = time.monotonic()
            t_start = t_first + plan["ramp_s"]
        else:
            t_start = time.monotonic() + 0.05
        setup_s = (time.time() - t0) + (t_start - time.monotonic())
        if trace:
            tdir = os.path.join(manifest.root, ".bench_trace",
                                cell["name"])

            def _trace():
                delay = t_start + max(0.0, (seconds - TRACE_SECONDS) / 2) \
                    - time.monotonic()
                time.sleep(max(0.0, delay))
                c0 = call("trace_start", tdir)
                time.sleep(min(TRACE_SECONDS, seconds))
                c1 = call("trace_stop")
                traced.update(trace_tokens=c1["tokens"] - c0["tokens"],
                              trace_requests=c1["requests"] - c0["requests"],
                              trace_counts_s=c1["t"] - c0["t"],
                              **call("engine_info"))

            tracer = threading.Thread(target=_trace, name="bench-trace")
            tracer.start()
        if plan["mode"] == "open":
            results = asyncio.run(http_client.run_open(
                "127.0.0.1", port, path, plan["requests"], t_start, seconds,
                plan["grace_s"]))
        else:
            results = asyncio.run(http_client.run_closed(
                "127.0.0.1", port, path, plan["requests"], plan["clients"],
                t_first, plan["start_stagger_s"], t_start + seconds,
                plan["grace_s"]))
        t_end = t_start + seconds
        if tracer is not None:
            tracer.join(timeout=120)
        log(f"window over; {len(results)} requests sent")

        # ---- outside the window: counters, repeat, reference ------------------
        after = call("stats")
        for r in results:
            r["problem"] = _check_result(r, vocab)
        failed = [(r["id"], r["problem"]) for r in results if r["problem"]]
        ok = [r for r in results if not r["problem"]]
        counters_ok = True
        lost = [r for r in results if r["problem"]]
        # A request the client gave up on may still have completed.
        for key, want, slack in (
                ("requests_completed", len(ok), len(lost)),
                ("tokens_generated", sum(r["want"] for r in ok),
                 sum(r["want"] for r in lost))):
            got = after[key] - before[key]
            if not want <= got <= want + slack:
                counters_ok = False
                notes.append(f"counter {key}: replica says {got}, client "
                             f"counted {want}")
        shed = after["requests_shed"] - before["requests_shed"]
        if failed:
            notes.append(f"{len(failed)} failed, first: {failed[:3]}")
        sample = sorted(ok, key=lambda r: r["id"])[:1]
        repeat_ok = True
        requests_by_id = {r["id"]: r for r in plan["requests"]}
        if sample:
            req = dict(requests_by_id[sample[0]["id"]], due_s=0.0)
            again = asyncio.run(http_client.run_open(
                "127.0.0.1", port, path, [req], time.monotonic(), 0.0,
                120.0))[0]
            repeat_ok = (not _check_result(again, vocab)
                         and _tokens(again) == _tokens(sample[0]))
            if not repeat_ok:
                notes.append("the same greedy request gave other tokens "
                             "when repeated after the window")
        pick = np.random.default_rng(seed).permutation(len(ok))[:4]
        samples = [{"prompt": requests_by_id[ok[i]["id"]]["prompt"],
                    "tokens": _tokens(ok[i])} for i in pick]
        log("counters and repeated request checked")
        ref = call("check_reference", samples, cfg["reference"],
                   timeout=600) if samples else None
        log("reference compared")
        ref_ok = bool(ref) and ref["finite"] and \
            ref["max_gap"] <= REFERENCE_MAX_GAP
        notes.append(f"reference: {ref} (bound {REFERENCE_MAX_GAP})")
        mem = call("memory_stats")
        device["memory_peak_bytes"] = mem["memory_peak_bytes"]
        reduced, timings = None, []
        if trace:
            timings = call("engine_timings")
            reduced = call("trace_reduce", timeout=600)
        correct = (not failed and counters_ok and repeat_ok and ref_ok
                   and shed == 0 and len(results) > 0)
        ctx = _context(results, timings, reduced, t_start, t_end, setup_s,
                       after, before, cfg)
        ctx["counters"].update(traced)
        notes.append("ids in order of first token: " + " ".join(
            str(r["id"]) for r in sorted(ok, key=lambda r: r["first_t"])))
        notes.append(
            f"requests {len(results)} ok {len(ok)} shed {shed}; "
            f"tokens in window {ctx['counters']['out_tokens']}; "
            f"completed in window {ctx['counters']['completed_in_window']}; "
            f"set-up {setup_s:.3f}s of which replica "
            f"{base['startup_s']}; memory peak {mem}")
        return {"correct": correct, "attempted": len(results),
                "failed": len(failed), "device": device, "ctx": ctx,
                "notes": notes}
    finally:
        try:
            serve.shutdown()
        finally:
            rt.shutdown()


def _context(results, timings, reduced, t_start, t_end, setup_s, after,
             before, cfg) -> dict:
    """Series and counters the readers take their numbers from. Times in
    milliseconds; every finished request is in the tails, and a failed one
    counts with the time it had been waiting when it was given up."""
    series = {"ttft_ms": [], "tpot_ms": [], "late_ms": [], "e2e_ms": [],
              "hop_ms": [], "engine_queue_ms": []}
    by_id = {t["id"]: t for t in timings}
    out_tokens = 0
    done_in_window = 0
    for r in results:
        if r["sent_t"] is not None:
            series["late_ms"].append((r["sent_t"] - r["due_t"]) * 1e3)
        if r["problem"]:
            # a request that failed misses every limit: it enters the
            # tails at the time it had waited when the client gave up
            waited = ((r["last_t"] or t_end) - r["due_t"]) * 1e3
            series["ttft_ms"].append(waited)
            continue
        series["ttft_ms"].append((r["first_t"] - r["due_t"]) * 1e3)
        series["e2e_ms"].append((r["last_t"] - r["due_t"]) * 1e3)
        if r["reply"] is None and r["want"] > 1:
            series["tpot_ms"].append(
                (r["last_t"] - r["first_t"]) * 1e3 / (r["want"] - 1))
        # Output tokens that reached the client inside the window: a
        # streamed reply's tokens each at their own arrival, an unstreamed
        # reply's all at its return.
        arrivals = r["token_t"] if r["reply"] is None \
            else [r["last_t"]] * r["want"]
        out_tokens += sum(1 for t in arrivals if t_start < t <= t_end)
        done_in_window += t_start < r["last_t"] <= t_end
        timing = by_id.get(r["id"]) or (r["reply"] or {}).get("timing")
        if timing:
            series["engine_queue_ms"].append(
                (timing["admission_s"] + timing["queue_s"]) * 1e3)
            if r["reply"] is None:
                engine_first = (timing["admission_s"] + timing["queue_s"]
                                + timing["prefill_s"])
                series["hop_ms"].append(
                    (r["first_t"] - r["sent_t"] - engine_first) * 1e3)
    counters = {
        "setup_s": setup_s, "window_s": t_end - t_start,
        "out_tokens": out_tokens, "completed_in_window": done_in_window,
        "tokens_generated": after["tokens_generated"]
        - before["tokens_generated"],
        "num_slots": cfg["deployment"]["num_slots"],
    }
    per_request = [{"due_s": r["due_t"] - t_start,
                    "ttft_ms": None if r["problem"] else
                    (r["first_t"] - r["due_t"]) * 1e3,
                    "ok": not r["problem"]} for r in results]
    return {"series": series, "counters": counters, "trace": reduced,
            "requests": per_request}
