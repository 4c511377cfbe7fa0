#!/usr/bin/env python3
"""What stalls a serving cell's engine loop, over the WHOLE window: one
untraced run of a cell with the program's ring tracer on in the replica
(``observability/tracing.py``: every ``rt.*`` step span with its
attributes, ``off_cpu_us`` among them, and every ``rt.gc``), which costs
~30 us a step where a profiler trace costs seconds and sees 5 s of 51.

    chiprun -- python3 benchmark/tools/stall_probe.py --seed <n> [--workload
        smollm2-1.7b.chat_steady] [--seconds 51] [--long-ms 20]

Prints the run's end-to-end metrics, then every engine-loop span that ran
``--long-ms`` or longer (``rt.llm.wait_work`` left out: waiting for a
request is no stall) with what lay beside it — collections, launches that
compiled — the stretches of the engine thread that no span covers, and
every loop span's median with no profiler on;
keeps all of it in ``chiprun_out/stall/<cell>.<seed>.json``. The driver
is ``drivers/serve.py``'s, whole, with its replica class bound to the
subclass below before it starts, as ``drivers/serve_lfm2.py`` binds its
own; nothing of the benchmark is edited.

Made for ``chat_steady`` (ten runs on the chip, PR 37), and runs on the
two closed-loop cells too (PR 37 after review, parent and change). On
those read the medians, not the stalls: a ring of 100 000 live span
records makes every generation-2 collection 70-130 ms (three or four a
run, each an ``rt.gc`` beside the long span it made), and the ring costs
the ``lfm2`` cell ~12 % of its tokens. A traced run has neither
(``tools/host_split.py``)."""
import argparse
import collections
import json
import os
import sys
import time

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
sys.path.insert(0, ROOT)

from benchmark.drivers.serve_lfm2_replica import (  # noqa: E402
    REFERENCE_MAX_GAP, Lfm2BenchServer)
from benchmark.drivers.serve_replica import BenchLLMServer  # noqa: E402

RING = 400_000  # spans: ~250 a second of chat_steady, 5000 of lfm2
LOOP = "rt.llm."
WAITS = ("rt.llm.wait_work",)


class _Ring:
    """A benchmark replica with the ring tracer on from the end of
    set-up; what the ring holds is written out when the reference check
    starts, which is after the window and its grace."""

    def __init__(self, *args, **kwargs):
        from ray_tpu.observability import tracing

        super().__init__(*args, **kwargs)
        self._probe_out = os.path.join(kwargs["bench_root"], "chiprun_out",
                                       "stall", "ring.json")
        tracer = tracing.get_tracer()
        tracer.max_spans = RING
        tracer._spans = collections.deque(maxlen=RING)
        tracing.enable()
        # the ring only: nothing is shipped to the head
        tracer.export_enabled = False
        self._probe_stats_calls = 0

    def stats(self) -> dict:
        # the driver's second call is its ``before``, taken after the
        # warm-up requests and just before the window: the ring starts
        # there
        self._probe_stats_calls += 1
        if self._probe_stats_calls == 2:
            from ray_tpu.observability import tracing

            tracing.get_tracer().clear()
        return super().stats()

    def check_reference(self, samples, reference):
        from ray_tpu.observability import tracing

        tracing.disable()
        spans = [[s.name, s.start_s, s.end_s, s.attributes]
                 for s in tracing.get_tracer().spans("rt.")
                 if s.end_s is not None]
        os.makedirs(os.path.dirname(self._probe_out), exist_ok=True)
        with open(self._probe_out, "w") as fh:
            json.dump({"spans": spans, "stats": self.stats()}, fh)
        return super().check_reference(samples, reference)


class RingServer(_Ring, BenchLLMServer):
    pass


class Lfm2RingServer(_Ring, Lfm2BenchServer):
    pass


def stalls(spans: list, long_ms: float) -> dict:
    """Long engine-loop spans, long collections, compiling launches and
    uncovered stretches of the loop, times in ms from the first span."""
    t0 = min(s[1] for s in spans)
    loop = sorted((s for s in spans if s[0].startswith(LOOP)),
                  key=lambda s: s[1])
    gcs = [s for s in spans if s[0] == "rt.gc"]

    def row(s):
        return {"name": s[0], "at_ms": round((s[1] - t0) * 1e3, 1),
                "ms": round((s[2] - s[1]) * 1e3, 2), "attrs": s[3]}

    def beside(s):
        return [row(g) for g in gcs if g[1] < s[2] and g[2] > s[1]
                and g[2] - g[1] >= 1e-3]

    long_spans = sorted(
        (dict(row(s), gc_beside=beside(s)) for s in loop
         if s[0] not in WAITS and (s[2] - s[1]) * 1e3 >= long_ms),
        key=lambda r: -r["ms"])[:40]
    # stretches between two top-level spans of the loop
    top = [s for s in loop if s[0] in ("rt.llm.step", "rt.llm.acquire",
                                       "rt.llm.wait_work")]
    gaps = [{"after": a[0], "at_ms": round((a[2] - t0) * 1e3, 1),
             "ms": round((b[1] - a[2]) * 1e3, 2)}
            for a, b in zip(top, top[1:]) if (b[1] - a[2]) * 1e3 >= 5.0]
    steps = sorted((s[2] - s[1]) * 1e3 for s in loop
                   if s[0] == "rt.llm.step")
    # the host's step with no profiler on: medians, and the off-CPU
    # share as a ratio of sums (tools/host_split.py says why)
    by_name = {}
    for name in sorted({s[0] for s in loop}):
        mine = [s for s in loop if s[0] == name]
        ms = sorted((s[2] - s[1]) * 1e3 for s in mine)
        wall = sum(s[3].get("wall_us", 0.0) for s in mine)
        by_name[name] = {
            "n": len(ms), "p50_ms": round(ms[len(ms) // 2], 4),
            "total_ms": round(sum(ms), 1),
            "off_cpu_share": round(100.0 * sum(
                s[3].get("off_cpu_us", 0.0) for s in mine) / wall, 2)
            if wall else None}
    return {"spans": len(spans), "steps": len(steps),
            "step_p50_ms": steps[len(steps) // 2] if steps else None,
            "by_name": by_name,
            "long": long_spans, "uncovered": gaps,
            "gc_long": [row(g) for g in gcs if g[2] - g[1] >= 5e-3],
            "gc_total_ms": round(sum(g[2] - g[1] for g in gcs) * 1e3, 2),
            "gc_by_generation": dict(collections.Counter(
                str(g[3].get("generation")) for g in gcs)),
            "compiled": [row(s) for s in loop
                         if s[0] == "rt.llm.dispatch.launch"
                         and s[3].get("compiled")]}


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", default="smollm2-1.7b.chat_steady")
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, default=51.0)
    ap.add_argument("--long-ms", type=float, default=20.0)
    ap.add_argument("--rehearsal", action="store_true",
                    help="let the replica run on the CPU (tiny sizes)")
    args = ap.parse_args()
    from benchmark import run as bench
    from benchmark.drivers import serve as base
    from benchmark.manifest import Manifest, compute_metrics
    from benchmark.tools import stall_probe as me  # the importable class

    t0 = time.time()
    manifest = Manifest(ROOT)
    cell = manifest.cell(args.workload)
    if args.rehearsal:  # XLA's CPU loader chokes on entries it reads back
        import shutil

        fresh = os.path.join(ROOT, ".jax_cache", "rehearsal")
        shutil.rmtree(fresh, ignore_errors=True)
        os.environ["JAX_COMPILATION_CACHE_DIR"] = fresh
    bench.place_caches()
    if cell["config"]["driver"] == "serve_lfm2":   # as drivers/serve_lfm2.py
        base.BenchLLMServer = me.Lfm2RingServer
        base.REFERENCE_MAX_GAP = REFERENCE_MAX_GAP
    else:
        base.BenchLLMServer = me.RingServer
    out = base.run(manifest, cell, seed=args.seed, seconds=args.seconds,
                   trace=False, t0=t0, log=bench.log,
                   rehearsal=args.rehearsal)
    ctx = dict(out["ctx"], config=cell["config"], traffic=cell["traffic"],
               chips=cell["chips"], seconds=args.seconds)
    metrics = compute_metrics(manifest, cell["metrics"]["end_to_end"], ctx)
    ring_path = os.path.join(ROOT, "chiprun_out", "stall", "ring.json")
    with open(ring_path) as fh:
        ring = json.load(fh)
    os.remove(ring_path)
    found = stalls(ring["spans"], args.long_ms)
    stats = ring["stats"]
    result = {"workload": args.workload, "seed": args.seed,
              "correct": bool(out["correct"]), "failed": out["failed"],
              "device": out["device"],
              "metrics": {k: v["value"] for k, v in metrics.items()},
              "tpot_ms_top": sorted(ctx["series"]["tpot_ms"])[-4:],
              "counters": {k: stats[k] for k in (
                  "gc_pauses", "gc_pause_s", "compiles", "compile_s",
                  "compile_cache_hits") if k in stats},
              "startup_s": stats.get("startup_s"), **found}
    with open(os.path.join(ROOT, "chiprun_out", "stall",
                           f"{args.workload}.{args.seed}.json"), "w") as fh:
        json.dump(result, fh)
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
