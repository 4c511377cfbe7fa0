#!/usr/bin/env python3
"""The host's half of an engine step, from the program part of a traced
run (``trace/program.py reduce``, as ``tools/program_overlay.py`` keeps it
in ``.bench_trace/<cell>.program.json``):

    python3 benchmark/tools/host_split.py <program.json>...

For every ``rt.*`` span name: how many, the median and the largest
duration, and for the spans that carry ``wall_us`` / ``off_cpu_us``
(``observability/tracing.py step_span(cpu=True)``) the share of their wall
time the thread was not on a CPU (a ratio of sums: one span's reading
means nothing where the thread's clock ticks every 10 ms);
``rt.llm.step``'s own remainder (the
step minus its children); what a dispatch uploads; wake-ups a deliver;
collections and compiles; the window's idle seconds by the span they lie
under. Reads only the file: no JAX, no chip."""
import json
import statistics
import sys
from collections import defaultdict

CHILDREN = ("rt.llm.schedule", "rt.llm.dispatch", "rt.llm.fetch",
            "rt.llm.deliver")


def split(program: dict) -> dict:
    by_name = defaultdict(list)
    for sp in program["spans"]:
        by_name[sp["name"]].append(sp)
    out = {"window_s": program["window_s"], "busy_s": program["busy_s"],
           "spans": {}}
    for name, spans in sorted(by_name.items()):
        ms = sorted(sp["duration_s"] * 1e3 for sp in spans)
        row = {"n": len(ms), "p50_ms": statistics.median(ms),
               "max_ms": ms[-1], "total_ms": sum(ms)}
        timed = [sp["attrs"] for sp in spans if "wall_us" in sp["attrs"]]
        wall = sum(a["wall_us"] for a in timed)
        if wall:
            # sums only: where the thread's clock moves a 10 ms tick at a
            # time one span says nothing; on_cpu_ms / 10 is how many
            # ticks the share rests on
            off = sum(a["off_cpu_us"] for a in timed)
            row.update(off_cpu_share=100.0 * off / wall,
                       on_cpu_ms=(wall - off) / 1e3)
        out["spans"][name] = row
    # a step's own remainder: what none of its children covers
    steps = [sp for sp in by_name["rt.llm.step"]
             if sp["attrs"].get("program") != "none"]
    kids = [sp for name in CHILDREN for sp in by_name[name]]
    own = []
    for st in steps:
        lo, hi = st["start_s"], st["start_s"] + st["duration_s"]
        inside = sum(k["duration_s"] for k in kids
                     if lo <= k["start_s"] and
                     k["start_s"] + k["duration_s"] <= hi)
        own.append((st["duration_s"] - inside) * 1e3)
    if own:
        out["step_own_ms"] = {"p50": statistics.median(own),
                              "max": max(own), "total": sum(own)}
    up = [sp["attrs"] for sp in by_name["rt.llm.dispatch.upload"]]
    if up:
        out["upload"] = {k: sorted({a[k] for a in up})
                         for k in ("arrays", "bytes")}
    calls = [sp["attrs"].get("callbacks", 0)
             for sp in by_name["rt.llm.deliver"]]
    if calls:
        out["callbacks_a_deliver"] = {"p50": statistics.median(calls),
                                      "max": max(calls)}
    out["gc"] = [[sp["attrs"].get("generation"), sp["thread"],
                  round(sp["duration_s"] * 1e3, 3)]
                 for sp in by_name["rt.gc"] if sp["duration_s"] >= 1e-3]
    out["compiled"] = sum(sp["attrs"].get("compiled", 0)
                          for sp in by_name["rt.llm.dispatch.launch"])
    out["idle_by_span_ms"] = {
        k: round(v * 1e3, 3) for k, v in sorted(
            program["idle_by_span"].items(), key=lambda kv: -kv[1])}
    return out


def main() -> int:
    for path in sys.argv[1:]:
        with open(path) as fh:
            got = split(json.load(fh))
        print(f"== {path}: window {got['window_s']:.4f}s busy "
              f"{got['busy_s']:.4f}s idle "
              f"{100 * (1 - got['busy_s'] / got['window_s']):.2f}%")
        for name, row in got.pop("spans").items():
            extra = ""
            if "off_cpu_share" in row:
                extra = (f" off-CPU {row['off_cpu_share']:.1f}% "
                         f"(on CPU {row['on_cpu_ms']:.0f}ms)")
            print(f"{name:28s} n={row['n']:5d} p50 {row['p50_ms']:8.3f}ms "
                  f"max {row['max_ms']:8.3f}ms total "
                  f"{row['total_ms']:9.1f}ms{extra}")
        print(json.dumps(got))
    return 0


if __name__ == "__main__":
    sys.exit(main())
