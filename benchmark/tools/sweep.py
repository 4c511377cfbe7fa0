#!/usr/bin/env python3
"""Find an open-loop cell's knee once, on the chip: the same traffic at
several fixed rates, one run each.

    chiprun -- python benchmark/tools/sweep.py --workload <cell> \
        --rates 1,1.5,2,2.5,3,3.5 --seconds 30

The knee is the highest rate at which at least 95% of the requests due
finish, none is shed, and the median TTFT of the last third of the window
is not more than 1.5 x that of the first third. The cell then runs at 0.8
of it, written into its traffic file by hand. Prints one JSON line a rate
and writes them all to ``chiprun_out/sweep/<cell>.jsonl``.
"""
import argparse
import json
import os
import statistics
import sys
import time

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
sys.path.insert(0, ROOT)


def main() -> int:
    from benchmark import run as bench_run
    from benchmark.manifest import Manifest
    from benchmark.readers import series_stat

    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", required=True)
    ap.add_argument("--rates", required=True)
    ap.add_argument("--seconds", type=float, default=30.0)
    ap.add_argument("--seed", type=int, default=1)
    args = ap.parse_args()
    bench_run.place_caches()
    manifest = Manifest(ROOT)
    out_dir = os.path.join(ROOT, "chiprun_out", "sweep")
    os.makedirs(out_dir, exist_ok=True)
    rows = []
    for rate in (float(r) for r in args.rates.split(",")):
        cell = manifest.cell(args.workload)
        cell["traffic"]["rate_per_s"] = rate
        driver = manifest.load_module("drivers", cell["config"]["driver"])
        out = driver.run(manifest, cell, seed=args.seed,
                         seconds=args.seconds, trace=False, t0=time.time(),
                         log=lambda s: print(f"[sweep {rate}] {s}",
                                             flush=True))
        ctx = out["ctx"]
        reqs = ctx["requests"]
        third = args.seconds / 3
        med = lambda lo, hi: statistics.median(
            [r["ttft_ms"] for r in reqs
             if lo <= r["due_s"] < hi and r["ttft_ms"] is not None] or [-1])
        row = {
            "rate_per_s": rate, "sent": len(reqs),
            "finished_share": sum(r["ok"] for r in reqs) / len(reqs),
            "correct": out["correct"],
            "ttft_p50_ms": series_stat.read(ctx, "ttft_ms", 50),
            "ttft_p90_ms": series_stat.read(ctx, "ttft_ms", 90),
            "tpot_p50_ms": series_stat.read(ctx, "tpot_ms", 50),
            "tpot_p90_ms": series_stat.read(ctx, "tpot_ms", 90),
            "ttft_p50_first_third_ms": med(0, third),
            "ttft_p50_last_third_ms": med(2 * third, args.seconds),
            "late_p90_ms": series_stat.read(ctx, "late_ms", 90),
            "notes": out["notes"][-1]}
        rows.append(row)
        print(json.dumps(row), flush=True)
        with open(os.path.join(out_dir, args.workload + ".jsonl"),
                  "a") as fh:
            fh.write(json.dumps(row) + "\n")
    return 0


if __name__ == "__main__":
    sys.exit(main())
