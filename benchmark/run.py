#!/usr/bin/env python3
"""The benchmark's one command.

    python3 benchmark/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

A new process for every run. It finds the cell, its configuration, its
traffic mix, its driver and its metrics by name (``manifest.py``), so it
knows none of them itself. This process never initialises a JAX backend:
the replica or the train worker that the driver starts holds the chip. A
run that finds no accelerator, or fewer chips than the cell asks for,
exits non-zero and prints no result.

The last line of standard output is the one JSON object the contract
fixes; everything else (medians, counts, set-up split, lateness) is on
earlier lines.
"""

from __future__ import annotations

import argparse
import json
import os
import sys
import time
import traceback

T0 = time.time()  # process start, as near as Python lets us see it
ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def log(msg: str) -> None:
    print(f"[bench {time.time() - T0:7.1f}s] {msg}", flush=True)


def place_caches() -> str:
    """The compile cache sits where ``JAX_COMPILATION_CACHE_DIR`` says or,
    failing that, at one fixed path inside the checkout (the path is part
    of the cache's key). Exported before anything imports JAX, so every
    process this one starts inherits it; small programs are cached too,
    so that a second run finds every program there."""
    cache = os.environ.setdefault("JAX_COMPILATION_CACHE_DIR",
                                  os.path.join(ROOT, ".jax_cache"))
    os.environ.setdefault("JAX_PERSISTENT_CACHE_MIN_COMPILE_TIME_SECS", "0")
    os.environ.setdefault("JAX_PERSISTENT_CACHE_MIN_ENTRY_SIZE_BYTES", "-1")
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    parts = [ROOT] + [p for p in os.environ.get("PYTHONPATH", "").split(
        os.pathsep) if p]
    os.environ["PYTHONPATH"] = os.pathsep.join(parts)
    return cache


def no_backend_here() -> None:
    bridge = sys.modules.get("jax._src.xla_bridge")
    if bridge is not None and bridge.backends_are_initialized():
        raise RuntimeError("the benchmark's parent process initialised a JAX "
                           "backend: it would hold the chip its worker needs")


def run(args) -> dict:
    sys.path.insert(0, ROOT)
    from benchmark.manifest import Manifest, compute_metrics

    manifest = Manifest(ROOT)
    cell = manifest.cell(args.workload)
    cache = place_caches()
    log(f"cell {cell['name']}: config {cell['config']['name']}, "
        f"{cell['chips']} chip(s), {args.seconds}s, seed {args.seed}, "
        f"trace {args.trace}; compile cache {cache}")
    if os.environ.get("JAX_PLATFORMS", "").strip().lower() == "cpu":
        raise RuntimeError("JAX is pinned to the CPU (JAX_PLATFORMS=cpu): "
                           "this benchmark measures only on the chip")
    driver = manifest.load_module("drivers", cell["config"]["driver"])
    out = driver.run(manifest, cell, seed=args.seed, seconds=args.seconds,
                     trace=bool(args.trace), t0=T0, log=log)
    device = out["device"]
    if device.get("platform") in (None, "cpu") or \
            device.get("count", 0) < cell["chips"]:
        raise RuntimeError(f"cell needs {cell['chips']} accelerator chip(s); "
                           f"the worker found {device}")
    no_backend_here()
    ctx = dict(out["ctx"], config=cell["config"], traffic=cell["traffic"],
               chips=cell["chips"], seconds=args.seconds,
               peaks=manifest.peaks(device["kind"]))
    for note in out.get("notes", []):
        log(note)
    kind = "per_layer" if args.trace else "end_to_end"
    metrics = compute_metrics(manifest, cell["metrics"][kind], ctx)
    if not args.trace:
        # The tails' medians and counts, beside them, never in place of them.
        for name, values in sorted(ctx.get("series", {}).items()):
            if values:
                s = sorted(values)
                log(f"series {name}: n={len(s)} p50={s[len(s) // 2]:.3f} "
                    f"min={s[0]:.3f} max={s[-1]:.3f}")
    result = {"correct": bool(out["correct"]), "attempted": out["attempted"],
              "failed": out["failed"], "metrics": metrics, "device": device}
    trace = ctx.get("trace")
    if args.trace and trace:
        device["busy_s"] = trace["busy_s"]
        device["window_s"] = trace["window_s"]
        result["breakdown"] = {"device_ops": trace["device_ops"][:10],
                               "idle_gaps": trace["idle_gaps"][:10]}
    return result


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--seconds", type=float, default=10.0)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args()
    try:
        result = run(args)
    except BaseException:  # noqa: BLE001 — reported, then a non-zero exit
        traceback.print_exc()
        sys.stderr.flush()
        log("FAILED: no result line")
        return 1
    log(f"done in {time.time() - T0:.1f}s")
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
