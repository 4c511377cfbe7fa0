#!/usr/bin/env python3
"""Where a serving cell's engine loop lost its time, over the WHOLE window
and with nothing switched on: one UNTRACED, un-ringed run of a cell through
that cell's own driver, reading the account the engine keeps anyway
(``ray_tpu/llm/engine.py``: ``LLMServer.stats()["loop"]``: every stage's
count, seconds and longest run, and every HOLE, a step 20 ms or more over
its kind's typical, with the stage it lay under, what the engine's thread
and the replica's event-loop thread did meanwhile, and the collections and
compiles beside it).

    chiprun -- python3 benchmark/tools/hole_probe.py --seed <n> [<n> ...]
        [--workload solar-open2-250b.reasoning_closed_1k] [--seconds 51]

The replica class is taken FROM the cell's driver, whichever family it is:
the driver binds its own class to ``drivers/serve.py`` and this wraps that
module's ``deploy`` to subclass whatever is bound there just before it is
deployed. The subclass only remembers ``stats()["loop"]`` at the driver's
``before`` call (50 ms before the window of an open-loop cell, before the
ramp of a closed-loop one) and writes it with the ``after`` call's (after
the window and its grace) into ``chiprun_out/holes/<cell>.<seed>.json``;
each hole carries ``t_unix``, so those inside the window are told apart.
Nothing of the benchmark is edited and no tracer is on: the run is the
driver's ``--trace 0`` run, its end-to-end metrics the benchmark's.

Prints, a seed: the end-to-end metrics, the holes by stage (count, seconds
over the typical, in the window and in the whole run), each hole of the
window with ``off_cpu_ms`` / ``caller_cpu_ms`` / ``gc_ms`` / ``compiled``,
and every stage's untraced mean and longest run. Several seeds run one
after another, each in a process of its own (a chip belongs to one process
at a time), and a table of ``<metric> hole_s holes`` ends the output.

``--step-cost`` instead times the account itself, no chip: 10 000 steps of
a tiny engine on the CPU whose programs answer at once, us a step with and
without ``into=`` turn about, and the three clocks and the hole rule alone;
``--root`` times a ``git archive`` copy of another commit as it is (a CPU
count, never a chip rate).
``--rehearsal`` runs a tiny root's cell on the CPU (``tests/``).
"""
import argparse
import json
import os
import subprocess
import sys
import time

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))


class _Holes:
    """Mixed in before a cell's replica class: remembers the loop's
    account at the driver's second ``stats()`` call (its ``before``) and
    writes both at the third (its ``after``)."""

    def __init__(self, *args, **kwargs):
        super().__init__(*args, **kwargs)
        self._probe_out = os.path.join(kwargs["bench_root"], "chiprun_out",
                                       "holes", "account.json")
        self._probe_calls = []

    def stats(self) -> dict:
        stats = super().stats()
        self._probe_calls.append({"t_unix": time.time(),
                                  "loop": stats.get("loop")})
        if len(self._probe_calls) == 3:
            os.makedirs(os.path.dirname(self._probe_out), exist_ok=True)
            with open(self._probe_out, "w") as fh:
                json.dump({"before": self._probe_calls[1],
                           "after": self._probe_calls[2]}, fh)
        return stats


def bind(module) -> None:
    """``module.deploy`` (``drivers/serve.py``'s) deploys a subclass of
    whatever replica class is bound to ``module.BenchLLMServer`` when it
    is called: by then the cell's driver has bound its family's."""
    from benchmark.tools import hole_probe as me  # the importable mixin

    deploy = module.deploy

    def deploy_with_holes(*args, **kwargs):
        module.BenchLLMServer = type(
            "HoleProbeServer", (me._Holes, module.BenchLLMServer), {})
        return deploy(*args, **kwargs)

    module.deploy = deploy_with_holes


def account(before: dict, after: dict, t_start: float, t_end: float) -> dict:
    """The run's holes by stage and every stage's mean and longest run,
    from the loop's account at the two ends."""
    b, a = before["loop"], after["loop"]
    kept = [h for h in a["last_holes"]
            if h["t_unix"] > before["t_unix"]]
    inside = [h for h in kept if t_start <= h["t_unix"] <= t_end]
    by_stage = {}
    for h in kept:
        row = by_stage.setdefault(h["stage"], {
            "holes": 0, "hole_s": 0.0, "in_window": 0, "in_window_s": 0.0})
        row["holes"] += 1
        row["hole_s"] = round(row["hole_s"] + h["over_ms"] / 1e3, 6)
        if h in inside:
            row["in_window"] += 1
            row["in_window_s"] = round(
                row["in_window_s"] + h["over_ms"] / 1e3, 6)
    was = {r["handler"]: r for r in b["stages"]}
    stages = {}
    for r in a["stages"]:
        r0 = was.get(r["handler"], {"count": 0, "total_ms": 0.0})
        n = r["count"] - r0["count"]
        if n:
            stages[r["handler"]] = {
                "count": n,
                "mean_ms": round((r["total_ms"] - r0["total_ms"]) / n, 4),
                # the longest run since the ENGINE was built: the account
                # keeps one maximum, not one a window
                "max_ms": r["max_ms"]}
    return {"holes": a["holes"] - b["holes"],
            "hole_s": round(a["hole_s"] - b["hole_s"], 6),
            # more than the engine keeps (32): the oldest are counted above
            # and not listed
            "holes_kept": len(kept),
            "in_window": len(inside),
            "in_window_s": round(sum(h["over_ms"] for h in inside) / 1e3, 6),
            "by_stage": by_stage, "window_holes": inside,
            "other_holes": [h for h in kept if h not in inside],
            "stages": stages}


def run_cell(args, seed: int) -> dict:
    sys.path.insert(0, args.root)
    from benchmark import run as bench
    from benchmark.drivers import serve as shared
    from benchmark.manifest import Manifest, compute_metrics

    t0 = time.time()
    manifest = Manifest(args.root)
    cell = manifest.cell(args.workload)
    if args.rehearsal:  # XLA's CPU loader chokes on entries it reads back
        import shutil

        fresh = os.path.join(args.root, ".jax_cache", "rehearsal")
        shutil.rmtree(fresh, ignore_errors=True)
        os.environ["JAX_COMPILATION_CACHE_DIR"] = fresh
    bench.place_caches()
    driver = manifest.load_module("drivers", cell["config"]["driver"])
    # a family's driver runs drivers/serve.py's module; the llama cells'
    # driver IS that file, loaded anew by the manifest
    for module in (shared, driver):
        if hasattr(module, "deploy"):
            bind(module)
    kwargs = {"rehearsal": True} if args.rehearsal else {}
    out = driver.run(manifest, cell, seed=seed, seconds=args.seconds,
                     trace=False, t0=t0, log=bench.log, **kwargs)
    ctx = dict(out["ctx"], config=cell["config"], traffic=cell["traffic"],
               chips=cell["chips"], seconds=args.seconds)
    metrics = compute_metrics(manifest, cell["metrics"]["end_to_end"], ctx)
    path = os.path.join(args.root, "chiprun_out", "holes", "account.json")
    with open(path) as fh:
        ends = json.load(fh)
    os.remove(path)
    # the window on the wall clock, as drivers/serve.py reckons set-up
    t_start = t0 + ctx["counters"]["setup_s"]
    result = {"workload": args.workload, "seed": seed,
              "correct": bool(out["correct"]), "failed": out["failed"],
              "device": out["device"],
              "metrics": {k: v["value"] for k, v in metrics.items()},
              "window_unix": [t_start, t_start + args.seconds],
              **account(ends["before"], ends["after"], t_start,
                        t_start + args.seconds)}
    with open(os.path.join(args.root, "chiprun_out", "holes",
                           f"{args.workload}.{seed}.json"), "w") as fh:
        json.dump(result, fh)
    return result


def show(r: dict) -> None:
    print(f"== {r['workload']} seed {r['seed']}: correct {r['correct']}, "
          + ", ".join(f"{k} {v}" for k, v in r["metrics"].items()))
    print(f"   holes {r['holes']} ({r['hole_s']} s over the typical step), "
          f"{r['in_window']} of them ({r['in_window_s']} s) in the window")
    for stage, row in sorted(r["by_stage"].items(),
                             key=lambda kv: -kv[1]["hole_s"]):
        print(f"   under {stage}: {row['holes']} holes {row['hole_s']} s; "
              f"in the window {row['in_window']}, {row['in_window_s']} s")
    for h in r["window_holes"]:
        print(f"     step {h['step']} {h['program']} +{h['over_ms']} ms "
              f"over {h['typical_ms']} under {h['stage']} "
              f"{h['stages_ms']}: off_cpu_ms {h['off_cpu_ms']} "
              f"caller_cpu_ms {h['caller_cpu_ms']} gc_ms {h['gc_ms']} "
              f"compiled {h['compiled']} active {h['active']} admitted "
              f"{h['admitted']} callbacks {h['callbacks']}")
    print("   stages, untraced (count, mean ms, longest ms): " + "; ".join(
        f"{name[len('rt.llm.'):]} {s['count']} {s['mean_ms']} {s['max_ms']}"
        for name, s in r["stages"].items()))
    sys.stdout.flush()


def step_cost(root: str, steps: int = 10_000) -> dict:
    """us a step of a tiny engine stepped by hand on the CPU: what the
    host's half of a step costs in ``root``'s program, the account
    included where it has one."""
    os.environ.setdefault("JAX_PLATFORMS", "cpu")
    sys.path.insert(0, root)
    import jax

    from ray_tpu.llm.engine import SlotEngine
    from ray_tpu.models import llama

    cfg = llama.CONFIGS["llama-tiny"]
    params, _ = llama.init_params(jax.random.PRNGKey(0), cfg)
    eng = SlotEngine(params, cfg, num_slots=4, chunk=16, prefix_cache=False)
    eng.warmup()
    # the device's half, taken out: a step's programs answer at once
    # with what they answered to an idle dispatch
    idle = {fused: jax.numpy.asarray(vector)
            for fused, (_, vector) in eng._host_in.items()}
    block = eng._block(eng._params, eng._cache, eng._last_dev, idle[True])
    decode = eng._decode_only(eng._params, block[-1], eng._last_dev,
                              idle[False])
    eng._cache = decode[-1]
    eng._block = lambda p, cache, last, h: block[:3] + (cache,)
    eng._decode_only = lambda p, cache, last, h: decode[:2] + (cache,)

    def timed(target):
        n, t0 = 0, time.perf_counter()
        while n < target:
            if eng.step():
                n += 1
            else:  # every request ran to its length: four more
                for i in range(4):
                    eng.submit([1, 2, 3, 4 + i], max_new=cfg.max_seq - 8)
        return (time.perf_counter() - t0) / n * 1e6

    timed(steps // 10)
    out = {"root": root, "steps": steps}
    if not hasattr(eng, "account"):  # a checkout from before the account
        out["us_a_step"] = round(min(timed(steps // 10)
                                     for _ in range(10)), 3)
        return out
    # with and without ``into=``, turn about in one process, the least of
    # ten rounds each: the host's noise is tens of us, the account's cost a few
    account, rounds = eng.account, {True: [], False: []}
    for _ in range(10):
        for on in (True, False):
            eng.account = account if on else None
            rounds[on].append(timed(steps // 10))
    eng.account = account
    out["us_a_step"] = round(min(rounds[True]), 3)
    out["us_a_step_without_into"] = round(min(rounds[False]), 3)
    # what a step pays beside the spans: the three clocks at its two ends
    # and the hole rule
    from ray_tpu.llm.engine import _Typical

    typical = _Typical()
    t0 = time.perf_counter()
    for _ in range(steps):
        typical.over(eng._since(eng._read_clocks())[0] / 1e9)
    out["us_clocks_and_rule"] = round(
        (time.perf_counter() - t0) / steps * 1e6, 3)
    return out


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload",
                    default="solar-open2-250b.reasoning_closed_1k")
    ap.add_argument("--seed", type=int, nargs="+", default=[])
    ap.add_argument("--seconds", type=float, default=51.0)
    ap.add_argument("--root", default=ROOT,
                    help="the checkout to run (a tiny one, or a copy of "
                    "another commit for --step-cost)")
    ap.add_argument("--rehearsal", action="store_true",
                    help="let the replica run on the CPU (tiny sizes)")
    ap.add_argument("--step-cost", action="store_true")
    args = ap.parse_args()
    args.root = os.path.abspath(args.root)
    if args.step_cost:
        print(json.dumps(step_cost(args.root)), flush=True)
        return 0
    if not args.seed:
        ap.error("--seed is required")
    if len(args.seed) == 1:
        result = run_cell(args, args.seed[0])
        show(result)
        print(json.dumps({k: result[k] for k in (
            "workload", "seed", "correct", "metrics", "holes", "hole_s",
            "in_window", "in_window_s", "by_stage")}), flush=True)
        return 0 if result["correct"] else 1
    # one process a seed: the replica of the last must have let go of the
    # chip before the next asks for it
    rows, rc = [], 0
    for seed in args.seed:
        cmd = [sys.executable, os.path.abspath(__file__), "--workload",
               args.workload, "--seed", str(seed), "--seconds",
               str(args.seconds), "--root", args.root]
        done = subprocess.run(cmd + (["--rehearsal"] if args.rehearsal
                                     else []), stdout=subprocess.PIPE,
                              text=True)
        sys.stdout.write(done.stdout)
        rc = rc or done.returncode
        try:
            rows.append(json.loads(done.stdout.strip().splitlines()[-1]))
        except (IndexError, ValueError):
            print(f"seed {seed}: no result (exit {done.returncode})")
        time.sleep(0 if args.rehearsal else 20)
    print("seed " + " ".join(rows[0]["metrics"]) +
          " holes hole_s in_window in_window_s by_stage" if rows else "")
    for r in rows:
        print(r["seed"], *r["metrics"].values(), r["holes"], r["hole_s"],
              r["in_window"], r["in_window_s"],
              {k: [v["in_window"], v["in_window_s"]]
               for k, v in r["by_stage"].items()})
    sys.stdout.flush()
    return rc


if __name__ == "__main__":
    sys.exit(main())
