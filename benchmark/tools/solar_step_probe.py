#!/usr/bin/env python3
"""The ``solar-open2-250b`` step alone on the chip, scope by scope:

    chiprun -- python3 benchmark/tools/solar_step_probe.py [--head-block 16,32]

The configuration as its file sizes it (8 layers, 128 slots, the whole
state and pool), one ``SlotEngine`` stepped in this process with no Serve
plane round it. Every slot decodes; a few 192-token prompts then go
through the 64-token lane beside them while a profiler trace runs. For
each ``--head-block`` (heads a grid step of ``ops/delta_rule.py``): the
median device time of one step with and without a live chunk, the device
milliseconds a step under every scope name (``trace/program.py``'s
reduction with this family's names), and the longest operations. One JSON
line a setting, also in ``chiprun_out/solar_step_probe/probe.jsonl``.
``PROBE_TINY=1`` rehearses the script on the CPU at a tiny size (no trace
is read there).
"""
import argparse
import gc
import glob
import json
import os
import shutil
import statistics
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
sys.path.insert(0, ROOT)


def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--head-block", default="")
    ap.add_argument("--balance", default="",
                    help="<sequences>x<length> of the expert bias's "
                    "balancing pass, or 'raw' for none; default: the "
                    "cell's (serve_solar_replica.balance_expert_bias)")
    ap.add_argument("--prompts", type=int, default=6)
    ap.add_argument("--seed", type=int, default=3900000001)
    args = ap.parse_args()
    import jax
    import numpy as np

    from benchmark.drivers.serve_lfm2_replica import scopes_known
    from benchmark.drivers.serve_solar_replica import (SCOPES,
                                                       balance_expert_bias,
                                                       solar_config)
    from benchmark.manifest import Manifest
    from benchmark.trace import program as trace_program
    from benchmark.trace import reduce as trace_reduce
    from ray_tpu.llm.engine import SlotEngine
    from ray_tpu.models import serving, solar
    from ray_tpu.ops import delta_rule

    dev = jax.devices()[0]
    tiny = os.environ.get("PROBE_TINY") == "1"
    file = None
    if tiny:
        cfg, slots, page = solar.CONFIGS["solar-tiny"], 4, 8
    elif dev.platform != "tpu":
        print(json.dumps({"ok": False, "why": f"no chip: {dev.platform}"}))
        return 1
    else:
        file = Manifest(ROOT).config("solar-open2-250b")
        cfg, slots = solar_config(file), file["deployment"]["num_slots"]
        page = file["deployment"]["page_size"]
    out_dir = os.path.join(ROOT, "chiprun_out", "solar_step_probe")
    os.makedirs(out_dir, exist_ok=True)
    log = open(os.path.join(out_dir, "probe.jsonl"), "a")
    params, _ = serving.model_for(cfg).init_params(
        jax.random.PRNGKey(args.seed % (2**31 - 1)), cfg)
    if args.balance != "raw" and file is not None:
        from benchmark.reference import solar_open2 as ref

        n, length = map(int, (args.balance or "16x256").split("x"))
        params = balance_expert_bias(ref, params, file,
                                     args.seed % (2**31 - 1), n, length)
    params = jax.block_until_ready(params)
    rng = np.random.default_rng(args.seed)

    def prompt(n):
        return rng.integers(0, cfg.vocab_here, size=n).tolist()

    blocks = [int(x) for x in args.head_block.split(",") if x] or [
        delta_rule._HEAD_BLOCK]
    for hb in blocks:
        delta_rule._HEAD_BLOCK = hb
        eng = SlotEngine(params, cfg, num_slots=slots, page_size=page,
                         chunk=16 if tiny else None)
        eng.warmup()
        new = 40 if tiny else 400
        busy = [eng.submit(prompt(8 if tiny else 64), max_new=new)
                for _ in range(slots - 1)]
        while not all(h._tokens for h in busy):
            eng.step()
        def counts():
            return {k: getattr(eng, k) for k in (
                "steps_block", "steps_decode_only", "kda_rows",
                "experts_hit", "expert_rows", "expert_picks",
                "expert_rows_max")}

        before = counts()       # every slot decodes from here on
        tdir = os.path.join(out_dir, "trace")
        shutil.rmtree(tdir, ignore_errors=True)
        if not tiny:
            opts = jax.profiler.ProfileOptions()
            opts.python_tracer_level = 0
            opts.host_tracer_level = 1
            jax.profiler.start_trace(tdir, profiler_options=opts)
        for _ in range(args.prompts):
            h = eng.submit(prompt(24 if tiny else 192), max_new=2)
            while not h._done.is_set():
                eng.step()
            for _ in range(3):      # and a few steps with an empty lane
                eng.step()
        c = {k: v - before[k] for k, v in counts().items()}
        steps = c["steps_block"] + c["steps_decode_only"]
        row = {"head_block": hb, "balance": args.balance or "default",
               "device": dev.device_kind, "steps": steps,
               "kda_rows_a_step": c["kda_rows"] / steps,
               "experts_hit_share": c["experts_hit"] / (
                   steps * cfg.experts_here * cfg.num_layers),
               "local_pick_share": c["expert_rows"] / c["expert_picks"],
               "load_max_share": c["expert_rows_max"] / c["expert_rows"]}
        if not tiny:
            jax.profiler.stop_trace()
            pb = glob.glob(os.path.join(tdir, "**", "*.xplane.pb"),
                           recursive=True)[0]
            with scopes_known(SCOPES):
                program = trace_program.reduce(trace_program.load(pb))
            reduced = trace_reduce.reduce(trace_reduce.load_xplane(pb))
            steps = [d for name, ds in reduced["modules"].items()
                     if name.startswith("jit_block_fn") for d in ds]
            n = len(steps)
            row.update(
                steps_traced=n,
                step_ms_median=round(statistics.median(steps) * 1e3, 3),
                # a prompt's three chunks, then three steps with an empty
                # lane: the lower quartile is a step without a chunk, the
                # upper one with
                step_ms_p25=round(sorted(steps)[n // 4] * 1e3, 3),
                step_ms_p75=round(sorted(steps)[3 * n // 4] * 1e3, 3),
                step_ms_min=round(min(steps) * 1e3, 3),
                step_ms_max=round(max(steps) * 1e3, 3),
                scope_ms_a_step={k: round(v * 1e3 / n, 3) for k, v in sorted(
                    program["scopes"].items(), key=lambda kv: -kv[1])},
                top_ops_ms_a_step=[[name[:90], round(s * 1e3 / n, 3)]
                                   for name, s in reduced["device_ops"][:24]])
            shutil.rmtree(tdir, ignore_errors=True)
        line = json.dumps(row)
        print(line, flush=True)
        log.write(line + "\n")
        log.flush()
        while eng.step():
            pass
        del eng
        gc.collect()
    return 0


if __name__ == "__main__":
    sys.exit(main())
