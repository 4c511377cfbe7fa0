#!/usr/bin/env python3
"""Time the serving engine's fused step on the chip, prefill lane by
prefill lane: what a prompt token costs at each width.

    chiprun -- python benchmark/tools/lane_sweep.py [--lanes 64,128,256,512]

``smollm2-1.7b`` as its configuration file sizes it (full depth, 8 slots,
the whole pool), one ``SlotEngine`` a lane, stepped in this process with
no Serve plane round it. A lane's round: ``--busy`` requests decode
(256-token prompts), then ``--prompts`` prompts of ``--prompt`` tokens go
through the lane one after another while they decode beside it; then the
same prompts with nothing else in flight (``busy`` 0: how ``chat_steady``
mostly finds the engine). One JSON line a lane and round, all of them also
in ``chiprun_out/lane_sweep/sweep.jsonl``:

``fused_ms`` / ``decode_ms``: median device time of one execution of
``jit_block_fn`` / ``jit_decode_only_fn`` in a profiler trace of the
round (as ``step.decode_ms.*`` reads a cell's); ``fused_ms_by_chunk``: the
median for each chunk of a prompt in order, so the lane's attention over
a longer prefix shows; ``us_per_prompt_token`` = ``fused_ms`` / lane;
``prefill_ms``: the host's clock from a prompt's first chunk to its first
token (the engine's own ``prefill_s``), median over the prompts;
``warmup_s``: building the engine's programs (both compiled anew for
every lane: the growth over the first lane is the lane kernel's compile).
``PROBE_TINY=1`` rehearses the script on the CPU at a tiny size (its
times mean nothing).
"""
import argparse
import gc
import glob
import json
import os
import shutil
import statistics
import sys
import time

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
sys.path.insert(0, ROOT)

CONFIG = "smollm2-1.7b"


def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--lanes", default="64,128,256,512")
    ap.add_argument("--prompt", type=int, default=1024)
    ap.add_argument("--prompts", type=int, default=4)
    ap.add_argument("--busy", type=int, default=7)
    ap.add_argument("--seed", type=int, default=3500000001)
    args = ap.parse_args()
    import jax
    import numpy as np

    from benchmark.drivers.serve_replica import llama_config
    from benchmark.manifest import Manifest
    from ray_tpu.llm.engine import SlotEngine
    from ray_tpu.models import llama, serving

    dev = jax.devices()[0]
    tiny = os.environ.get("PROBE_TINY") == "1"
    lanes = [int(x) for x in args.lanes.split(",")]
    if tiny:
        import dataclasses

        cfg = dataclasses.replace(llama.CONFIGS["llama-tiny"], max_seq=512)
        slots, lanes, args.prompt, args.busy = 4, [16, 64], 128, 2
        busy_prompt, busy_new = 24, 300
    elif dev.platform != "tpu":
        print(json.dumps({"ok": False, "why": f"no chip: {dev.platform}"}))
        return 1
    else:
        file = Manifest(ROOT).config(CONFIG)
        cfg, slots = llama_config(file), file["deployment"]["num_slots"]
        busy_prompt, busy_new = 256, 400
    out_dir = os.path.join(ROOT, "chiprun_out",
                           "lane_sweep_tiny" if tiny else "lane_sweep")
    os.makedirs(out_dir, exist_ok=True)
    log = open(os.path.join(out_dir, "sweep.jsonl"), "w")

    def emit(**row):
        row["device"] = dev.device_kind
        line = json.dumps(row)
        print(line, flush=True)
        log.write(line + "\n")
        log.flush()

    t0 = time.perf_counter()
    params, _ = serving.model_for(cfg).init_params(
        jax.random.PRNGKey(args.seed % (2**31 - 1)), cfg)
    params = jax.block_until_ready(
        jax.tree.map(lambda x: x.astype(cfg.dtype), params))
    emit(params_s=round(time.perf_counter() - t0, 2))
    rng = np.random.default_rng(args.seed)

    def prompt(n):
        return rng.integers(1, cfg.vocab_size, size=n).tolist()

    def med(xs):
        return round(statistics.median(xs), 4) if xs else None

    def run_until(eng, done, limit=100000):
        for _ in range(limit):
            if done():
                return
            eng.step()
        raise RuntimeError("the engine did not get there")

    def traced(eng, tdir):
        """The round's prompts through the lane, inside one trace; the
        device time of every execution of either program, in order."""
        shutil.rmtree(tdir, ignore_errors=True)
        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0
        opts.host_tracer_level = 1
        jax.profiler.start_trace(tdir, profiler_options=opts)
        handles = []
        for _ in range(args.prompts):
            h = eng.submit(prompt(args.prompt), max_new=2)
            run_until(eng, h._done.is_set)
            handles.append(h)
        jax.profiler.stop_trace()
        pb = glob.glob(os.path.join(tdir, "**", "*.xplane.pb"),
                       recursive=True)[0]
        fused, decode = [], []
        for plane in jax.profiler.ProfileData.from_file(pb).planes:
            if plane.name != "/device:TPU:0":
                continue
            for line in plane.lines:
                if line.name != "XLA Modules":
                    continue
                for ev in sorted(line.events, key=lambda ev: ev.start_ns):
                    if ev.name.startswith("jit_block_fn"):
                        fused.append(ev.duration_ns / 1e6)
                    elif ev.name.startswith("jit_decode_only_fn"):
                        decode.append(ev.duration_ns / 1e6)
        shutil.rmtree(tdir, ignore_errors=True)
        return handles, fused, decode

    for lane in lanes:
        t0 = time.perf_counter()
        eng = SlotEngine(params, cfg, num_slots=slots, chunk=lane,
                         prefix_cache=False)
        eng.warmup()
        warmup_s = time.perf_counter() - t0
        for busy in (args.busy, 0):
            background = [eng.submit(prompt(busy_prompt), max_new=busy_new)
                          for _ in range(busy)]
            run_until(eng, lambda: all(h._tokens for h in background))
            handles, fused, decode = traced(
                eng, os.path.join(out_dir, "trace"))
            chunks = -(-args.prompt // lane)
            whole = len(fused) == chunks * args.prompts
            fused_ms = med(fused)
            emit(lane=lane, busy=busy, prompt=args.prompt,
                 prompts=args.prompts, fused_steps=len(fused),
                 fused_ms=fused_ms, decode_ms=med(decode),
                 us_per_prompt_token=(None if fused_ms is None else
                                      round(fused_ms * 1e3 / lane, 2)),
                 prompt_device_ms=(round(sum(fused) / args.prompts, 3)
                                   if whole else None),
                 fused_ms_by_chunk=([med(fused[i::chunks])
                                     for i in range(chunks)]
                                    if whole else None),
                 prefill_ms=med([h.timing["prefill_s"] * 1e3
                                 for h in handles]),
                 lane_fill=round(eng.prefill_lane_fill, 4),
                 warmup_s=round(warmup_s, 2))
            # let the background finish, so the next round starts empty
            while eng.step():
                pass
        del eng
        gc.collect()
    emit(ok=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
