#!/usr/bin/env python3
"""The ``granite-4.0-h-micro`` step alone on the chip, scope by scope and,
under the scopes asked for, operation by operation:

    chiprun -- python3 benchmark/tools/granite_step_probe.py \
        [--scopes ssm.conv,ssm.out]

The configuration as its file sizes it (40 layers, 64 slots, the lane of
128, the whole state and pool), one ``SlotEngine`` stepped in this process
with no Serve plane round it. 63 slots decode; 1024-token prompts then go
through the lane beside them, one after another, while a profiler trace
runs, so that nearly every traced step carries a full lane, as the cell's
do. One JSON line, also in ``chiprun_out/granite_step_probe/probe.jsonl``:
the median device time of a step, the device milliseconds a step under
every scope name (``trace/program.py``'s reduction with this family's
names), the longest operations, and ``ops_us_a_layer``: every device
operation under ``--scopes`` as ``[scope, what the program calls it (the
end of its ``op_name``), XLA's name(s), result, calls a step, us a call, us
a mamba layer]``. XLA numbers its fusions anew in every compile and the two
loop bodies hold twins, so operations are told apart by ``op_name`` and
result, and twins are summed. ``PROBE_TINY=1`` rehearses the script on the
CPU at a tiny size (no trace is read there).
"""
import argparse
import glob
import json
import os
import shutil
import statistics
import sys
from collections import defaultdict

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
sys.path.insert(0, ROOT)


def op_names(path: str) -> dict:
    """``{program id: {instruction: op_name}}`` from the compiled programs
    a trace holds in its ``/host:metadata`` plane."""
    from benchmark.trace import program as trace_program

    cls = trace_program._messages()
    space = cls["XSpace"]()
    with open(path, "rb") as fh:
        space.ParseFromString(fh.read())
    out = {}
    for plane in space.planes:
        if plane.name != "/host:metadata":
            continue
        for entry in plane.event_metadata:
            pid = trace_program._PROGRAM_ID.search(entry.value.name)
            for stat in entry.value.stats:
                if stat.bytes_value and pid:
                    proto = cls["HloProto"]()
                    proto.ParseFromString(stat.bytes_value)
                    out[int(pid.group(1))] = {
                        ins.name: ins.metadata.op_name
                        for comp in proto.hlo_module.computations
                        for ins in comp.instructions}
    return out


def ops_by_name(space: dict, names: dict, scopes, steps: int,
                layers: int) -> list:
    """The device operations under ``scopes`` of the program that took
    most of the device's time, twins of the loop bodies summed. ``space``
    is ``trace/program.py load``'s form of the trace, ``names``
    :func:`op_names` of it."""
    from benchmark.trace.reduce import CONTAINERS, parse_op

    by_pid = defaultdict(float)
    for _, pid, _, d in space["devices"][0]["ops"]:
        by_pid[pid] += d
    pid = max(by_pid, key=by_pid.get)
    rows = defaultdict(lambda: [set(), 0, 0.0])
    for text, p, _, d in space["devices"][0]["ops"]:
        op = parse_op(text)
        if p != pid or op["opcode"] in CONTAINERS:
            continue
        got = space["programs"].get(pid, {}).get(op["short"])
        if got is None or got[0] not in scopes:
            continue
        said = names.get(pid, {}).get(op["short"], "")
        said = said.partition(got[0] + "/")[2] or op["opcode"]
        result = ";".join(f"{d}[{','.join(map(str, dims))}]"
                          for d, dims in op["outputs"])
        row = rows[got[0], said, result]
        row[0].add(op["short"])
        row[1] += 1
        row[2] += d * 1e-3
    return sorted(
        ([scope, said, "/".join(sorted(short)), result,
          round(calls / steps, 2), round(us / calls, 2),
          round(us / steps / layers, 2)]
         for (scope, said, result), (short, calls, us) in rows.items()),
        key=lambda r: -r[-1])


def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--scopes", default="ssm.conv,ssm.out,ssm.proj")
    ap.add_argument("--seed", type=int, default=4500000001)
    args = ap.parse_args()
    import jax
    import numpy as np

    from benchmark.drivers.serve_granite_replica import SCOPES, granite_config
    from benchmark.drivers.serve_lfm2_replica import scopes_known
    from benchmark.manifest import Manifest
    from benchmark.trace import program as trace_program
    from benchmark.trace import reduce as trace_reduce
    from ray_tpu.llm.engine import SlotEngine
    from ray_tpu.models import granite, serving

    dev = jax.devices()[0]
    tiny = os.environ.get("PROBE_TINY") == "1"
    if tiny:
        cfg, slots, page, lane = granite.CONFIGS["granite-tiny"], 4, 8, 16
    elif dev.platform != "tpu":
        print(json.dumps({"ok": False, "why": f"no chip: {dev.platform}"}))
        return 1
    else:
        file = Manifest(ROOT).config("granite-4.0-h-micro")
        cfg, slots = granite_config(file), file["deployment"]["num_slots"]
        page, lane = (file["deployment"]["page_size"],
                      file["deployment"]["chunk"])
    out_dir = os.path.join(ROOT, "chiprun_out", "granite_step_probe")
    os.makedirs(out_dir, exist_ok=True)
    params, _ = serving.model_for(cfg).init_params(
        jax.random.PRNGKey(args.seed % (2**31 - 1)), cfg)
    params = jax.block_until_ready(params)
    rng = np.random.default_rng(args.seed)

    def prompt(n):
        return rng.integers(0, cfg.vocab_size, size=n).tolist()

    eng = SlotEngine(params, cfg, num_slots=slots, page_size=page, chunk=lane)
    eng.warmup()
    busy = [eng.submit(prompt(8 if tiny else 64), max_new=40 if tiny else 400)
            for _ in range(slots - 1)]
    while not all(h._tokens for h in busy):
        eng.step()
    tdir = os.path.join(out_dir, "trace")
    shutil.rmtree(tdir, ignore_errors=True)
    if not tiny:
        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0
        opts.host_tracer_level = 1
        jax.profiler.start_trace(tdir, profiler_options=opts)
    before = eng.steps_block + eng.steps_decode_only
    for _ in range(5):
        h = eng.submit(prompt(24 if tiny else 8 * lane), max_new=2)
        while not h._done.is_set():
            eng.step()
    row = {"device": dev.device_kind, "slots": slots, "lane": lane,
           "steps": eng.steps_block + eng.steps_decode_only - before}
    if not tiny:
        jax.profiler.stop_trace()
        pb = glob.glob(os.path.join(tdir, "**", "*.xplane.pb"),
                       recursive=True)[0]
        with scopes_known(SCOPES):
            space = trace_program.load(pb)
            program = trace_program.reduce(space)
            reduced = trace_reduce.reduce(trace_reduce.load_xplane(pb))
            steps = [d for name, ds in reduced["modules"].items()
                     if name.startswith("jit_block_fn") for d in ds]
            n = len(steps)
            row.update(
                steps_traced=n,
                step_ms_median=round(statistics.median(steps) * 1e3, 3),
                step_ms_min=round(min(steps) * 1e3, 3),
                step_ms_max=round(max(steps) * 1e3, 3),
                scope_ms_a_step={k: round(v * 1e3 / n, 3) for k, v in sorted(
                    program["scopes"].items(), key=lambda kv: -kv[1])},
                unscoped_ms_a_step=[[name[:60], round(s * 1e3 / n, 3)]
                                    for name, s in program["unscoped_ops"]],
                top_ops_ms_a_step=[[name[:90], round(s * 1e3 / n, 3)]
                                   for name, s in reduced["device_ops"][:24]],
                ops_us_a_layer=ops_by_name(
                    space, op_names(pb), args.scopes.split(","), n,
                    cfg.layer_types.count(granite.MAMBA)))
        shutil.rmtree(tdir, ignore_errors=True)
    line = json.dumps(row)
    print(line, flush=True)
    with open(os.path.join(out_dir, "probe.jsonl"), "a") as log:
        log.write(line + "\n")
    while eng.step():
        pass
    return 0


if __name__ == "__main__":
    sys.exit(main())
