"""A training cell's step alone on the chip, operation by operation:

    chiprun [--chips 4] -- python3 benchmark/tools/train_step_probe.py \
        --config gpt2-large [--root _bench_archive/parent] [--steps 4]

It builds the step as ``drivers/train_worker.py`` does, from the
configuration's ``training`` section (mesh, batch, optimizer, remat policy,
the flash kernel) and the program of ``--root`` (this checkout, or a ``git
archive`` copy of another commit: what is compared is the program, read by
one reader), runs it on one fixed batch, traces ``--steps`` steps and
prints one JSON line, also appended to ``chiprun_out/train_step_probe/
probe.jsonl``: the step's device ms, ms a step by scope, and every device
operation of the ``attn`` scope and every ``copy`` as ``[what the program
calls it (the end of its op_name), opcode, result, calls a step, us a
call, us a layer]``, twins of the loop bodies apart (forward, recomputed
and backward differ in ``op_name``), summed over devices and divided by
their number. ``relayout_us_a_layer`` sums the ``copy`` operations and the
fusions traced from a split, reshape, transpose or squeeze of the
attention half: q, k, v, o and their gradients moved between a matmul and a
kernel. ``flash_roofline`` gives the flash forward and backward kernels'
shares of their rooflines APART and together, each call counted as the
configuration's heads at its head dim whatever layout the operands are in
(``kernel.flash_roofline`` in a cell reads what ``trace/opsbytes.py
classify_flash`` recognises: both kernels of an unpacked program, of a packed
one the backward alone, 13 rows of two heads for 25 heads as 26; so across
the two layouts only this line compares like with like).
``PROBE_TINY=1`` rehearses on the CPU (two small layers, no trace).
"""

from __future__ import annotations

import argparse
import glob
import json
import os
import re
import shutil
import statistics
import sys
from collections import defaultdict

HERE = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
RELAYOUT = re.compile(r"attn/(split|reshape|transpose|squeeze)$")


def by_operation(space: dict, names: dict, steps: int, layers: int):
    """(rows, relayout us a layer, attn us a layer) of the program that
    took most of the device's time; ``space`` as ``trace/program.py load``
    gives it, ``names`` ``{program: {instruction: op_name}}``."""
    from benchmark.trace.reduce import CONTAINERS, parse_op

    by_pid = defaultdict(float)
    for dev in space["devices"]:
        for _, pid, _, d in dev["ops"]:
            by_pid[pid] += d
    pid = max(by_pid, key=by_pid.get)
    rows = defaultdict(lambda: [0, 0.0])
    moved = attn = 0.0
    parsed: dict = {}
    for dev in space["devices"]:
        for text, p, _, d in dev["ops"]:
            op = parsed.get(text) or parsed.setdefault(text, parse_op(text))
            if p != pid or op["opcode"] in CONTAINERS:
                continue
            said = names.get(pid, {}).get(op["short"], "")
            said = said.rpartition("/while/body/")[2]
            in_attn = "attn/" in said or said.endswith("attn")
            relaid = op["opcode"] == "copy" or bool(RELAYOUT.search(said))
            if not (in_attn or relaid):
                continue
            us = d * 1e-3 / len(space["devices"])
            attn += us if in_attn else 0.0
            moved += us if relaid else 0.0
            result = ";".join(f"{t}[{','.join(map(str, dims))}]"
                              for t, dims in op["outputs"][:2])
            row = rows[said[-90:], op["opcode"], result]
            row[0] += 1 / len(space["devices"])
            row[1] += us
    table = sorted(
        ([said, opcode, result, round(calls / steps, 2),
          round(us / calls, 1), round(us / steps / layers, 1)]
         for (said, opcode, result), (calls, us) in rows.items()),
        key=lambda r: -r[-1])
    return table, moved / steps / layers, attn / steps / layers


def flash_roofline(kernels, heads: int, head_dim: int, peaks: dict) -> dict:
    """{"fwd" | "bwd" | "both": [least us a call, measured us a call, share
    %]} of the traced flash calls (three 4-d operands and two results, six
    and three), causal, ``heads`` real heads a call: ``readers/
    flash_roofline.py``'s arithmetic on one head count for every layout."""
    from benchmark.trace import opsbytes

    sums = {"fwd": [0.0, 0.0, 0], "bwd": [0.0, 0.0, 0]}
    for k in kernels:
        ops, outs = k["operands"], k["outputs"]
        shape = {(3, 2): "fwd", (6, 3): "bwd"}.get((len(ops), len(outs)))
        if shape is None or any(len(dims) != 4 for _, dims in ops[:3]):
            continue
        fn = opsbytes.flash_forward if shape == "fwd" \
            else opsbytes.flash_backward
        (b, _, sq, _), sk = ops[0][1], ops[1][1][2]
        flops, nbytes = fn(b, heads, sq, sk, head_dim, True,
                           opsbytes.DTYPE_BYTES[ops[0][0]])
        least = max(flops / peaks["bf16_flops_per_s"],
                    nbytes / peaks["hbm_bytes_per_s"])
        sums[shape][0] += least * k["calls"]
        sums[shape][1] += k["seconds"]
        sums[shape][2] += k["calls"]
    sums["both"] = [sum(v[i] for v in sums.values()) for i in range(3)]
    return {name: [round(least / calls * 1e6, 1),
                   round(seconds / calls * 1e6, 1),
                   round(100 * least / seconds, 2)]
            for name, (least, seconds, calls) in sums.items() if calls}


def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--config", default="gpt2-large")
    ap.add_argument("--root", default=HERE)
    ap.add_argument("--steps", type=int, default=4)
    ap.add_argument("--seed", type=int, default=50)
    args = ap.parse_args()
    root = os.path.abspath(args.root)
    sys.path[:0] = [root] if root == HERE else [root, HERE]
    tiny = os.environ.get("PROBE_TINY") == "1"
    os.environ.setdefault("TPU_LOG_DIR", "disabled")

    import jax
    import jax.numpy as jnp
    import numpy as np

    import ray_tpu
    assert os.path.realpath(ray_tpu.__file__).startswith(root + os.sep), \
        ray_tpu.__file__
    sys.path.insert(0, HERE)   # the reader is this checkout's, always
    from benchmark.drivers.train_worker import MODELS, _optimizer
    from benchmark.manifest import Manifest
    from benchmark.tools.granite_step_probe import op_names
    from benchmark.trace import program as trace_program
    from benchmark.trace import reduce as trace_reduce
    from ray_tpu.parallel.mesh import MeshSpec
    from ray_tpu.parallel.sharding import prune_rules_for_mesh
    from ray_tpu.train.step import build_sharded_train

    with open(os.path.join(HERE, "benchmark", "configs",
                           args.config + ".json")) as fh:
        cfg = json.load(fh)
    training = cfg["training"]
    if tiny:
        cfg.update(n_layer=2, n_embd=64 * cfg["n_head"] // 5, n_head=max(
            2, cfg["n_head"] // 5), vocab_size=512, n_positions=128)
        training = dict(training, batch=4, seq=128, mesh={})
    mesh_spec = MeshSpec(**training["mesh"])
    dev = jax.devices()[0]
    if not tiny and (dev.platform == "cpu"
                     or len(jax.devices()) < mesh_spec.num_devices):
        print(json.dumps({"error": f"needs {mesh_spec.num_devices} "
                                   f"chip(s), found {dev.platform}"}))
        return 1
    mesh = mesh_spec.build(jax.devices()[:mesh_spec.num_devices])
    init_fn, loss_for = MODELS[training["model"]](cfg, training)
    sinit, sstep, _ = build_sharded_train(
        init_fn, loss_for(prune_rules_for_mesh(mesh)), mesh,
        optimizer=_optimizer(training), master_fp32=training["master_fp32"])
    state = sinit(jax.random.PRNGKey(args.seed))
    rng = np.random.default_rng(args.seed)
    batch = {"tokens": jnp.asarray(rng.integers(
        0, cfg["vocab_size"], (training["batch"], training["seq"] + 1),
        dtype=np.int32))}
    compiled = sstep.lower(*state, batch).compile()
    memory = compiled.memory_analysis()
    losses = []

    def run(n):
        nonlocal state
        for _ in range(n):
            *state, metrics = compiled(*state, batch)
            losses.append(float(metrics["loss"]))

    run(2 if tiny else 3)
    out_dir = os.path.join(HERE, "chiprun_out", "train_step_probe")
    os.makedirs(out_dir, exist_ok=True)
    row = {"config": args.config, "root": os.path.relpath(root, HERE),
           "device": dev.device_kind, "chips": mesh.size,
           "temp_bytes": memory.temp_size_in_bytes,
           "pallas_calls": compiled.as_text().count("tpu_custom_call")}
    if not tiny:
        tdir = os.path.join(out_dir, "trace")
        shutil.rmtree(tdir, ignore_errors=True)
        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0
        opts.host_tracer_level = 1
        jax.profiler.start_trace(tdir, profiler_options=opts)
        run(args.steps)
        jax.profiler.stop_trace()
        pb = glob.glob(os.path.join(tdir, "**", "*.xplane.pb"),
                       recursive=True)[0]
        space = trace_program.load(pb)
        program = trace_program.reduce(space)
        reduced = trace_reduce.reduce(trace_reduce.load_xplane(pb))
        steps = max(reduced["modules"].values(), key=sum)
        n, layers = len(steps) // mesh.size, cfg["n_layer"]
        table, moved, attn = by_operation(space, op_names(pb), n, layers)
        row.update(
            steps_traced=n,
            step_ms_median=round(statistics.median(steps) * 1e3, 3),
            scope_ms_a_step={k: round(v * 1e3 / n, 3) for k, v in sorted(
                program["scopes"].items(), key=lambda kv: -kv[1])},
            scope_phase_ms_a_step={k: round(v * 1e3 / n, 3) for k, v in sorted(
                program["scope_phases"].items(), key=lambda kv: -kv[1])},
            kernel_ms_a_step={k["short"]: round(
                k["seconds"] * 1e3 / n / mesh.size, 3)
                for k in reduced["kernels"]},
            flash_roofline=flash_roofline(
                reduced["kernels"], cfg["n_head"],
                cfg["n_embd"] // cfg["n_head"],
                Manifest(HERE).peaks(dev.device_kind)),
            top_ops_ms_a_step=[[name[:90], round(s * 1e3 / n, 3)]
                               for name, s in reduced["device_ops"][:12]],
            relayout_us_a_layer=round(moved, 1),
            attn_us_a_layer=round(attn, 1),
            ops_us_a_layer=table[:60])
        shutil.rmtree(tdir, ignore_errors=True)
    row["losses"] = [round(x, 4) for x in losses]
    line = json.dumps(row)
    print(line, flush=True)
    with open(os.path.join(out_dir, "probe.jsonl"), "a") as log:
        log.write(line + "\n")
    return 0


if __name__ == "__main__":
    sys.exit(main())
