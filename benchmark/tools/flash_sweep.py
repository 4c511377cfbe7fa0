#!/usr/bin/env python3
"""Time the flash-attention kernels alone on the chip, tile size by tile
size, forward and backward apart, beside another checkout's kernels.

    chiprun -- python benchmark/tools/flash_sweep.py [--parent DIR]

For each shape of ``SHAPES`` (the train cells' ``[8,20,1024,64]`` and
``[6,25,1024,64]``, the ring block ``[1,16,4096,64]``; causal and not):
the kernels of ``--parent`` (a ``git archive`` copy, default
``_bench_archive/parent``; left out if absent) called as that tree calls
them, then this tree's ``_flash_fwd_pallas`` / ``_flash_bwd_pallas`` at
every (block_q, block_k) of ``TILES``, then this tree's own choice.
``schedule`` says which of the kernels' two schedules a row ran.
Two times a call: ``device_ms``, the median duration of the kernel's own
custom-call events in a profiler trace of five calls a variant (what
``kernel.flash_roofline`` reads in a cell), and ``ms``, the host's clock
over 30 calls queued back to back (the least of three rounds; it includes
the XLA operations round the call: ``delta``, the reshapes of ``lse``).
And the largest difference from the parent's result. One JSON line a
measurement, all of them also in ``chiprun_out/flash_sweep/sweep.jsonl``.
``PROBE_TINY=1`` rehearses the script on the CPU (interpreted kernels, one
small shape; its times mean nothing).
"""
import argparse
import glob
import importlib.util
import json
import math
import os
import shutil
import statistics
import sys
import time

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
sys.path.insert(0, ROOT)

# (shape, causal, passes)
SHAPES = [
    ((8, 20, 1024, 64), True, ("fwd", "bwd")),
    ((6, 25, 1024, 64), True, ("fwd", "bwd")),
    ((8, 20, 1024, 64), False, ("fwd", "bwd")),
    ((1, 16, 4096, 64), True, ("fwd",)),
    ((1, 16, 4096, 64), False, ("fwd",)),
]
# block_q never over block_k: the rolled causal walk would cut it down.
# Where the sequence is at most four blocks of block_q the schedule is the
# static one, which takes no notice of block_k.
TILES = [(bq, bk) for bq in (128, 256, 512) for bk in (128, 256, 512)
         if bq <= bk]


def load_parent(path):
    file = os.path.join(path, "ray_tpu", "ops", "attention.py")
    if not os.path.exists(file):
        return None
    spec = importlib.util.spec_from_file_location("parent_attention", file)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--parent", default=os.path.join(
        ROOT, "_bench_archive", "parent"))
    ap.add_argument("--calls", type=int, default=30)
    args = ap.parse_args()
    import jax
    import jax.numpy as jnp

    from benchmark.trace.opsbytes import classify_flash
    from benchmark.trace.reduce import parse_op
    from ray_tpu.ops import attention as A

    dev = jax.devices()[0]
    tiny = os.environ.get("PROBE_TINY") == "1"
    shapes, tile_list = SHAPES, TILES
    if tiny:
        shapes = [((1, 2, 1024, 64), True, ("fwd", "bwd"))]
        tile_list, args.calls = [(256, 256), (128, 512)], 1
    elif dev.platform != "tpu":
        print(json.dumps({"ok": False, "why": f"no chip: {dev.platform}"}))
        return 1
    parent = load_parent(args.parent)
    out_dir = os.path.join(ROOT, "chiprun_out",
                           "flash_sweep_tiny" if tiny else "flash_sweep")
    os.makedirs(out_dir, exist_ok=True)
    log = open(os.path.join(out_dir, "sweep.jsonl"), "w")

    def emit(**row):
        row["device"] = dev.device_kind
        line = json.dumps(row)
        print(line, flush=True)
        log.write(line + "\n")
        log.flush()

    def timed(fn, operands):
        t0 = time.perf_counter()
        out = jax.block_until_ready(fn(*operands))
        compile_s = time.perf_counter() - t0
        best = math.inf
        for _ in range(3):
            t0 = time.perf_counter()
            for _ in range(args.calls):
                res = fn(*operands)
            jax.block_until_ready(res)
            best = min(best, (time.perf_counter() - t0) / args.calls)
        return out, best * 1e3, compile_s

    def is_flash(hlo_text):
        """As the cells' ``kernel.flash_roofline`` finds the kernels."""
        op = parse_op(hlo_text)
        return (op["opcode"] == "custom-call"
                and classify_flash(op) is not None)

    def device_ms(runs, traced_calls=5):
        """Median device milliseconds of each run's custom call: one trace
        over all of them, the calls told apart by their order."""
        tdir = os.path.join(out_dir, "trace")
        shutil.rmtree(tdir, ignore_errors=True)
        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0
        opts.host_tracer_level = 1
        jax.profiler.start_trace(tdir, profiler_options=opts)
        for fn, operands in runs:
            for _ in range(traced_calls):
                jax.block_until_ready(fn(*operands))
        jax.profiler.stop_trace()
        pb = glob.glob(os.path.join(tdir, "**", "*.xplane.pb"),
                       recursive=True)[0]
        calls = []
        for plane in jax.profiler.ProfileData.from_file(pb).planes:
            if plane.name != "/device:TPU:0":
                continue
            for line in plane.lines:
                if line.name == "XLA Ops":
                    calls = [ev.duration_ns / 1e6 for ev in sorted(
                        line.events, key=lambda ev: ev.start_ns)
                        if is_flash(ev.name)]
        shutil.rmtree(tdir, ignore_errors=True)
        if len(calls) != traced_calls * len(runs):
            return [None] * len(runs)
        return [round(statistics.median(
            calls[i * traced_calls:(i + 1) * traced_calls]), 4)
            for i in range(len(runs))]

    def gap(got, want):
        if want is None:
            return None
        return max(float(jnp.max(jnp.abs(
            g.astype(jnp.float32) - w.astype(jnp.float32))))
            for g, w in zip(jax.tree.leaves(got), jax.tree.leaves(want)))

    for shape, causal, passes in shapes:
        scale = 1.0 / math.sqrt(shape[-1])
        q, k, v, do = (jax.random.normal(kk, shape, jnp.bfloat16)
                       for kk in jax.random.split(jax.random.PRNGKey(0), 4))
        o, lse = jax.block_until_ready(A.mha_reference_with_lse(
            q, k, v, causal=causal, scale=scale))
        lim = min(512, shape[2])
        variants = []  # (label, module, bq, bk)
        if parent is not None:
            variants.append(("parent", parent, lim, lim))
        variants += [(f"{bq}x{bk}", A, bq, bk) for bq, bk in tile_list]
        for which in passes:
            base = None
            rows = list(variants)
            # what flash_attention itself does at its default blocks
            rows.append(("chosen", A, lim, lim))
            done = []  # (row to emit, fn, operands)
            for label, mod, bq, bk in rows:
                if label == "chosen":
                    call = A._fwd if which == "fwd" else A._bwd
                    tail = (causal, scale, bq, bk)
                else:
                    call = (mod._flash_fwd_pallas if which == "fwd"
                            else mod._flash_bwd_pallas)
                    tail = (causal, scale, bq, bk, tiny)
                fn = jax.jit(lambda *xs, call=call, tail=tail:
                             call(*xs, *tail))
                operands = (q, k, v) if which == "fwd" else (q, k, v, o,
                                                             lse, do)
                try:
                    res, ms, compile_s = timed(fn, operands)
                except Exception as e:  # noqa: BLE001 — tiling refused
                    emit(shape=shape, causal=causal, which=which,
                         tiles=label, error=repr(e)[:300])
                    continue
                if label == "parent":
                    base = res
                done.append((dict(
                    shape=shape, causal=causal, which=which, tiles=label,
                    block_q=bq, block_k=bk, ms=round(ms, 4),
                    schedule=(None if mod is not A or label == "chosen"
                              else "static" if A._is_static(
                                  causal, shape[2], shape[2], bq)
                              else "rolled"),
                    compile_s=round(compile_s, 2),
                    max_gap_to_parent=gap(res, base),
                    max_gap_to_reference=(gap(res, (o, lse))
                                          if which == "fwd" else None)),
                    fn, operands))
            on_device = device_ms([(fn, ops) for _, fn, ops in done])
            for (row, _, _), dms in zip(done, on_device):
                emit(device_ms=dms, **row)
    log.close()
    return 0


if __name__ == "__main__":
    sys.exit(main())
