#!/usr/bin/env python3
"""Record a small trace of the program's own spans and scopes on the chip.

    chiprun -- python benchmark/tools/program_probe.py

A tiny paged engine stepped by its own thread while two asyncio tasks pull
interleaved ``rt.serve.next_chunks``-style annotations on one thread, then
two steps of a tiny sharded train step. Writes
``chiprun_out/probe/program_1chip.xplane.pb.gz`` (the fixture that
``benchmark/tests/test_trace_program.py`` reduces: the recorded trace cut
to the planes and fields ``trace/program.py`` reads, which takes 2.6 MB to
0.3) and prints the reduction: spans with their attributes, seconds by
scope, what stayed unscoped.
"""
import asyncio
import glob
import gzip
import json
import os
import shutil
import sys
import time

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
sys.path.insert(0, ROOT)


def trim(raw: bytes) -> bytes:
    """The recorded XSpace with only the device, host and metadata planes
    and only the fields the reduction's schema names."""
    from benchmark.trace import program

    cls = program._messages()
    space, out = cls["XSpace"](), cls["XSpace"]()
    space.ParseFromString(raw)
    for plane in space.planes:
        if not (plane.name in ("/host:metadata", program.HOST_PLANE)
                or program.DEVICE_PLANE.match(plane.name)):
            continue
        kept = out.planes.add()
        kept.CopyFrom(plane)
        for entry in kept.event_metadata:
            for stat in entry.value.stats:
                if stat.bytes_value:  # the compiled program
                    hlo = cls["HloProto"]()
                    hlo.ParseFromString(stat.bytes_value)
                    hlo.DiscardUnknownFields()
                    stat.bytes_value = hlo.SerializeToString()
    out.DiscardUnknownFields()
    return out.SerializeToString()


def main() -> int:
    import jax
    import jax.numpy as jnp
    import numpy as np
    import optax

    from benchmark.trace import program
    from ray_tpu.llm.engine import SlotEngine
    from ray_tpu.models import gpt2, llama
    from ray_tpu.observability import tracing
    from ray_tpu.parallel.mesh import MeshSpec
    from ray_tpu.train.step import build_sharded_train

    print("devices", jax.devices(), flush=True)
    out = os.path.join(ROOT, "chiprun_out", "probe")
    os.makedirs(out, exist_ok=True)
    cfg = llama.CONFIGS["llama-tiny"]
    params, _ = llama.init_params(jax.random.PRNGKey(0), cfg)
    eng = SlotEngine(params, cfg, num_slots=4, chunk=16, decode_block=1)
    eng.warmup()
    gcfg = gpt2.GPT2Config(vocab_size=512, max_seq=128, num_layers=2,
                           num_heads=2, d_model=128, remat=True,
                           remat_policy="mem2", attention_impl="auto")
    mesh = MeshSpec().build(jax.devices()[:1])
    sinit, sstep, _ = build_sharded_train(
        lambda k: gpt2.init_params(k, gcfg),
        lambda p, b: gpt2.loss_fn(p, b, gcfg), mesh,
        optimizer=optax.adamw(1e-4), master_fp32=True)
    p, o, st = sinit(jax.random.PRNGKey(1))
    batch = {"tokens": jnp.asarray(np.random.default_rng(0).integers(
        0, 512, (4, 129)), jnp.int32)}
    p, o, st, m = sstep(p, o, st, batch)  # compile outside the trace
    float(m["loss"])

    async def pulls():
        async def one(first, wait):
            with tracing.step_span("rt.serve.next_chunks",
                                   interleaved=True, first=first) as sp:
                await asyncio.sleep(wait)
                sp.set(items=8, done=0)
        await asyncio.gather(one(1, 0.010), one(0, 0.004))

    tdir = os.path.join(out, "trace_program")
    shutil.rmtree(tdir, ignore_errors=True)
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    opts.host_tracer_level = 2
    eng.start()
    jax.profiler.start_trace(tdir, profiler_options=opts)
    handles = [eng.submit(list(range(1, 30 + 7 * i)), max_new=6)
               for i in range(5)]
    asyncio.run(pulls())
    for h in handles:
        h.result(timeout=120)
    for _ in range(2):
        p, o, st, m = sstep(p, o, st, batch)
        float(m["loss"])
    jax.profiler.stop_trace()
    eng.stop()
    found = glob.glob(os.path.join(tdir, "**", "*.xplane.pb"),
                      recursive=True)
    dest = os.path.join(out, "program_1chip.xplane.pb.gz")
    with open(found[0], "rb") as fh:
        raw = fh.read()
    with gzip.open(dest, "wb") as fh:
        fh.write(trim(raw))
    shutil.rmtree(tdir, ignore_errors=True)
    t0 = time.time()
    reduced = program.reduce(program.load_bytes(raw))
    if reduced != program.reduce(program.load(dest)):
        raise RuntimeError("the cut fixture reduces to something else")
    print(f"reduced in {time.time() - t0:.2f}s; {len(raw)} bytes recorded, "
          f"{os.path.getsize(dest)} kept")
    spans = reduced.pop("spans")
    print(json.dumps(reduced, indent=1))
    for sp in spans[:40]:
        print(sp)
    print("counters", {k: getattr(eng, k) for k in
                       eng.STEP_COUNTERS + ("tokens_generated",)})
    return 0


if __name__ == "__main__":
    sys.exit(main())
