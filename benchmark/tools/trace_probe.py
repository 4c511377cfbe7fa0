#!/usr/bin/env python3
"""Record a small device trace on the chip and print how it is laid out.

    chiprun -- python benchmark/tools/trace_probe.py [--chips N]

Writes ``chiprun_out/probe/<n>chip.xplane.pb`` (the recorded trace that
``benchmark/tests/`` reduces) and ``chiprun_out/probe/<n>chip.txt`` (planes,
lines, event names, the stats of one event of each name). The program it
traces is small on purpose: a flash-attention forward and backward, two
matrix products and, on several chips, an all-gather and a psum that
nothing overlaps.
"""
import argparse
import glob
import os
import shutil
import sys
import time

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
sys.path.insert(0, ROOT)


def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--chips", type=int, default=1)
    args = ap.parse_args()
    import jax
    import jax.numpy as jnp
    from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

    from ray_tpu.ops.attention import flash_attention

    devs = jax.devices()[:args.chips]
    print("devices", devs, flush=True)
    print("memory_stats keys", sorted((devs[0].memory_stats() or {}).keys()))
    out = os.path.join(ROOT, "chiprun_out", "probe")
    os.makedirs(out, exist_ok=True)
    key = jax.random.PRNGKey(0)
    q, k, v = (jax.random.normal(kk, (2, 4, 512, 64), jnp.bfloat16)
               for kk in jax.random.split(key, 3))
    w = jax.random.normal(key, (1024, 1024), jnp.bfloat16)

    def loss(q, k, v, w):
        o = flash_attention(q, k, v)
        return jnp.sum((w @ w)[:64, :64].astype(jnp.float32)) + jnp.sum(
            o.astype(jnp.float32) ** 2)

    step = jax.jit(jax.value_and_grad(loss, argnums=(0, 1, 2)))
    fns = [("attn_step", lambda: step(q, k, v, w))]
    if args.chips > 1:
        mesh = Mesh(devs, ("x",))
        big = jax.device_put(
            jax.random.normal(key, (args.chips * 2048, 2048), jnp.bfloat16),
            NamedSharding(mesh, P("x", None)))

        @jax.jit
        def coll(a):
            full = jax.lax.with_sharding_constraint(
                a, NamedSharding(mesh, P(None, None)))
            s = jnp.sum(full.astype(jnp.float32) ** 2)
            prod = jax.lax.with_sharding_constraint(
                a @ full[:2048].T, NamedSharding(mesh, P("x", None)))
            return s, prod

        fns.append(("collective_step", lambda: coll(big)))
    for _, f in fns:
        jax.block_until_ready(f())  # compile outside the trace
    tdir = os.path.join(out, f"trace{args.chips}")
    shutil.rmtree(tdir, ignore_errors=True)
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    opts.host_tracer_level = 2
    t0 = time.monotonic()
    jax.profiler.start_trace(tdir, profiler_options=opts)
    for i in range(3):
        for name, f in fns:
            with jax.profiler.TraceAnnotation(f"bench.{name}"):
                jax.block_until_ready(f())
        time.sleep(0.002)
    jax.profiler.stop_trace()
    print(f"traced in {time.monotonic() - t0:.3f}s", flush=True)
    pb = glob.glob(os.path.join(tdir, "**", "*.xplane.pb"), recursive=True)[0]
    dst = os.path.join(out, f"{args.chips}chip.xplane.pb")
    shutil.copy(pb, dst)
    shutil.rmtree(tdir, ignore_errors=True)
    print("xplane bytes", os.path.getsize(dst))
    data = jax.profiler.ProfileData.from_file(dst)
    with open(os.path.join(out, f"{args.chips}chip.txt"), "w") as fh:
        for plane in data.planes:
            fh.write(f"PLANE {plane.name!r}\n")
            for line in plane.lines:
                evs = list(line.events)
                fh.write(f"  LINE {line.name!r} events={len(evs)}\n")
                seen = {}
                for ev in evs:
                    if ev.name not in seen and len(seen) < 60:
                        seen[ev.name] = ev
                for name, ev in seen.items():
                    stats = {k: (str(v)[:80]) for k, v in ev.stats}
                    fh.write(f"    EV {name!r} start_ns={ev.start_ns} "
                             f"dur_ns={ev.duration_ns} stats={stats}\n")
    print(open(os.path.join(out, f"{args.chips}chip.txt")).read()[-20000:])
    return 0


if __name__ == "__main__":
    sys.exit(main())
