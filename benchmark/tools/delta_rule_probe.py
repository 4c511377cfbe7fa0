#!/usr/bin/env python3
"""``ops/delta_rule.py``'s kernel alone on the chip, at the geometry of
``solar-open2-250b.reasoning_closed_1k`` (128 decode rows x 64 heads of
128 x 128 float32, six layers' states in one array, a 64-token lane):

    chiprun -- python3 benchmark/tools/delta_rule_probe.py [--head-block 16,32,64]

What binds the decode pass, from three readings a head block, each one
jitted program of six calls (a layer each) timed on the host's clock over
``--iters`` runs that feed the state back, so ms a call is the device's:

* ``as_it_is``: every decode row in the step, the lane empty (and
  ``with_chunk``: the lane's 64 tokens live beside them);
* ``copy_only``: the same DMAs in the same order and nothing computed
  (``_prepare`` and ``_through`` replaced by nothing): what the kernel's
  schedule of reads and writes gives alone;
* ``resident``: 128 tokens of ONE slot as a chunk (``B`` = 0), so every
  grid step is aimed at one block and the state never moves: the vector
  units' time alone.

``stream_share`` is the state's bytes (rows x 64 heads x 128 KiB, in and
out) over 819 GB/s, divided by the time. One JSON line a reading, also in
``chiprun_out/delta_rule_probe/probe.jsonl``. ``PROBE_TINY=1`` rehearses
the script on the CPU (interpreted kernel, tiny sizes, no time means
anything there).
"""
import argparse
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

HBM_GBPS = 819.0


def timed(run, state, operands, iters, trace_dir=None):
    """-> (ms a run on the host's clock over ``iters`` runs that feed the
    state back, the median device ms of a ``delta_rule`` custom call in a
    trace of two more runs or None, the first run's seconds, the last
    run's o)."""
    import jax

    t0 = time.perf_counter()
    o, state = jax.block_until_ready(run(state, *operands))
    first_s = time.perf_counter() - t0
    o, state = jax.block_until_ready(run(state, *operands))
    t0 = time.perf_counter()
    for _ in range(iters):
        o, state = run(state, *operands)
    jax.block_until_ready(state)
    ms = (time.perf_counter() - t0) * 1e3 / iters
    device_ms = None
    if trace_dir is not None:
        shutil.rmtree(trace_dir, ignore_errors=True)
        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0
        opts.host_tracer_level = 1
        jax.profiler.start_trace(trace_dir, profiler_options=opts)
        for _ in range(2):
            o, state = jax.block_until_ready(run(state, *operands))
        jax.profiler.stop_trace()
        pb = glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"),
                       recursive=True)[0]
        calls = [ev.duration_ns / 1e6
                 for plane in jax.profiler.ProfileData.from_file(pb).planes
                 if plane.name == "/device:TPU:0"
                 for line in plane.lines if line.name == "XLA Ops"
                 for ev in line.events if "delta_rule" in ev.name
                 and "custom-call" in ev.name]
        shutil.rmtree(trace_dir, ignore_errors=True)
        if calls:
            device_ms = round(statistics.median(calls), 4)
    return ms, device_ms, first_s, o


def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--head-block", default="")
    ap.add_argument("--iters", type=int, default=20)
    ap.add_argument("--seed", type=int, default=4100000001)
    args = ap.parse_args()
    import jax
    import jax.numpy as jnp

    from ray_tpu.ops import delta_rule as dr

    dev = jax.devices()[0]
    tiny = os.environ.get("PROBE_TINY") == "1"
    if tiny:
        layers, rows, heads, d, lane, iters = 2, 4, 8, 128, 8, 2
    elif dev.platform != "tpu":
        print(json.dumps({"ok": False, "why": f"no chip: {dev.platform}"}))
        return 1
    else:
        layers, rows, heads, d, lane, iters = 6, 128, 64, 128, 64, args.iters
    out_dir = os.path.join(ROOT, "chiprun_out", "delta_rule_probe")
    os.makedirs(out_dir, exist_ok=True)
    log = open(os.path.join(out_dir, "probe.jsonl"), "a")
    ks = jax.random.split(jax.random.PRNGKey(args.seed % (2**31 - 1)), 6)
    unit = lambda x: x / jnp.linalg.norm(x, axis=-1, keepdims=True)  # noqa: E731
    n = rows + max(lane, rows)

    def draw(key, *shape):
        return jax.random.normal(key, shape, jnp.float32)

    q = unit(draw(ks[0], n, heads, d)) * d ** -0.5
    k = unit(draw(ks[1], n, heads, d))
    v = draw(ks[2], n, heads, d)
    g = -jnp.exp(draw(ks[3], n, heads, d) - 2.0)
    beta = 2 * jax.nn.sigmoid(draw(ks[4], n, heads))
    everyone = jnp.ones((rows,), bool)
    empty_lane = dr.step_plan(everyone, (jnp.int32(0), jnp.int32(0)))
    # reading -> (decode rows B, rows handed over, the plan, state rows
    # moved a call, tokens carried through a resident state)
    readings = {
        "as_it_is": (rows, rows + lane, empty_lane, rows, 0),
        "with_chunk": (rows, rows + lane, dr.step_plan(
            everyone.at[0].set(False), (jnp.int32(0), jnp.int32(lane))),
            rows, lane),
        "copy_only": (rows, rows + lane, empty_lane, rows, 0),
        "resident": (0, rows, dr.step_plan(
            jnp.zeros((0,), bool), (jnp.int32(0), jnp.int32(rows))), 0,
            rows),
    }
    arithmetic = dr._prepare, dr._through
    blocks = [int(x) for x in args.head_block.split(",") if x] or [
        dr._HEAD_BLOCK]
    for hb in blocks:
        for name, (b, n_rows, plan, moved, resident) in readings.items():
            dr._prepare, dr._through = (
                (lambda *a: None,) * 2 if name == "copy_only" else arithmetic)

            def six(state, plan, *xs):
                for layer in range(layers):
                    o, state = dr.delta_rule(
                        state, jnp.int32(layer), plan, *xs,
                        interpret=tiny, head_block=hb)
                return o, state

            run = jax.jit(six, donate_argnums=(0,))
            xs = [x[:n_rows] for x in (q, k, v, g, beta)]
            state = 0.1 * draw(ks[5], layers, rows, heads, d, d)
            gap = None
            if name in ("as_it_is", "with_chunk"):
                # one call against the recurrence on the same rows
                want_o, want_s = jax.jit(dr.delta_rule_reference)(
                    state, jnp.int32(1), plan, *xs)
                got_o, got_s = jax.jit(lambda st, *a: dr.delta_rule(
                    st, jnp.int32(1), *a, interpret=tiny, head_block=hb))(
                        state, plan, *xs)
                live = jnp.concatenate([
                    jnp.zeros((b,), bool).at[plan[3:]].set(
                        jnp.arange(b) < plan[0]),
                    jnp.arange(n_rows - b) < plan[2]])
                # [largest gap of a live row's o, of the layer's states,
                # largest change the reference made to a state]
                gap = [float(jnp.abs(jnp.where(live[:, None, None],
                                               got_o - want_o, 0)).max()),
                       float(jnp.abs(got_s[1] - want_s[1]).max()),
                       float(jnp.abs(want_s[1] - state[1]).max()),
                       bool((got_s[0] == state[0]).all())]
                del want_o, want_s, got_o, got_s
            ms, device_ms, compile_s, o = timed(
                run, state, (plan, *xs), iters,
                None if tiny else os.path.join(out_dir, "trace"))
            ms /= layers
            least_ms = moved * heads * 2 * d * d * 4 / (HBM_GBPS * 1e9) * 1e3
            row = {"reading": name, "head_block": hb,
                   "device": dev.device_kind, "rows": rows,
                   "tokens_resident": resident,
                   "ms_a_call": round(ms, 4), "device_ms_a_call": device_ms,
                   "stream_ms": round(least_ms, 4),
                   "stream_share": round(least_ms / ms, 4) if not tiny
                   else None,
                   "first_call_s": round(compile_s, 2),
                   "gap_o_state_moved_rest": gap,
                   # the rows in the step: a parked row's o is unspecified
                   "finite": None if name == "copy_only" else bool(
                       jnp.isfinite(o[name == "with_chunk":b or None]).all())}
            line = json.dumps(row)
            print(line, flush=True)
            log.write(line + "\n")
            log.flush()
            del state, o
    dr._prepare, dr._through = arithmetic
    return 0


if __name__ == "__main__":
    sys.exit(main())
