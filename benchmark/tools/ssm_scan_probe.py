#!/usr/bin/env python3
"""``ops/ssm_scan.py``'s kernel alone on the chip, at the geometry of
``granite-4.0-h-micro.rag_closed_1k`` (64 decode rows x 32 blocks of 128 x
128 float32 = 2 MiB a row, a 256-token lane; six layers' states in one
array stand for the 36):

    chiprun -- python3 benchmark/tools/ssm_scan_probe.py [--blocks 16,32]

What binds the pass, from four readings, each one jitted program of six
calls (a layer each) timed on the host's clock over ``--iters`` runs that
feed the state back, so ms a call is the device's:

* ``as_it_is``: every decode row in the step, the lane empty;
* ``with_chunk``: 63 decode rows and the lane's 256 tokens live beside
  them, which is a fused step of the cell;
* ``copy_only``: the same DMAs in the same order and nothing computed:
  what the schedule of reads and writes gives alone;
* ``resident``: 256 tokens of ONE slot as a chunk (``B`` = 0): the vector
  units' time a token alone.

``stream_share`` is the state's bytes (rows x 2 MiB, in and out) over 819
GB/s, divided by the time. The first two are also held, in one call, to
the recurrence computed by XLA ON THE CHIP (``gap``: the largest
difference of a live row's y, of the layer's states, the largest change
the reference made, and whether the other layer was left alone), which an
interpreted kernel cannot give for a DMA race. One JSON line a reading,
also in ``chiprun_out/ssm_scan_probe/probe.jsonl``. ``PROBE_TINY=1``
rehearses the script on the CPU.
"""
import argparse
import json
import os
import sys
import time

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
sys.path.insert(0, ROOT)

HBM_GBPS = 819.0


def timed(run, state, operands, iters):
    """-> (ms a run over ``iters`` runs that feed the state back, the
    first run's seconds, the last run's y)."""
    import jax

    t0 = time.perf_counter()
    y, state = jax.block_until_ready(run(state, *operands))
    first_s = time.perf_counter() - t0
    y, state = jax.block_until_ready(run(state, *operands))
    t0 = time.perf_counter()
    for _ in range(iters):
        y, state = run(state, *operands)
    jax.block_until_ready(state)
    return (time.perf_counter() - t0) * 1e3 / iters, first_s, y


def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--blocks", default="")
    ap.add_argument("--iters", type=int, default=20)
    ap.add_argument("--seed", type=int, default=4300000001)
    args = ap.parse_args()
    import jax
    import jax.numpy as jnp

    from ray_tpu.ops import ssm_scan as ss

    dev = jax.devices()[0]
    tiny = os.environ.get("PROBE_TINY") == "1"
    if tiny:
        layers, rows, g, lane, iters = 2, 4, 2, 8, 2
    elif dev.platform != "tpu":
        print(json.dumps({"ok": False, "why": f"no chip: {dev.platform}"}))
        return 1
    else:
        layers, rows, g, lane, iters = 6, 64, 32, 256, args.iters
    n = w = 128
    out_dir = os.path.join(ROOT, "chiprun_out", "ssm_scan_probe")
    os.makedirs(out_dir, exist_ok=True)
    log = open(os.path.join(out_dir, "probe.jsonl"), "a")
    ks = jax.random.split(jax.random.PRNGKey(args.seed % (2**31 - 1)), 4)
    total = rows + lane

    def draw(key, *shape):
        return jax.random.normal(key, shape, jnp.float32)

    x = 0.05 * draw(ks[0], total, g, w)
    a = jax.nn.sigmoid(draw(ks[1], total, 2 * g, 1) + 3)
    a = jnp.broadcast_to(a, (total, 2 * g, w // 2)).reshape(total, g, w)
    bc = draw(ks[2], total, 2, n)
    everyone = jnp.ones((rows,), bool)
    empty_lane = ss.step_plan(everyone, (jnp.int32(0), jnp.int32(0)))
    # reading -> (decode rows B, rows handed over, the plan, state rows
    # moved a call, tokens carried through a resident state)
    readings = {
        "as_it_is": (rows, total, empty_lane, rows, 0),
        "with_chunk": (rows, total, ss.step_plan(
            everyone.at[0].set(False), (jnp.int32(0), jnp.int32(lane))),
            rows, lane),
        "copy_only": (rows, total, empty_lane, rows, 0),
        "resident": (0, lane, ss.step_plan(
            jnp.zeros((0,), bool), (jnp.int32(0), jnp.int32(lane))), 1,
            lane),
    }
    arithmetic = ss._prepare, ss._through
    for gb in [int(v) for v in args.blocks.split(",") if v] or [
            min(ss._BLOCKS, g)]:
        for name, (b, n_rows, plan, moved, resident) in readings.items():
            ss._prepare, ss._through = (
                (lambda *a: None,) * 2 if name == "copy_only" else arithmetic)

            def six(state, plan, *xs):
                for layer in range(layers):
                    y, state = ss.ssm_scan(state, jnp.int32(layer), plan, *xs,
                                           interpret=tiny, blocks=gb)
                return y, state

            run = jax.jit(six, donate_argnums=(0,))
            xs = [v[:n_rows] for v in (x, a, bc)]
            state = 0.1 * draw(ks[3], layers, rows, g, n, w)
            gap = None
            if name in ("as_it_is", "with_chunk"):
                want_y, want_s = jax.jit(ss.ssm_scan_reference)(
                    state, jnp.int32(1), plan, *xs)
                got_y, got_s = jax.jit(lambda st, *v: ss.ssm_scan(
                    st, jnp.int32(1), *v, interpret=tiny, blocks=gb))(
                        state, plan, *xs)
                live = jnp.concatenate([
                    ss.plan_valid(plan), jnp.arange(n_rows - b) < plan[2]])
                gap = [float(jnp.abs(jnp.where(live[:, None, None],
                                               got_y - want_y, 0)).max()),
                       float(jnp.abs(got_s[1] - want_s[1]).max()),
                       float(jnp.abs(want_s[1] - state[1]).max()),
                       bool((got_s[0] == state[0]).all())]
                del want_y, want_s, got_y, got_s
            ms, compile_s, y = timed(run, state, (plan, *xs), iters)
            ms /= layers
            least_ms = moved * g * 2 * n * w * 4 / (HBM_GBPS * 1e9) * 1e3
            row = {"reading": name, "blocks": gb, "device": dev.device_kind,
                   "rows": b, "tokens_resident": resident,
                   "ms_a_call": round(ms, 4),
                   "stream_ms": round(least_ms, 4),
                   "stream_share": round(least_ms / ms, 4) if not tiny
                   else None,
                   "first_call_s": round(compile_s, 2), "gap": gap,
                   # the rows in the step: a parked row's y is unspecified
                   "finite": None if name == "copy_only" else bool(
                       jnp.isfinite(jnp.where(jnp.concatenate([
                           ss.plan_valid(plan),
                           jnp.arange(n_rows - b) < plan[2]])[:, None, None],
                           y, 0)).all())}
            line = json.dumps(row)
            print(line, flush=True)
            log.write(line + "\n")
            log.flush()
            del state, y
    ss._prepare, ss._through = arithmetic
    return 0


if __name__ == "__main__":
    sys.exit(main())
