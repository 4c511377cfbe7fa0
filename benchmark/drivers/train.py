"""Driver of the training cells: ``DataParallelTrainer(...).fit()`` with one
worker process that holds the cell's chips; the loop it runs is
``train_worker.train_loop``. This process only starts the runtime and
reads what the worker reports."""

from __future__ import annotations

import os

from benchmark.drivers import train_worker


def run(manifest, cell: dict, seed: int, seconds: float, trace: bool,
        t0: float, log, rehearsal: bool = False) -> dict:
    import ray_tpu as rt
    from ray_tpu.parallel.mesh import MeshSpec
    from ray_tpu.train.config import ScalingConfig
    from ray_tpu.train.trainer import DataParallelTrainer

    cfg, traffic = cell["config"], cell["traffic"]
    training = cfg["training"]
    chips = cell["chips"]
    loop_config = {
        "config": cfg, "traffic": traffic, "seed": seed, "seconds": seconds,
        "chips": chips, "rehearsal": rehearsal, "t0": t0,
        "root": manifest.root,
        "trace_dir": os.path.join(manifest.root, ".bench_trace",
                                  cell["name"]) if trace else None,
    }
    rt.init(num_cpus=4, resources={"TPU": float(chips)})
    try:
        result = DataParallelTrainer(
            train_worker.train_loop, train_loop_config=loop_config,
            scaling_config=ScalingConfig(
                num_workers=1, use_tpu=True,
                resources_per_worker={"TPU": float(chips)},
                mesh=MeshSpec(**training["mesh"])),
        ).fit()
    finally:
        rt.shutdown()
    if not result.ok:
        raise RuntimeError(f"the trainer failed: {result.error}")
    m = result.metrics
    device = dict(m["device"], memory_peak_bytes=m["memory_peak_bytes"])
    losses, steps = m["losses"], len(m["losses"])
    notes = [
        f"mesh {m['mesh']}; worker started {m['worker_started_s']}s after "
        f"the process; phases {m['phases_s']}; set-up {m['setup_s']:.3f}s",
        f"{steps} steps in {m['window_s']:.3f}s; loss first ten "
        f"{sum(losses[:10]) / max(1, len(losses[:10])):.4f} last ten "
        f"{sum(losses[-10:]) / max(1, len(losses[-10:])):.4f}; reference "
        f"loss {m['ref_loss']:.5f} program {m['prog_loss']:.5f} (bound "
        f"{train_worker.REFERENCE_LOSS_TOLERANCE}); {m['pallas_calls']} "
        f"Pallas calls in the lowered step; state spread "
        f"{m['state_spread']}; per-device peak {m['per_device_peak']}"]
    n = min(10, steps // 2)
    falling = steps >= 2 and (sum(losses[-n:]) / n < sum(losses[:n]) / n)
    ref_ok = abs(m["ref_loss"] - m["prog_loss"]) \
        <= train_worker.REFERENCE_LOSS_TOLERANCE
    kernel_ok = rehearsal or training["attention_impl"] != "flash" \
        or m["pallas_calls"] >= 2
    spread_ok = chips == 1 or (
        m["state_spread"] is not None and len(m["state_spread"]) == chips
        and all(0.8 / chips <= s <= 1.25 / chips
                for s in m["state_spread"].values()))
    bad_steps = sum(1 for x in losses if x != x or x in (float("inf"),
                                                         float("-inf")))
    correct = (m["all_finite"] and falling and ref_ok and kernel_ok
               and spread_ok)
    if not correct:
        notes.append(f"NOT correct: finite {m['all_finite']} falling "
                     f"{falling} reference {ref_ok} kernel {kernel_ok} "
                     f"spread {spread_ok}")
    tokens = steps * training["batch"] * training["seq"]
    ctx = {"series": {"step_ms": [s * 1e3 for s in m["step_s"]],
                      "input_wait_ms": [s * 1e3 for s in m["wait_s"]],
                      "cycle_ms": [(a + b) * 1e3 for a, b in
                                   zip(m["step_s"], m["wait_s"])]},
           "counters": {"setup_s": m["setup_s"], "window_s": m["window_s"],
                        "train_tokens": tokens, "steps": steps,
                        "seq": training["seq"],
                        "tokens_per_step": training["batch"]
                        * training["seq"]},
           "trace": m["trace"]}
    return {"correct": correct, "attempted": steps, "failed": bad_steps,
            "device": device, "ctx": ctx, "notes": notes}
