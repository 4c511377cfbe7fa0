"""Preemption-tolerant training: a Trainer survives SIGKILL of the node
daemon hosting its worker mid-run and resumes from the last async
checkpoint (SURVEY §7.3's beyond-reference goal — the TPU-spot story).

Flow: train worker pinned (custom resource) to a daemon-backed node; the
daemon process is SIGKILLed after checkpoints land (a real host crash:
the driver notices via connection EOF); the Trainer's failure loop
respawns the gang, which schedules onto a replacement node and resumes
from the checkpoint. Deterministic training makes the final loss
EXACTLY match an uninterrupted run.
"""

import os
import threading
import time


def _make_train_fn():
    """Closure (not module-level) so cloudpickle ships it BY VALUE:
    workers on remote daemon nodes cannot import pytest test modules."""

    def train_fn(config):
        import time as _time

        from ray_tpu.train import session
        from ray_tpu.train.checkpoint import Checkpoint

        ckpt = session.get_checkpoint()
        start, w = 0, 0.0
        if ckpt is not None:
            d = ckpt.to_dict()
            start, w = d["step"] + 1, d["w"]
        for i in range(start, config["steps"]):
            w = w - config["lr"] * 2.0 * (w - 3.0)  # GD on (w-3)^2
            session.report({"loss": (w - 3.0) ** 2, "step": i, "w": w},
                           Checkpoint.from_dict({"step": i, "w": w}))
            _time.sleep(config["step_time"])

    return train_fn


def _expected_final_w(steps: int, lr: float) -> float:
    w = 0.0
    for _ in range(steps):
        w = w - lr * 2.0 * (w - 3.0)
    return w


def test_trainer_survives_daemon_sigkill(rt_cluster, tmp_path):
    from ray_tpu.train.config import (
        CheckpointConfig,
        FailureConfig,
        RunConfig,
        ScalingConfig,
    )
    from ray_tpu.train.trainer import DataParallelTrainer

    cluster = rt_cluster
    node_a = cluster.add_node(num_cpus=2, resources={"train_slot": 1},
                              remote=True)
    cluster.wait_for_nodes()

    steps, lr = 30, 0.1
    trainer = DataParallelTrainer(
        _make_train_fn(),
        train_loop_config={"steps": steps, "lr": lr, "step_time": 0.25},
        scaling_config=ScalingConfig(
            num_workers=1,
            resources_per_worker={"CPU": 1.0, "train_slot": 1.0}),
        run_config=RunConfig(
            name="preempt", storage_path=str(tmp_path),
            failure_config=FailureConfig(max_failures=2),
            checkpoint_config=CheckpointConfig(async_save=True)),
    )

    result_box = {}

    def run_fit():
        result_box["result"] = trainer.fit()

    t = threading.Thread(target=run_fit, daemon=True)
    t.start()

    # Wait until async checkpoints have landed on disk, then SIGKILL the
    # daemon hosting the train worker mid-run.
    ckpt_dir = os.path.join(str(tmp_path), "preempt", "checkpoints")
    deadline = time.monotonic() + 120
    while time.monotonic() < deadline:
        if os.path.isdir(ckpt_dir) and len(
                [d for d in os.listdir(ckpt_dir)
                 if d.startswith("checkpoint_")]) >= 3:
            break
        time.sleep(0.1)
    else:
        raise AssertionError("no checkpoints landed before deadline")
    assert t.is_alive(), "training finished before it could be preempted"

    node = cluster.runtime.scheduler.get_node(node_a)
    assert node is not None and getattr(node, "is_remote", False)
    node.process.kill()  # SIGKILL — a spot preemption

    # Replacement capacity arrives (as a spot pool would backfill).
    time.sleep(1.0)
    cluster.add_node(num_cpus=2, resources={"train_slot": 1}, remote=True)

    t.join(timeout=240)
    assert not t.is_alive(), "trainer did not finish after preemption"
    result = result_box["result"]
    assert result.ok, f"trainer failed: {result.error}"

    # The run resumed (did not restart from scratch): some step indices
    # at the front are NOT re-reported after the resume...
    reported_steps = [m["step"] for m in result.metrics_history]
    assert max(reported_steps) == steps - 1
    # ...and the deterministic trajectory converges to EXACTLY the
    # uninterrupted run's final weight.
    expected_w = _expected_final_w(steps, lr)
    assert abs(result.metrics["w"] - expected_w) < 1e-12, (
        f"final w {result.metrics['w']} != uninterrupted {expected_w}")
    assert abs(result.metrics["loss"] - (expected_w - 3.0) ** 2) < 1e-12
    # The preemption actually interrupted mid-run: the full history has
    # more reports than steps (resumed steps re-reported) OR the kill
    # window shows in duplicated step ids.
    assert len(reported_steps) >= steps, (
        "history shorter than steps — did the kill land mid-run?")
