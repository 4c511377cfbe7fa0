"""Experiment-level Tuner.restore: a killed sweep resumes with completed
trials intact.

The driver process running an ASHA sweep is SIGKILLed mid-experiment;
``Tuner.restore(path)`` then resumes it: the trial table, searcher
cursor, and scheduler rungs come back from ``experiment_state.pkl``, so
the total number of trials equals the original budget and no trial that
finished before the kill is retrained (reference: ``tune/tuner.py:159``
``Tuner.restore`` + ``tune/execution/trial_runner.py:682`` experiment
checkpointing).
"""

import os
import signal
import subprocess
import sys
import time

import pytest

BUDGET = 6

_DRIVER = """
import ray_tpu as rt
from ray_tpu import tune
from ray_tpu.tune import TuneConfig, Tuner
from ray_tpu.tune.schedulers import AsyncHyperBandScheduler
from ray_tpu.train.config import RunConfig
import trainable_mod

if __name__ == "__main__":
    rt.init(num_cpus=2)
    tuner = Tuner(
        trainable_mod.trainable,
        param_space={"x": tune.grid_search([1, 2, 3]),
                     "y": tune.grid_search([10, 20])},
        tune_config=TuneConfig(
            max_concurrent_trials=2,
            scheduler=AsyncHyperBandScheduler(
                metric="score", mode="max", max_t=40)),
        run_config=RunConfig(name="exp", storage_path=EXP_ROOT),
    )
    tuner.fit()
"""

_TRAINABLE = """
import os
import time

from ray_tpu import tune


def trainable(config):
    from ray_tpu.train.session import get_session

    trial_id = get_session().ctx.trial_id
    with open(os.path.join(EXP_ROOT, "starts.log"), "a") as f:
        f.write(trial_id + "\\n")
        f.flush()
    for i in range(40):
        tune.report({"score": config["x"] * config["y"] * (i + 1)})
        time.sleep(0.25)
"""


def test_tuner_restore_after_driver_kill(tmp_path):
    exp_root = str(tmp_path)
    exp_path = os.path.join(exp_root, "exp")
    # The trainable must be importable by name from BOTH the subprocess
    # driver and the restored in-process run (cloudpickle stores module
    # functions by reference only when importable; a file module makes
    # the restored state loadable here).
    (tmp_path / "trainable_mod.py").write_text(
        f"EXP_ROOT = {exp_root!r}\n" + _TRAINABLE)
    (tmp_path / "driver.py").write_text(
        f"EXP_ROOT = {exp_root!r}\n" + _DRIVER)

    env = dict(os.environ)
    repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    env["PYTHONPATH"] = f"{tmp_path}:{repo}:" + env.get("PYTHONPATH", "")
    proc = subprocess.Popen([sys.executable, str(tmp_path / "driver.py")],
                            env=env, cwd=str(tmp_path))
    # load_state unpickles the trainable by module reference — make
    # trainable_mod importable in THIS process before polling.
    sys.path.insert(0, str(tmp_path))
    from ray_tpu.tune.tuner import TrialRunner, TrialStatus

    # Wait until at least one trial finished AND the sweep is not done,
    # then kill the driver hard (simulated preemption).
    deadline = time.monotonic() + 240
    pre_state = None
    try:
        while time.monotonic() < deadline:
            if proc.poll() is not None:
                pytest.fail("driver finished before it could be killed "
                            f"(rc={proc.returncode})")
            try:
                state = TrialRunner.load_state(exp_path)
            except Exception:
                time.sleep(0.1)
                continue
            finished = [t for t in state["trials"]
                        if t.status in (TrialStatus.TERMINATED,
                                        TrialStatus.STOPPED)]
            in_flight = [t for t in state["trials"]
                         if t.status in (TrialStatus.RUNNING,
                                         TrialStatus.PENDING)]
            if finished and (in_flight
                             or len(state["trials"]) < BUDGET):
                pre_state = state
                break
            time.sleep(0.1)
        assert pre_state is not None, "no trial finished within deadline"
    finally:
        if proc.poll() is None:
            os.kill(proc.pid, signal.SIGKILL)
            proc.wait(timeout=30)

    finished_before = {t.trial_id for t in pre_state["trials"]
                       if t.status in (TrialStatus.TERMINATED,
                                       TrialStatus.STOPPED)}
    with open(os.path.join(exp_root, "starts.log")) as f:
        starts_before = f.read().splitlines()

    try:
        import ray_tpu as rt
        from ray_tpu.tune import Tuner

        # Explicit CPUs: auto_init sizes to the machine (1 core on the
        # bench box), which cannot host 2 concurrent trial actors. And a
        # runtime of its own: one left up by an earlier file has warm
        # workers spawned before ``tmp_path`` joined ``sys.path``, which
        # cannot import the trainable.
        if rt.is_initialized():
            rt.shutdown()
        rt.init(num_cpus=4)
        assert Tuner.can_restore(exp_path)
        result = Tuner.restore(exp_path).fit()
    finally:
        sys.path.remove(str(tmp_path))
        try:
            rt.shutdown()
        except Exception:
            pass

    # Budget preserved: the grid is 3x2 = 6 trials, no more, no less.
    assert len(result.trials) == BUDGET, (
        f"expected {BUDGET} trials, got {len(result.trials)}")
    configs = sorted((t.config["x"], t.config["y"]) for t in result.trials)
    assert configs == sorted(
        (x, y) for x in (1, 2, 3) for y in (10, 20)), configs
    # Every trial ended (ASHA may stop some early; none left running).
    for t in result.trials:
        assert t.status in (TrialStatus.TERMINATED, TrialStatus.STOPPED,
                            TrialStatus.ERROR), t.status
    # No finished trial was retrained: its start count did not grow.
    with open(os.path.join(exp_root, "starts.log")) as f:
        starts_after = f.read().splitlines()
    for trial_id in finished_before:
        assert (starts_after.count(trial_id)
                == starts_before.count(trial_id)), (
            f"finished trial {trial_id} was retrained after restore")


def test_a_trial_that_cannot_start_ends_as_an_error(rt_init, tmp_path,
                                                    monkeypatch):
    """A trainable the warm workers cannot import (its module's directory
    joined ``sys.path`` after they were spawned: what ``Tuner.restore``
    met on a runtime an earlier test had left up) fails in the actor's
    ``start``; the runner reads that and ends the trial. It polled
    ``drain`` on a session that never began, and ``fit()`` did not return
    (the driver's tier-1 run of PR 52: 1 472 s, cut)."""
    import threading

    rt = rt_init

    @rt.remote
    def warm():
        time.sleep(0.2)

    rt.get([warm.remote() for _ in range(4)], timeout=60)
    (tmp_path / "late_module.py").write_text(
        "def trainable(config):\n    pass\n")
    monkeypatch.syspath_prepend(str(tmp_path))
    import late_module

    from ray_tpu.tune import Tuner, grid_search

    box = {}
    fit = threading.Thread(daemon=True, target=lambda: box.update(
        grid=Tuner(late_module.trainable,
                   param_space={"x": grid_search([1, 2])}).fit()))
    fit.start()
    fit.join(timeout=120)
    assert not fit.is_alive(), "fit() still polls trials that never began"
    assert len(box["grid"].trials) == 2
    assert all("late_module" in t.error for t in box["grid"].trials)
