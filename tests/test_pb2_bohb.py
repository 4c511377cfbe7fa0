"""PB2 (GP-UCB population-based bandits) + BOHB searcher tests
(reference: tune/tests/test_trial_scheduler_pbt.py PB2 cases,
tune/tests/test_searchers.py BOHB cases)."""

import time
from dataclasses import dataclass, field
from typing import Dict

import numpy as np
import pytest


@dataclass
class FakeTrial:
    trial_id: str
    config: Dict
    rungs_passed: Dict = field(default_factory=dict)


def test_pb2_requires_bounds():
    from ray_tpu.tune import PB2

    with pytest.raises(ValueError, match="hyperparam_bounds"):
        PB2(metric="score", mode="max")
    with pytest.raises(ValueError, match="bad bounds"):
        PB2(metric="score", mode="max",
            hyperparam_bounds={"lr": (1.0, 1.0)})


def test_pb2_gp_selects_near_optimum():
    """Feed the GP synthetic reward-change data peaked at lr=0.5: the
    UCB argmax should land near 0.5 far more often than uniform-random
    would (which averages |lr-0.5| = 0.25)."""
    from ray_tpu.tune import PB2

    pb2 = PB2(metric="score", mode="max",
              hyperparam_bounds={"lr": (0.0, 1.0)},
              perturbation_interval=1, seed=0)
    rng = np.random.default_rng(0)
    # 8 fake trials at random lrs reporting scores whose per-step
    # improvement is highest at lr=0.5.
    trials = [FakeTrial(f"t{i}", {"lr": float(rng.random())})
              for i in range(8)]
    scores = {t.trial_id: 0.0 for t in trials}
    for step in range(1, 6):
        for t in trials:
            rate = 1.0 - abs(t.config["lr"] - 0.5) * 2  # peak at 0.5
            scores[t.trial_id] += rate
            pb2.on_result(t, {"score": scores[t.trial_id],
                              "training_iteration": step})
    picks = [pb2.mutate_config({"lr": 0.9})["lr"] for _ in range(16)]
    assert all(0.0 <= p <= 1.0 for p in picks)
    mean_err = float(np.mean([abs(p - 0.5) for p in picks]))
    assert mean_err < 0.2, f"GP picks not concentrated: {picks}"


def test_pb2_cold_start_random_in_bounds():
    from ray_tpu.tune import PB2

    pb2 = PB2(metric="score", mode="max",
              hyperparam_bounds={"lr": (1e-5, 1e-1)},
              log_scale_keys=("lr",), seed=3)
    out = pb2.mutate_config({"lr": 1e-3})
    assert 1e-5 <= out["lr"] <= 1e-1


def test_pb2_end_to_end_tuner(rt_shared):
    """PB2 drives a population toward the high-improvement region."""
    from ray_tpu.train import Checkpoint
    from ray_tpu.train.session import get_checkpoint
    from ray_tpu.tune import PB2, TuneConfig, Tuner, grid_search, report

    def objective(config):
        ck = get_checkpoint()
        level = ck.to_dict()["level"] if ck else 0.0
        for _ in range(15):
            # Improvement rate peaks at lr = 0.6.
            level += max(0.0, 1.0 - abs(config["lr"] - 0.6) * 3)
            report({"score": level},
                   checkpoint=Checkpoint.from_dict({"level": level}))
            time.sleep(0.01)

    scheduler = PB2(metric="score", mode="max", perturbation_interval=3,
                    hyperparam_bounds={"lr": (0.0, 1.0)}, seed=1)
    results = Tuner(
        objective,
        param_space={"lr": grid_search([0.05, 0.9, 0.55])},
        tune_config=TuneConfig(scheduler=scheduler,
                               max_concurrent_trials=3),
    ).fit()
    best = results.get_best_result("score", mode="max")
    assert best.last_result["score"] > 10


def test_bohb_model_uses_largest_adequate_budget():
    from ray_tpu.tune import BOHBSearcher, uniform

    s = BOHBSearcher({"x": uniform(0, 1)}, metric="loss", mode="min",
                     min_points_in_model=3, seed=0)
    # Low-budget observations fill first.
    for i in range(4):
        tid = f"a{i}"
        s._live[tid] = {"x": 0.1 * i}
        s.on_trial_complete(tid, {"loss": 1.0, "training_iteration": 1})
    assert len(s._history) == 4  # budget 1 qualified
    # Higher budget with enough points takes over.
    for i in range(3):
        tid = f"b{i}"
        s._live[tid] = {"x": 0.5 + 0.1 * i}
        s.on_trial_complete(tid, {"loss": 0.5, "training_iteration": 9})
    assert len(s._history) == 3
    assert all(cfg["x"] >= 0.5 for cfg, _ in s._history)


def test_bohb_end_to_end(rt_shared):
    """create_bohb pair: ASHA prunes, the KDE model concentrates near
    the optimum; the sweep finds x near 0.7."""
    from ray_tpu.tune import TuneConfig, Tuner, create_bohb, report, uniform

    def objective(config):
        for i in range(9):
            # Converges toward the true objective value over budget.
            frac = (i + 1) / 9
            report({"loss": frac * (config["x"] - 0.7) ** 2
                    + (1 - frac) * 0.5})
            # Stream reports (a zero-latency loop finishes before the
            # runner polls, so ASHA could never prune mid-flight).
            time.sleep(0.03)

    scheduler, searcher = create_bohb(
        {"x": uniform(0, 1)}, metric="loss", mode="min", max_t=9,
        grace_period=3, max_trials=12, seed=0)
    results = Tuner(
        objective, param_space=None,
        tune_config=TuneConfig(scheduler=scheduler, search_alg=searcher,
                               max_concurrent_trials=2),
    ).fit()
    best = results.get_best_result("loss", mode="min")
    assert abs(best.config["x"] - 0.7) < 0.2
    # ASHA actually pruned something (not every trial ran to max_t).
    iters = [t.last_result.get("training_iteration", 0)
             for t in results.trials if t.last_result]
    assert min(iters) < 9
