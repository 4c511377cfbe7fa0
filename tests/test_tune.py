"""Tune tests: search spaces, Tuner loop, ASHA early stopping, PBT.

Mirrors reference coverage in ``python/ray/tune/tests/``.
"""

import time

import pytest


def test_grid_and_random_expansion():
    from ray_tpu.tune import BasicVariantGenerator, grid_search, uniform

    gen = BasicVariantGenerator(
        {"a": grid_search([1, 2, 3]), "b": uniform(0, 1), "c": "fixed"},
        num_samples=2, seed=0,
    )
    seen = []
    while True:
        cfg = gen.suggest("t")
        if cfg is None:
            break
        seen.append(cfg)
    assert len(seen) == 6
    assert sorted({c["a"] for c in seen}) == [1, 2, 3]
    assert all(0 <= c["b"] <= 1 and c["c"] == "fixed" for c in seen)


def test_tuner_basic(rt_shared):
    from ray_tpu.tune import Tuner, grid_search, report

    def objective(config):
        report({"score": config["x"] ** 2})

    results = Tuner(
        objective, param_space={"x": grid_search([1, 2, 3])}
    ).fit()
    assert len(results.trials) == 3
    best = results.get_best_result("score", mode="min")
    assert best.config["x"] == 1
    assert best.last_result["score"] == 1


def test_tune_run_multiple_reports(rt_shared):
    from ray_tpu.tune import report, run

    def objective(config):
        for i in range(4):
            report({"loss": 10.0 / (i + 1), "step": i})

    results = run(objective, config={"lr": 0.1}, num_samples=2)
    assert len(results.trials) == 2
    for t in results.trials:
        assert t.status == "TERMINATED"
        assert len(t.results) == 4
        assert t.last_result["training_iteration"] == 4


def test_asha_stops_bad_trials(rt_shared, tmp_path):
    from ray_tpu.tune import AsyncHyperBandScheduler, Tuner, TuneConfig, grid_search, report

    gate = str(tmp_path)

    def objective(config):
        # Trial quality is determined by "quality"; bad trials plateau high.
        # Paced by events, never by the clock, because ASHA judges a trial
        # against what EARLIER arrivals recorded at a rung: a trial reports
        # again only once the tuner has taken its last report (a stop
        # decided at a rung lands there, not a drained batch later), and a
        # bad trial comes to the first rung only after both good ones are
        # recorded there.
        import os

        from ray_tpu.train.session import get_session

        def wait_for(cond):
            deadline = time.monotonic() + 120
            while not cond():
                if time.monotonic() > deadline:
                    raise TimeoutError("trial waited 120 s on the tuner")
                time.sleep(0.002)

        good = config["quality"] == 0.0
        for i in range(20):
            if not good and i == 1:
                wait_for(lambda: len(os.listdir(gate)) >= 2)
            loss = config["quality"] + 10.0 / (i + 1)
            report({"loss": loss})
            wait_for(lambda: not get_session().results)
            if good and i == 1:
                open(os.path.join(gate, str(os.getpid())), "w").close()

    scheduler = AsyncHyperBandScheduler(
        metric="loss", mode="min", grace_period=2, reduction_factor=2,
        max_t=20,
    )
    results = Tuner(
        objective,
        param_space={"quality": grid_search([0.0, 0.0, 50.0, 50.0])},
        tune_config=TuneConfig(scheduler=scheduler,
                               max_concurrent_trials=4),
    ).fit()
    # Bad trials must be cut early; good trials must reach max_t (they end
    # as STOPPED too — ASHA stops at max_t — so compare iterations).
    bad = [t for t in results.trials if t.config["quality"] == 50.0]
    good = [t for t in results.trials if t.config["quality"] == 0.0]
    assert any(t.iteration < 20 for t in bad), [t.iteration for t in bad]
    assert any(t.iteration == 20 for t in good), [t.iteration for t in good]


def test_error_trial_reported(rt_shared):
    from ray_tpu.tune import Tuner, grid_search

    def objective(config):
        if config["x"] == 2:
            raise RuntimeError("bad trial")
        from ray_tpu.tune import report

        report({"score": config["x"]})

    results = Tuner(
        objective, param_space={"x": grid_search([1, 2])}
    ).fit()
    statuses = {t.config["x"]: t.status for t in results.trials}
    assert statuses[1] == "TERMINATED"
    assert statuses[2] == "ERROR"
    assert len(results.errors) == 1
    assert "bad trial" in results.errors[0]


def test_pbt_exploits(rt_shared):
    from ray_tpu.train import Checkpoint
    from ray_tpu.tune import (
        PopulationBasedTraining,
        Tuner,
        TuneConfig,
        grid_search,
        report,
    )
    from ray_tpu.train.session import get_checkpoint

    def objective(config):
        ck = get_checkpoint()
        start = ck.to_dict()["level"] if ck else 0.0
        lr = config["lr"]
        level = start
        for i in range(15):
            # Higher lr climbs faster; PBT should propagate high-lr configs.
            level += lr
            report({"score": level},
                   checkpoint=Checkpoint.from_dict({"level": level}))
            time.sleep(0.01)

    scheduler = PopulationBasedTraining(
        metric="score", mode="max", perturbation_interval=3,
        hyperparam_mutations={"lr": [0.1, 1.0, 5.0]}, seed=1,
    )
    results = Tuner(
        objective,
        param_space={"lr": grid_search([0.1, 0.1, 5.0])},
        tune_config=TuneConfig(scheduler=scheduler,
                               max_concurrent_trials=3),
    ).fit()
    best = results.get_best_result("score", mode="max")
    assert best.last_result["score"] > 10  # exploited trials climbed


def test_concurrency_limiter(rt_init):
    """Wrapped searchers never exceed max_concurrent in-flight trials
    (reference: tune/search/concurrency_limiter.py)."""
    import ray_tpu as rt
    from ray_tpu import tune
    from ray_tpu.tune import ConcurrencyLimiter, Tuner, TuneConfig
    from ray_tpu.tune.search import RandomSearch

    @rt.remote
    class Gauge:
        def __init__(self):
            self.cur = 0
            self.peak = 0

        def enter(self):
            self.cur += 1
            self.peak = max(self.peak, self.cur)

        def leave(self):
            self.cur -= 1

        def peak_value(self):
            return self.peak

    gauge = Gauge.remote()

    def trainable(config):
        import time

        import ray_tpu as rt2

        rt2.get(gauge.enter.remote())
        time.sleep(0.3)
        tune.report({"score": config["x"]})
        rt2.get(gauge.leave.remote())

    limiter = ConcurrencyLimiter(
        RandomSearch({"x": tune.uniform(0, 1)}, num_samples=6),
        max_concurrent=2)
    result = Tuner(
        trainable,
        tune_config=TuneConfig(max_concurrent_trials=4,
                               search_alg=limiter),
    ).fit()
    assert len(result.trials) == 6
    import ray_tpu as rt3

    peak = rt3.get(gauge.peak_value.remote())
    assert peak <= 2, f"limiter exceeded cap: {peak}"
