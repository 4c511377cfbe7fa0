"""Evolution Strategies + Augmented Random Search.

Reference analogs: ``rllib/algorithms/es/es.py`` (Salimans et al. 2017:
antithetic Gaussian perturbations, centered-rank fitness shaping, shared
noise table so only (index, return) pairs cross the wire) and
``rllib/algorithms/ars/ars.py`` (Mania et al. 2018: top-k directions,
reward-std step scaling).

The actor fan-out IS the algorithm here: N evaluation actors each hold
the env + a reconstruction of the shared noise table; the learner ships
one flat param vector per iteration and receives (noise_index, ret+,
ret-) triples — exactly the reference's communication pattern, on this
runtime's actor/object plane.
"""

from __future__ import annotations

from typing import Dict, List, Optional, Tuple

import numpy as np

from ..core import get, kill, remote
from .algorithm import Algorithm, AlgorithmConfig
from .env import make_env
from .policy import JaxPolicy


class SharedNoiseTable:
    """Deterministic noise pool every process regenerates from one seed
    (reference: es.py create_shared_noise / SharedNoiseTable). Slices
    are perturbation vectors; only indices travel."""

    def __init__(self, size: int = 2_000_000, seed: int = 42):
        self.noise = np.random.default_rng(seed).standard_normal(
            size, dtype=np.float32)

    def get(self, idx: int, dim: int) -> np.ndarray:
        return self.noise[idx:idx + dim]

    def sample_index(self, rng: np.random.Generator, dim: int) -> int:
        return int(rng.integers(0, len(self.noise) - dim + 1))


def centered_ranks(x: np.ndarray) -> np.ndarray:
    """Fitness shaping: returns -> ranks in [-0.5, 0.5]
    (reference: es/utils.py compute_centered_ranks)."""
    ranks = np.empty(len(x), dtype=np.float32)
    ranks[x.argsort()] = np.arange(len(x), dtype=np.float32)
    if len(x) > 1:
        ranks = ranks / (len(x) - 1) - 0.5
    else:
        ranks[:] = 0.0
    return ranks


class ESEvalWorker:
    """Actor body: evaluates perturbed policies by full-episode rollout
    (reference: es.py Worker.do_rollouts)."""

    def __init__(self, env_spec, policy_config: Optional[Dict] = None,
                 seed: int = 0, worker_index: int = 0,
                 noise_size: int = 2_000_000, noise_seed: int = 42):
        import jax

        # CPU rollouts; the chip stays with the learner (rollout_worker.py).
        jax.config.update("jax_platforms", "cpu")
        from jax.flatten_util import ravel_pytree

        cfg = policy_config or {}
        self.env = make_env(env_spec, 1, seed + worker_index * 1000)
        self.policy = JaxPolicy(
            self.env.observation_space_shape, self.env.num_actions,
            hidden=cfg.get("hidden", (32, 32)), seed=seed)
        flat, self._unravel = ravel_pytree(self.policy.params)
        self.dim = int(flat.shape[0])
        self.noise = SharedNoiseTable(noise_size, noise_seed)
        self.rng = np.random.default_rng(seed + worker_index * 7919 + 1)
        self._max_steps = cfg.get("max_episode_steps", 500)

    def param_dim(self) -> int:
        return self.dim

    def _episode_return(self, flat: np.ndarray) -> Tuple[float, int]:
        self.policy.params = self._unravel(flat)
        obs = self.env.vector_reset(
            seed=int(self.rng.integers(0, 2 ** 31)))
        total, steps = 0.0, 0
        while steps < self._max_steps:
            a, _, _ = self.policy.compute_actions(obs, deterministic=True)
            obs, r, done, _ = self.env.vector_step(a)
            total += float(r[0])
            steps += 1
            if bool(done[0]):
                break
        return total, steps

    def do_rollouts(self, flat_params: np.ndarray, num_pairs: int,
                    sigma: float) -> Dict:
        """Antithetic pairs: evaluate theta +/- sigma*noise[idx]."""
        flat_params = np.asarray(flat_params, np.float32)
        indices, pos, neg, steps = [], [], [], 0
        for _ in range(num_pairs):
            idx = self.noise.sample_index(self.rng, self.dim)
            eps = self.noise.get(idx, self.dim)
            r_pos, s1 = self._episode_return(flat_params + sigma * eps)
            r_neg, s2 = self._episode_return(flat_params - sigma * eps)
            indices.append(idx)
            pos.append(r_pos)
            neg.append(r_neg)
            steps += s1 + s2
        return {"indices": indices, "pos": pos, "neg": neg,
                "steps": steps}

    def eval_policy(self, flat_params: np.ndarray,
                    episodes: int = 3) -> float:
        rets = [self._episode_return(np.asarray(flat_params,
                                                np.float32))[0]
                for _ in range(episodes)]
        return float(np.mean(rets))


class ESConfig(AlgorithmConfig):
    def __init__(self):
        super().__init__()
        self._algo_class = ES
        self.num_rollout_workers = 2
        self.episodes_per_batch = 16  # antithetic pairs per iteration
        self.sigma = 0.05
        self.step_size = 0.02
        self.noise_size = 2_000_000
        self.policy_hidden = (32, 32)
        self.l2_coeff = 0.005

    def training(self, episodes_per_batch=None, sigma=None,
                 step_size=None, noise_size=None, l2_coeff=None,
                 **kwargs) -> "ESConfig":
        super().training(**kwargs)
        for name, val in [("episodes_per_batch", episodes_per_batch),
                          ("sigma", sigma), ("step_size", step_size),
                          ("noise_size", noise_size),
                          ("l2_coeff", l2_coeff)]:
            if val is not None:
                setattr(self, name, val)
        return self


class ES(Algorithm):
    """Learner: fan out rollout requests, combine centered-rank-weighted
    noise into one gradient, Adam step (reference: es.py _train)."""

    _is_ars = False

    def setup(self, config: ESConfig) -> None:
        # No WorkerSet: ES uses its own evaluation actors (the policy
        # weights here are a flat vector, not a JaxPolicy sync).
        policy_cfg = {"hidden": config.policy_hidden,
                      **config.policy_config_extra}
        self._local = ESEvalWorker(config.env, policy_cfg,
                                   seed=config.seed,
                                   noise_size=config.noise_size)
        self.dim = self._local.dim
        remote_cls = remote(ESEvalWorker)
        n = max(0, config.num_rollout_workers)
        self.eval_workers = [
            remote_cls.options(num_cpus=1).remote(
                config.env, policy_cfg, seed=config.seed,
                worker_index=i + 1, noise_size=config.noise_size)
            for i in range(n)
        ]
        from jax.flatten_util import ravel_pytree

        flat, _ = ravel_pytree(self._local.policy.params)
        self.flat_params = np.asarray(flat, np.float32)
        self.noise = self._local.noise
        # Adam moments (reference: es/optimizers.py Adam)
        self._m = np.zeros(self.dim, np.float32)
        self._v = np.zeros(self.dim, np.float32)
        self._t = 0

    def _adam_step(self, grad: np.ndarray, lr: float) -> None:
        b1, b2, eps = 0.9, 0.999, 1e-8
        self._t += 1
        self._m = b1 * self._m + (1 - b1) * grad
        self._v = b2 * self._v + (1 - b2) * grad * grad
        mhat = self._m / (1 - b1 ** self._t)
        vhat = self._v / (1 - b2 ** self._t)
        self.flat_params = self.flat_params - lr * mhat / (
            np.sqrt(vhat) + eps)

    def _collect(self, num_pairs: int) -> Dict:
        cfg = self.config
        if self.eval_workers:
            from ..core import put

            per = max(1, num_pairs // len(self.eval_workers))
            # One object-store copy, N readers (same pattern as
            # WorkerSet.sync_weights).
            ref = put(self.flat_params)
            results = get([
                w.do_rollouts.remote(ref, per, cfg.sigma)
                for w in self.eval_workers
            ])
        else:
            results = [self._local.do_rollouts(self.flat_params,
                                               num_pairs, cfg.sigma)]
        out = {"indices": [], "pos": [], "neg": [], "steps": 0}
        for r in results:
            out["indices"].extend(r["indices"])
            out["pos"].extend(r["pos"])
            out["neg"].extend(r["neg"])
            out["steps"] += r["steps"]
        return out

    def training_step(self) -> Dict:
        cfg: ESConfig = self.config
        res = self._collect(cfg.episodes_per_batch)
        pos = np.asarray(res["pos"], np.float32)
        neg = np.asarray(res["neg"], np.float32)
        n = len(pos)
        # Centered-rank shaping over ALL 2n returns, then the antithetic
        # difference per pair (reference: es.py batched_weighted_sum).
        shaped = centered_ranks(np.concatenate([pos, neg]))
        w = shaped[:n] - shaped[n:]
        grad = np.zeros(self.dim, np.float32)
        for wi, idx in zip(w, res["indices"]):
            grad += wi * self.noise.get(idx, self.dim)
        grad /= (n * cfg.sigma)
        grad -= cfg.l2_coeff * self.flat_params  # weight decay
        self._adam_step(-grad, cfg.step_size)  # ascend
        self._timesteps_total += res["steps"]
        return {
            "timesteps_this_iter": res["steps"],
            "episodes_this_iter": 2 * n,
            "episode_reward_mean": float(np.mean(
                np.concatenate([pos, neg]))),
            "grad_norm": float(np.linalg.norm(grad)),
        }

    def train(self) -> Dict:
        import time

        t0 = time.perf_counter()
        result = self.training_step()
        self.iteration += 1
        result.update({
            "training_iteration": self.iteration,
            "timesteps_total": self._timesteps_total,
            "time_this_iter_s": time.perf_counter() - t0,
        })
        return result

    def evaluate(self, episodes: int = 3) -> float:
        return self._local.eval_policy(self.flat_params, episodes)

    def get_state(self) -> Dict:
        return {"iteration": self.iteration,
                "timesteps_total": self._timesteps_total,
                "flat_params": self.flat_params,
                "m": self._m, "v": self._v, "t": self._t}

    def set_state(self, state: Dict) -> None:
        self.iteration = state.get("iteration", 0)
        self._timesteps_total = state.get("timesteps_total", 0)
        if "flat_params" in state:
            self.flat_params = np.asarray(state["flat_params"],
                                          np.float32)
        self._m = state.get("m", self._m)
        self._v = state.get("v", self._v)
        self._t = state.get("t", self._t)

    def stop(self) -> None:
        for w in self.eval_workers:
            try:
                kill(w)
            except Exception:
                pass


class ARSConfig(ESConfig):
    def __init__(self):
        super().__init__()
        self._algo_class = ARS
        self.top_k: Optional[int] = None  # default: use all directions
        self.sigma = 0.05
        self.step_size = 0.05

    def training(self, top_k=None, **kwargs) -> "ARSConfig":
        if top_k is not None:
            self.top_k = top_k
        super().training(**kwargs)
        return self


class ARS(ES):
    """ARS V1-t: keep only the top_k directions by max(r+, r-), weight
    by the raw return difference, scale the step by the std of the used
    returns (reference: ars.py; Mania et al. 2018 Alg. 2)."""

    _is_ars = True

    def training_step(self) -> Dict:
        cfg: ARSConfig = self.config
        res = self._collect(cfg.episodes_per_batch)
        pos = np.asarray(res["pos"], np.float32)
        neg = np.asarray(res["neg"], np.float32)
        n = len(pos)
        k = min(cfg.top_k or n, n)
        order = np.argsort(-np.maximum(pos, neg))[:k]
        used = np.concatenate([pos[order], neg[order]])
        sigma_r = float(used.std()) + 1e-8
        grad = np.zeros(self.dim, np.float32)
        for i in order:
            grad += (pos[i] - neg[i]) * self.noise.get(
                res["indices"][i], self.dim)
        grad /= (k * sigma_r)
        self._adam_step(-grad, cfg.step_size)
        self._timesteps_total += res["steps"]
        return {
            "timesteps_this_iter": res["steps"],
            "episodes_this_iter": 2 * n,
            "episode_reward_mean": float(np.mean(
                np.concatenate([pos, neg]))),
            "grad_norm": float(np.linalg.norm(grad)),
        }
