"""RolloutWorker: env-sampling actor.

Reference analog: ``rllib/evaluation/rollout_worker.py:124`` with the
``SyncSampler`` env loop (``sampler.py:145,546``) — collects fixed-length
time-major rollout fragments from a vectorized env using the current policy
weights; weights are synced from the learner each iteration
(``WorkerSet.sync_weights``, worker_set.py:205).
"""

from __future__ import annotations

from typing import Any, Dict, Optional

import numpy as np

from .connectors import ConnectorContext, create_connectors_for_policy
from .env import make_env
from .policy import JaxPolicy
from .sample_batch import (
    ACTIONS,
    DONES,
    LOGPS,
    OBS,
    REWARDS,
    STATE_IN,
    VF_PREDS,
    SampleBatch,
)


class RolloutWorker:
    """Actor body (also usable inline for num_workers=0 local mode)."""

    def __init__(self, env_spec: Any, num_envs: int = 1,
                 policy_config: Optional[Dict] = None, seed: int = 0,
                 worker_index: int = 0):
        import jax

        # Rollout workers always run CPU inference — the learner owns the
        # accelerator, and a chip belongs to one process (reference:
        # rollout workers are CPU actors). Takes effect because a fresh
        # worker process has not initialised a backend yet.
        jax.config.update("jax_platforms", "cpu")
        self.env = make_env(env_spec, num_envs, seed + worker_index * 1000)
        cfg = policy_config or {}
        # Connector pipelines sit between env and policy (reference:
        # connectors/util.py create_connectors_for_policy) — the policy
        # is built against the TRANSFORMED obs shape, and the batch
        # stores transformed observations (what the policy actually saw).
        ctx = ConnectorContext.from_env(self.env, cfg)
        self._policy_cfg = cfg
        self.agent_connectors, self.action_connectors = \
            create_connectors_for_policy(ctx, cfg.get("connectors"))
        raw = self.env.vector_reset(seed=seed + worker_index * 1000)
        self._obs = self.agent_connectors(raw)
        self._connected_obs_shape = tuple(np.asarray(self._obs).shape[1:])
        self.policy = self._make_policy(cfg, seed + worker_index)
        self._episode_rewards = np.zeros(self.env.num_envs, np.float32)
        self._completed: list = []
        self.worker_index = worker_index

    def _make_policy(self, cfg: Dict, seed: int):
        """Subclass hook: build the policy for this worker's env."""
        return JaxPolicy(
            self._connected_obs_shape, self.env.num_actions,
            hidden=cfg.get("hidden", (64, 64)), seed=seed,
            network=cfg.get("network", "auto"),
            model_config=cfg.get("model"),
        )

    def apply(self, fn) -> Any:
        """Run fn(self) in the worker (reference: RolloutWorker.apply)."""
        return fn(self)

    def _step_env(self, actions: np.ndarray):
        """One connected env step: action pipeline -> env.step -> agent
        pipeline on (obs, rewards) -> episode bookkeeping. Returns
        (transformed_next_obs, transformed_rewards, dones, infos)."""
        env_actions = self.action_connectors(actions)
        next_obs, rewards, dones, infos = self.env.vector_step(env_actions)
        self._episode_rewards += rewards
        for i in np.nonzero(dones)[0]:
            self._completed.append(float(self._episode_rewards[i]))
            self._episode_rewards[i] = 0.0
        self.agent_connectors.on_episode_done(dones)
        return (self.agent_connectors(next_obs),
                self.agent_connectors.transform_reward(rewards),
                dones, infos)

    def connector_state(self) -> Dict:
        """Serialized pipelines — Algorithm.get_state embeds this so a
        restored run (or a served policy) reconstructs the exact
        preprocessing, running statistics included (reference:
        connectors/util.py restore_connectors_for_policy).

        Non-serializable connectors (lambdas) are skipped with a warning
        rather than poisoning the whole checkpoint — losing a stateless
        lambda is recoverable; silently losing MeanStd statistics is not.
        """
        import warnings

        state: Dict = {"agent": [], "action": []}
        for key, pipe in (("agent", self.agent_connectors),
                          ("action", self.action_connectors)):
            for c in pipe.connectors:
                try:
                    state[key].append(c.to_state())
                except Exception:
                    warnings.warn(
                        f"connector {type(c).__name__} is not "
                        "serializable; omitted from checkpoint — "
                        "re-add it in the config on restore")
        return state

    def restore_connector_state(self, state: Dict) -> None:
        from .connectors import restore_connectors_for_policy

        ctx = ConnectorContext.from_env(self.env, self._policy_cfg)
        self.agent_connectors, self.action_connectors = \
            restore_connectors_for_policy(ctx, state)

    def set_weights(self, weights: Dict) -> None:
        self.policy.set_weights(weights)

    def get_weights(self) -> Dict:
        return self.policy.get_weights()

    def sample(self, rollout_length: int = 128) -> SampleBatch:
        """Collect a [T, N, ...] fragment; auto-resetting envs."""
        n = self.env.num_envs
        state_in = None
        if getattr(getattr(self.policy, "net", None), "is_recurrent",
                   False):
            # Ship the behavior policy's hidden state at fragment start
            # so the learner's sequence scan starts from the SAME state
            # (reference: state_in in rnn_sequencing.py) — zero-state
            # recompute would skew the importance ratio on fragments
            # starting mid-episode.
            state = self.policy.recurrent_state(n)
            state_in = np.stack([np.asarray(s) for s in state])
        # Preserve the env's obs dtype: forward_conv keys its /255
        # normalization on uint8, so coercing frames to float32 here would
        # make the training batch see a DIFFERENT function than the one
        # that sampled the actions (breaking the PPO importance ratio).
        obs_buf = np.empty((rollout_length, n) +
                           self._connected_obs_shape,
                           np.asarray(self._obs).dtype)
        act_buf = np.empty((rollout_length, n), np.int32)
        logp_buf = np.empty((rollout_length, n), np.float32)
        vf_buf = np.empty((rollout_length, n), np.float32)
        rew_buf = np.empty((rollout_length, n), np.float32)
        done_buf = np.empty((rollout_length, n), bool)
        for t in range(rollout_length):
            actions, logp, values = self.policy.compute_actions(self._obs)
            obs_buf[t] = self._obs
            act_buf[t] = actions
            logp_buf[t] = logp
            vf_buf[t] = values
            next_obs, rewards, dones, _ = self._step_env(actions)
            rew_buf[t] = rewards
            done_buf[t] = dones
            # Recurrent policies reset finished sub-envs' state slots.
            observe = getattr(self.policy, "observe_dones", None)
            if observe is not None:
                observe(dones)
            self._obs = next_obs
        # Bootstrap values for the final observation — side-effect-free
        # for recurrent policies: the next fragment will feed this same
        # observation again, so advancing the hidden state here would
        # make the LSTM see every fragment-boundary obs twice.
        saved_state = (self.policy.recurrent_state(n)
                       if state_in is not None else None)
        _, _, last_values = self.policy.compute_actions(self._obs)
        if saved_state is not None:
            self.policy.set_recurrent_state(n, saved_state)
        batch = SampleBatch({
            OBS: obs_buf, ACTIONS: act_buf, LOGPS: logp_buf,
            VF_PREDS: vf_buf, REWARDS: rew_buf, DONES: done_buf,
        })
        if state_in is not None:
            batch[STATE_IN] = state_in
        batch["last_values"] = np.asarray(last_values, np.float32)
        # Final observation [N, obs]: V-trace bootstraps V(x_T) under the
        # *learner's* policy (IMPALA), so ship the state, not just the
        # behavior-policy value estimate.
        batch["final_obs"] = np.asarray(self._obs)
        return batch

    def episode_stats(self, clear: bool = True) -> Dict:
        eps = list(self._completed)
        if clear:
            self._completed = []
        return {
            "episodes": len(eps),
            "episode_reward_mean": float(np.mean(eps)) if eps else None,
            "episode_reward_max": float(np.max(eps)) if eps else None,
        }
