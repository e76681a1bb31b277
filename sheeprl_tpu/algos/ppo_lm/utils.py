"""PPO over tokens: the auxiliary contract (aggregator keys, per-token GAE, greedy test)."""

from __future__ import annotations

from typing import Any, Dict, Tuple

import jax
import numpy as np

from sheeprl_tpu.utils.env import make_env

AGGREGATOR_KEYS = {"Rewards/rew_avg", "Game/ep_len_avg", "Loss/value_loss", "Loss/policy_loss", "Loss/entropy_loss"}
MODELS_TO_REGISTER = {"agent"}


def token_gae(rewards: np.ndarray, values: np.ndarray, dones: np.ndarray, gamma: float, lmbda: float
              ) -> Tuple[np.ndarray, np.ndarray]:
    """GAE per token over one rollout ``[R, E]`` (float32 on the host). The
    reward sits at an episode's last token (``dones`` = 1 there); the rollout's
    end is a truncation with no bootstrap, so a response cut there is scored
    as it stands. Returns ``(returns, advantages)``."""
    steps = rewards.shape[0]
    advantages = np.zeros_like(rewards)
    last = np.zeros_like(rewards[0])
    not_done = 1.0 - dones
    for t in reversed(range(steps)):
        next_value = values[t + 1] if t + 1 < steps else np.zeros_like(values[0])
        delta = rewards[t] + gamma * next_value * not_done[t] - values[t]
        last = delta + gamma * lmbda * not_done[t] * last
        advantages[t] = last
    return advantages + values, advantages


def test(agent: Any, params: Any, runtime: Any, cfg: Dict[str, Any], log_dir: str, logger: Any = None) -> float:
    """One greedy episode: prefill, then decode until the env ends it."""
    env = make_env(cfg, cfg.seed, 0, log_dir, "test", vector_env_idx=0)()
    obs = env.reset(seed=cfg.seed)[0]
    prefill = jax.jit(lambda p, s, prompt, n, k: agent.prefill(p, s, prompt, n, np.ones((1,), bool), k, greedy=True))
    decode = jax.jit(lambda p, s, t, k: agent.decode(p, s, t, k, greedy=True))
    acting = agent.acting_params(params)
    state, key = agent.init_state(1), jax.random.PRNGKey(cfg.seed)
    cumulative_rew = 0.0
    for t in range(int(cfg.algo.rollout_steps)):
        if t == 0:
            (token, _, _), state, key = prefill(acting, state, obs["prompt"][None], obs["prompt_len"], key)
        else:
            (token, _, _), state, key = decode(acting, state, obs["token"], key)
        obs, reward, _, _, _ = env.step(int(np.asarray(token)[0]))
        cumulative_rew += float(reward)
        if not int(obs["active"][0]) or cfg.dry_run:
            break
    runtime.print("Test - Reward:", cumulative_rew)
    if cfg.metric.log_level > 0 and logger is not None:
        logger.log_dict({"Test/cumulative_reward": cumulative_rew}, 0)
    env.close()
    return cumulative_rew
