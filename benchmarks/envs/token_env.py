"""The benchmark's own token env: prompts, response lengths and rewards drawn
from a seed, behind the interface of the program's token envs
(`sheeprl_tpu/envs/tokens.py`: observation keys ``prompt`` left-padded,
``prompt_len``, ``token``, ``active``; action = the next token; the reward at
the episode's last token; never ``terminated``, every env is reset by the loop
at the start of a rollout).

It stands in for a long-document task with a verifier (summarise, answer from
a retrieved document): a step does no work beyond the bookkeeping, so
`env_steps_per_s` read with it is an upper bound for such users.

- Prompt: ``samples_per_prompt`` consecutive envs share one (a prompt is
  sampled several times, as RL post-training does): its length is log-uniform
  on [min_prompt_len, max_prompt_len], its ids uniform over the vocabulary the
  model holds, both from (run_seed, group, episode).
- Response length: log-uniform on [response_low, response_high], from the
  env's own stream (random weights never end an episode themselves).
- Reward: uniform in {-1, 0, +1} at the last response token.
"""

from __future__ import annotations

from typing import Any, Dict, Optional

import gymnasium as gym
import numpy as np


def log_uniform(rng: np.random.Generator, low: int, high: int) -> int:
    return int(round(float(np.exp(rng.uniform(np.log(low), np.log(high))))))


class TokenBenchEnv(gym.Env):
    metadata = {"render_modes": []}

    def __init__(
        self,
        id: str = "token_bench",
        vocab_size: int = 16032,
        max_prompt_len: int = 2048,
        min_prompt_len: int = 512,
        response_low: int = 16,
        response_high: int = 64,
        samples_per_prompt: int = 4,
        run_seed: int = 0,
        rank: int = 0,
        seed: int = 0,
        **recipe: Any,  # keys of the recipe's own task (its fixed response length): this env draws its own
    ) -> None:
        top = int(vocab_size) - 1
        self.observation_space = gym.spaces.Dict(
            {
                "prompt": gym.spaces.Box(0, top, (int(max_prompt_len),), np.int32),
                "prompt_len": gym.spaces.Box(0, int(max_prompt_len), (1,), np.int32),
                "token": gym.spaces.Box(0, top, (1,), np.int32),
                "active": gym.spaces.Box(0, 1, (1,), np.int32),
            }
        )
        self.action_space = gym.spaces.Discrete(int(vocab_size))
        self._vocab = int(vocab_size)
        self._prompt_range = (int(min_prompt_len), int(max_prompt_len))
        self._response_range = (int(response_low), int(response_high))
        self._group = int(rank) // max(int(samples_per_prompt), 1)
        self._run_seed = int(run_seed) % (2**32)
        self._rng = np.random.default_rng([self._run_seed, int(rank), 0x51ED270B])
        self._episode = 0
        self._prompt = np.zeros((int(max_prompt_len),), np.int32)
        self._prompt_len = 0
        self._length = 0
        self._t = 0

    def _obs(self, token: int) -> Dict[str, np.ndarray]:
        return {
            "prompt": self._prompt,
            "prompt_len": np.array([self._prompt_len], np.int32),
            "token": np.array([token], np.int32),
            "active": np.array([int(self._t < self._length)], np.int32),
        }

    def reset(self, *, seed: Optional[int] = None, options: Optional[Dict[str, Any]] = None):
        shared = np.random.default_rng([self._run_seed, self._group, self._episode, 0x9E3779B1])
        self._episode += 1
        low, high = self._prompt_range
        self._prompt_len = min(max(log_uniform(shared, low, high), low), high)
        self._prompt = np.zeros_like(self._prompt)
        self._prompt[len(self._prompt) - self._prompt_len:] = shared.integers(0, self._vocab, self._prompt_len)
        self._length = min(max(log_uniform(self._rng, *self._response_range), 1), self._response_range[1])
        self._t = 0
        return self._obs(int(self._prompt[-1])), {}

    def step(self, action):
        reward = 0.0
        if self._t < self._length:
            self._t += 1
            if self._t == self._length:
                reward = float(self._rng.integers(-1, 2))
        return self._obs(int(action)), reward, False, False, {}

    def render(self):
        return None

    def close(self) -> None:
        pass
