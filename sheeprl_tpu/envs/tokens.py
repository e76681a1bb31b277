"""Token environments: an episode is a prompt and a response of tokens.

The interface `algos/ppo_lm` plays against (any gymnasium env that keeps to
it can stand here):

- ``action_space``: ``Discrete(vocab_size)``, the next response token.
- ``observation_space``: a ``Dict`` of int32 boxes:
  ``prompt`` ``[max_prompt_len]`` the episode's prompt, **left-padded** with
  0, and ``prompt_len`` ``[1]``; ``token`` ``[1]`` the last token of the
  context (the prompt's last at reset, then the action just taken);
  ``active`` ``[1]``, 1 while the episode runs. The prompt is constant
  through an episode: the player sends it to the device once, at reset, and
  afterwards reads ``token`` alone.
- ``reset`` starts a new episode (a new prompt). ``step(token)`` appends the
  token; the step that ends the episode returns its reward (every other step
  0) and ``active = 0``. The env never reports ``terminated``: a generation
  batch runs in lockstep, so an episode that has ended idles (reward 0,
  ``active`` 0) until the loop resets every env for the next rollout, and
  its further steps carry no loss.

:class:`CopyLastTokenEnv` is the small verifiable task of the tests and the
how-to: answer with the prompt's last token.
"""

from __future__ import annotations

from typing import Any, Dict, Optional, Tuple

import gymnasium as gym
import numpy as np


def token_spaces(vocab_size: int, max_prompt_len: int) -> Tuple[gym.spaces.Dict, gym.spaces.Discrete]:
    """The observation and action spaces of the interface."""
    top = int(vocab_size) - 1
    observation = gym.spaces.Dict(
        {
            "prompt": gym.spaces.Box(0, top, (int(max_prompt_len),), np.int32),
            "prompt_len": gym.spaces.Box(0, int(max_prompt_len), (1,), np.int32),
            "token": gym.spaces.Box(0, top, (1,), np.int32),
            "active": gym.spaces.Box(0, 1, (1,), np.int32),
        }
    )
    return observation, gym.spaces.Discrete(int(vocab_size))


class TokenEnv(gym.Env):
    """Bookkeeping of the interface; a task gives ``_new_prompt`` and ``_score``."""

    metadata = {"render_modes": []}

    def __init__(self, vocab_size: int, max_prompt_len: int, seed: int = 0) -> None:
        self.vocab_size = int(vocab_size)
        self.max_prompt_len = int(max_prompt_len)
        self.observation_space, self.action_space = token_spaces(vocab_size, max_prompt_len)
        self._rng = np.random.default_rng(int(seed) % (2**32))
        self._prompt = np.zeros((self.max_prompt_len,), np.int32)
        self._prompt_len = 0
        self._response: list = []
        self._active = False

    # ---- what a task defines
    def _new_prompt(self) -> np.ndarray:
        """The next episode's prompt, at most ``max_prompt_len`` ids."""
        raise NotImplementedError

    def _score(self, prompt: np.ndarray, response: list) -> Optional[float]:
        """The episode's reward once ``response`` ends it, else None."""
        raise NotImplementedError

    # ---- the interface
    def _obs(self, token: int) -> Dict[str, np.ndarray]:
        return {
            "prompt": self._prompt,
            "prompt_len": np.array([self._prompt_len], np.int32),
            "token": np.array([token], np.int32),
            "active": np.array([int(self._active)], np.int32),
        }

    def reset(self, *, seed: Optional[int] = None, options: Optional[Dict[str, Any]] = None):
        if seed is not None:
            self._rng = np.random.default_rng(int(seed) % (2**32))
        prompt = np.asarray(self._new_prompt(), np.int32)
        self._prompt = np.zeros((self.max_prompt_len,), np.int32)
        self._prompt[self.max_prompt_len - len(prompt):] = prompt
        self._prompt_len = len(prompt)
        self._response = []
        self._active = True
        return self._obs(int(prompt[-1])), {}

    def step(self, action):
        token = int(action)
        reward = 0.0
        if self._active:
            self._response.append(token)
            score = self._score(self._prompt[self.max_prompt_len - self._prompt_len:], self._response)
            if score is not None:
                reward, self._active = float(score), False
        return self._obs(token), reward, False, False, {}

    def render(self):
        return None

    def close(self) -> None:
        pass


class CopyLastTokenEnv(TokenEnv):
    """Answer with the prompt's last token: ``response_len`` tokens, each worth
    ``1 / response_len`` if it equals the prompt's last token."""

    def __init__(
        self,
        id: str = "tokens_copy_last",
        vocab_size: int = 16,
        max_prompt_len: int = 8,
        min_prompt_len: int = 2,
        response_len: int = 1,
        seed: int = 0,
    ) -> None:
        super().__init__(vocab_size, max_prompt_len, seed)
        self.min_prompt_len = int(min_prompt_len)
        self.response_len = int(response_len)

    def _new_prompt(self) -> np.ndarray:
        length = int(self._rng.integers(self.min_prompt_len, self.max_prompt_len + 1))
        return self._rng.integers(0, self.vocab_size, length)

    def _score(self, prompt: np.ndarray, response: list) -> Optional[float]:
        if len(response) < self.response_len:
            return None
        return float(np.mean(np.asarray(response) == prompt[-1]))
