"""JaxToGymnasium: run any pure-JAX env through the host compatibility lane.

The reverse adapter: a :class:`~sheeprl_tpu.envs.jax.base.JaxEnv` becomes a
standard ``gymnasium.Env``, so every jax env ALSO runs through the existing
pipeline unchanged — make_env's dict-ification/rescaling, SyncVectorEnv
with SAME_STEP autoreset, `core/interact.py`, RecordEpisodeStatistics, the
whole Gymnasium contract. This is what makes the two lanes comparable
(both step the *same* dynamics) and what lets a fused-lane checkpoint
resume on the host lane with nothing but ``algo.fused_rollout=false``.

Instantiable straight from a wrapper config::

    wrapper:
      _target_: sheeprl_tpu.envs.jax.JaxToGymnasium
      id: ${env.id}
      seed: null   # make_env injects the per-rank seed

Per-instance jitted reset/step keep host overhead to one dispatch per call;
outputs land on host in ONE coalesced transfer per step.
"""

from __future__ import annotations

from typing import Any, Dict, Optional, Tuple

import gymnasium as gym
import numpy as np

import jax

from sheeprl_tpu.envs.jax.adapter import make_jax_env
from sheeprl_tpu.envs.jax.base import JaxEnv

__all__ = ["JaxToGymnasium"]


class JaxToGymnasium(gym.Env):
    metadata = {"render_modes": ["rgb_array"], "render_fps": 30}

    def __init__(
        self,
        id: Optional[str] = None,  # noqa: A002 - gymnasium.make-compatible kwarg
        env: Optional[JaxEnv] = None,
        seed: Optional[int] = None,
        render_mode: str = "rgb_array",
        **kwargs: Any,
    ) -> None:
        if env is None:
            if id is None:
                raise ValueError("JaxToGymnasium needs either an env id or a JaxEnv instance")
            env = make_jax_env(id, **kwargs)
        self.jax_env = env
        self.observation_space = env.observation_space
        self.action_space = env.action_space
        self.render_mode = render_mode
        self.spec = None
        self._reset_fn = jax.jit(env.reset)
        self._step_fn = jax.jit(env.step)
        self._key = jax.random.PRNGKey(0 if seed is None else int(seed))
        self._state = None
        self._last_obs: Optional[np.ndarray] = None

    def _next_key(self) -> jax.Array:
        self._key, sub = jax.random.split(self._key)
        return sub

    def reset(
        self, *, seed: Optional[int] = None, options: Optional[Dict[str, Any]] = None
    ) -> Tuple[np.ndarray, Dict[str, Any]]:
        super().reset(seed=seed)
        if seed is not None:
            self._key = jax.random.PRNGKey(int(seed))
        state, obs = self._reset_fn(self._next_key())
        self._state = state
        np_obs = np.asarray(obs)
        self._last_obs = np_obs
        return np_obs, {}

    def step(self, action: Any) -> Tuple[np.ndarray, float, bool, bool, Dict[str, Any]]:
        if self._state is None:
            raise RuntimeError("step() before reset()")
        state, obs, reward, _done, info = self._step_fn(
            self._state, np.asarray(action), self._next_key()
        )
        self._state = state
        # ONE coalesced device->host transfer for the whole step's outputs.
        np_obs, np_reward, np_term, np_trunc = jax.device_get(
            (obs, reward, info["terminated"], info["truncated"])
        )
        self._last_obs = np_obs
        return np_obs, float(np_reward), bool(np_term), bool(np_trunc), {}

    def render(self) -> Optional[np.ndarray]:
        obs = self._last_obs
        if obs is not None and obs.ndim == 3 and obs.dtype == np.uint8:
            return obs
        # Vector envs have nothing to draw; a blank frame keeps RecordVideo
        # (capture_video=True setups) from crashing.
        return np.zeros((64, 64, 3), np.uint8)

    def close(self) -> None:
        self._state = None
