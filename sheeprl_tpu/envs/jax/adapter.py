"""Adapters + registry: external gymnax-style envs slot into the Anakin lane.

gymnax (and the broader pure-JAX env ecosystem it standardized) uses the
calling convention ``reset(key, params) -> (obs, state)`` /
``step(key, state, action, params) -> (obs, state, reward, done, info)``.
:class:`GymnaxAdapter` re-shuffles that into this repo's
:class:`~sheeprl_tpu.envs.jax.base.JaxEnv` protocol without touching the
wrapped env: drop a gymnax env in, get the fused loop and the
``JaxToGymnasium`` compatibility lane for free.

The registry maps env ids to factories. Ids are normalized (lowercase,
optional ``jax_`` prefix and ``-vN`` suffix stripped) so config ids like
``jax_cartpole`` and ``CartPole-v1`` resolve to the same first-party env.
"""

from __future__ import annotations

import re
from typing import Any, Callable, Dict, Optional

import gymnasium as gym
import numpy as np

import jax.numpy as jnp

from sheeprl_tpu.envs.jax.base import EnvState, JaxEnv, StepOut

__all__ = ["GymnaxAdapter", "make_jax_env", "register_jax_env", "registered_jax_envs"]

_VERSION_SUFFIX = re.compile(r"-v\d+$")
_REGISTRY: Dict[str, Callable[..., JaxEnv]] = {}


def _normalize(env_id: str) -> str:
    name = _VERSION_SUFFIX.sub("", str(env_id).strip()).lower()
    if name.startswith("jax_"):
        name = name[len("jax_"):]
    return name


def register_jax_env(env_id: str, factory: Callable[..., JaxEnv]) -> None:
    """Register a factory under a normalized id (last registration wins)."""
    _REGISTRY[_normalize(env_id)] = factory


def registered_jax_envs() -> Dict[str, Callable[..., JaxEnv]]:
    return dict(_REGISTRY)


def make_jax_env(env_id: str, **kwargs: Any) -> JaxEnv:
    """Instantiate a registered pure-JAX env from a config id."""
    name = _normalize(env_id)
    factory = _REGISTRY.get(name)
    if factory is None:
        known = ", ".join(sorted(_REGISTRY)) or "(none)"
        raise ValueError(
            f"No jax env registered under id '{env_id}' (normalized: '{name}'). "
            f"Known ids: {known}. Register external envs with "
            "sheeprl_tpu.envs.jax.register_jax_env(id, factory)."
        )
    return factory(**kwargs)


def _space_to_gymnasium(space: Any) -> gym.Space:
    """Duck-typed conversion of a gymnax-style space to gymnasium."""
    if isinstance(space, gym.Space):
        return space
    n = getattr(space, "n", None)
    if n is not None:
        return gym.spaces.Discrete(int(n))
    low = getattr(space, "low", None)
    high = getattr(space, "high", None)
    if low is not None and high is not None:
        shape = getattr(space, "shape", None) or np.shape(low)
        dtype = np.dtype(getattr(space, "dtype", np.float32))
        low = np.broadcast_to(np.asarray(low, dtype), shape)
        high = np.broadcast_to(np.asarray(high, dtype), shape)
        return gym.spaces.Box(low, high, tuple(shape), dtype)
    raise TypeError(f"Cannot convert space {space!r} to a gymnasium space")


class GymnaxAdapter(JaxEnv):
    """Wrap a gymnax-style env into the :class:`JaxEnv` protocol, unchanged.

    ``env_params`` defaults to the wrapped env's ``default_params``. Spaces
    come from ``observation_space(params)`` / ``action_space(params)`` when
    callable (the gymnax signature), plain attributes otherwise, or the
    explicit overrides. ``done`` maps to ``terminated`` unless the wrapped
    env's info dict reports its own ``truncated`` flag — gymnax collapses
    TimeLimit into ``done``, which the SAME_STEP lane tolerates (a
    truncation misread as termination only affects bootstrap targets).
    """

    def __init__(
        self,
        env: Any,
        env_params: Any = None,
        observation_space: Optional[gym.Space] = None,
        action_space: Optional[gym.Space] = None,
        max_episode_steps: int = 0,
    ) -> None:
        self._env = env
        self._params = env_params if env_params is not None else getattr(env, "default_params", None)
        self.max_episode_steps = int(max_episode_steps)

        def resolve(space_attr: str, override: Optional[gym.Space]) -> gym.Space:
            if override is not None:
                return override
            space = getattr(env, space_attr)
            if callable(space):
                space = space(self._params)
            return _space_to_gymnasium(space)

        self.observation_space = resolve("observation_space", observation_space)
        self.action_space = resolve("action_space", action_space)

    def reset(self, key):
        obs, state = self._env.reset(key, self._params)
        return state, obs

    def step(self, state: EnvState, action, key) -> StepOut:
        obs, new_state, reward, done, info = self._env.step(key, state, action, self._params)
        done = jnp.asarray(done, jnp.bool_).reshape(())
        truncated = jnp.asarray(
            info.get("truncated", jnp.zeros((), jnp.bool_)), jnp.bool_
        ).reshape(())
        terminated = done & ~truncated
        out_info = dict(info)
        out_info["terminated"] = terminated
        out_info["truncated"] = truncated
        return new_state, obs, jnp.asarray(reward, jnp.float32).reshape(()), done, out_info
