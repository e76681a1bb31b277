"""sheeprl_tpu.envs.jax: pure-functional environments for the Anakin lane.

Environments here are jit-safe pytree transforms (``reset(key)`` /
``step(state, action, key)`` — see base.py for the protocol), usable three
ways:

- fused: `core/fused_loop.py` vmaps + scans them inside the train jit
  (``env.jax_native=true`` + ``algo.fused_rollout=true``);
- adapted in: external gymnax-style envs via :class:`GymnaxAdapter`;
- adapted out: any jax env through the host Gymnasium pipeline via
  :class:`JaxToGymnasium` (the compatibility lane).

First-party envs — one per algorithm family: :class:`CartPole` (discrete,
ppo), :class:`Pendulum` (continuous, sac), :class:`Gridworld` (pixels,
dreamer_v3).
"""

from sheeprl_tpu.envs.jax.adapter import (
    GymnaxAdapter,
    make_jax_env,
    register_jax_env,
    registered_jax_envs,
)
from sheeprl_tpu.envs.jax.base import JaxEnv, action_to_env, canonical_action_space
from sheeprl_tpu.envs.jax.cartpole import CartPole
from sheeprl_tpu.envs.jax.gridworld import Gridworld
from sheeprl_tpu.envs.jax.pendulum import Pendulum
from sheeprl_tpu.envs.jax.to_gymnasium import JaxToGymnasium

register_jax_env("cartpole", CartPole)
register_jax_env("pendulum", Pendulum)
register_jax_env("gridworld", Gridworld)

__all__ = [
    "CartPole",
    "Gridworld",
    "GymnaxAdapter",
    "JaxEnv",
    "JaxToGymnasium",
    "Pendulum",
    "action_to_env",
    "canonical_action_space",
    "make_jax_env",
    "register_jax_env",
    "registered_jax_envs",
]
