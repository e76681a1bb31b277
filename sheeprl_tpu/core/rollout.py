"""Ship an on-policy rollout to the mesh for a fused bootstrap+GAE+update.

Shared by PPO and A2C (the two coupled on-policy loops): their whole
iteration is ONE jitted call — final-obs value bootstrap, GAE, epoch/
minibatch scans — so nothing round-trips the host between rollout and
update (reference shape: separate ``estimate_returns_and_advantages`` +
train loop, sheeprl/algos/ppo/ppo.py:345-420; here the fusion matters
because every extra dispatch pays the host-device dispatch latency).

Layout: every rollout tensor travels in ``(T, E, ...)`` — T the rollout
length, E the env columns — because the in-jit GAE scans T sequentially
while E is embarrassingly parallel. The env axis shards over `data`
whenever it divides the axis size; the minibatch phase reshards in-jit via
its ``with_sharding_constraint``. Multi-process coherence is the reason E
(not the flattened T*E) is the sharded axis: each process contributes ITS
env columns to the global array, so the GAE inputs and the sample rows a
column produces always come from the same process — a flattened row-block
assembly would interleave hosts differently for (T*E)-shaped and
(T, E)-shaped tensors and silently mix rollouts.

``share_data`` gathers along the env axis across hosts first (GAE is
independent per env column, so gather-then-GAE equals GAE-then-gather) —
the reference's every-process-trains-on-the-union mode (fabric.all_gather).
"""

from __future__ import annotations

import warnings
from typing import Any, Dict, Sequence, Tuple

import numpy as np

_SEQ_KEYS = ("rewards", "values", "dones")


def ship_rollout(
    runtime,
    local_data: Dict[str, Any],
    flat_keys: Sequence[str],
    next_obs_np: Dict[str, Any],
    share_data: bool = False,
) -> Tuple[Dict[str, Any], Dict[str, Any]]:
    """Device trees ``(data, next_obs)`` for the fused train jit.

    ``data`` holds ``flat_keys`` + rewards/values/dones, all ``(T, E, ...)``
    (pixels stay uint8); ``next_obs`` is the final obs, one row per env.
    """
    import jax

    from sheeprl_tpu.telemetry import trace_context
    from sheeprl_tpu.telemetry.tracer import current as _current_tracer

    # The ship site is a cross-process seam (decoupled player -> trainer):
    # stamp the wire-format traceparent into the span args so the receiving
    # side of a future infeed transport can adopt the same trace.
    ctx = trace_context.current()
    args = {"traceparent": ctx.to_traceparent()} if ctx is not None else {}
    with _current_tracer().span("rollout/ship", "transfer", **args):
        return _ship_rollout(runtime, local_data, flat_keys, next_obs_np, share_data, jax)


def _ship_rollout(runtime, local_data, flat_keys, next_obs_np, share_data, jax):
    data = {k: np.asarray(local_data[k]) for k in (*flat_keys, *_SEQ_KEYS)}
    if share_data and jax.process_count() > 1:
        from jax.experimental import multihost_utils

        gathered = multihost_utils.process_allgather(data)
        data = {
            k: np.moveaxis(v, 0, 1).reshape(v.shape[1], -1, *v.shape[3:])
            for k, v in gathered.items()
        }
        g_next = multihost_utils.process_allgather(next_obs_np)
        next_obs_np = jax.tree_util.tree_map(
            lambda v: v.reshape(-1, *v.shape[2:]), g_next
        )
    n_env_cols = data["rewards"].shape[1]
    if n_env_cols % runtime.world_size == 0:
        return (
            runtime.shard_batch(data, axis=1),
            runtime.shard_batch(next_obs_np, axis=0),
        )
    if jax.process_count() > 1 and not share_data:
        # Replication would be incoherent here: each process holds
        # DIFFERENT rollouts, and a "replicated" global array assumes every
        # copy is identical — GSPMD may then read any process's copy,
        # silently training on mixed data. No safe layout exists.
        raise ValueError(
            f"num_envs ({n_env_cols} env columns) must be divisible by the "
            f"data-axis size ({runtime.world_size}) in a multi-process run "
            "(or enable buffer.share_data to train on the gathered union)."
        )
    # Single process, OR the share_data allgather above already ran: every
    # process now holds the identical gathered union, so replication is
    # coherent (just pays the full copy per device).
    warnings.warn(
        f"num_envs ({n_env_cols}) is not divisible by the data-axis size "
        f"({runtime.world_size}): the rollout is replicated to every device "
        "(correct but pays a full copy per device). Set env.num_envs to a "
        "multiple of the device count for sharded transfers.",
        stacklevel=2,
    )
    return runtime.replicate(data), runtime.replicate(next_obs_np)


def fuse_gae_pool(
    agent,
    params,
    data: Dict[str, Any],
    next_obs: Dict[str, Any],
    flat_keys: Sequence[str],
    gamma: float,
    gae_lambda: float,
    include_values: bool = False,
) -> Dict[str, Any]:
    """The in-jit prologue both train steps share: bootstrap the final obs,
    GAE over ``(T, E, 1)`` scalars, and flatten everything into the
    ``(T*E, ...)`` minibatch pool (row order t*E + e)."""
    import jax.numpy as jnp

    from sheeprl_tpu.utils.ops import gae

    next_values = agent.get_values(params, next_obs)
    values = data["values"].astype(jnp.float32)
    returns, advantages = gae(
        data["rewards"].astype(jnp.float32),
        values,
        data["dones"].astype(jnp.float32),
        next_values,
        gamma,
        gae_lambda,
    )
    n = returns.shape[0] * returns.shape[1]
    pool = {k: data[k].reshape(n, *data[k].shape[2:]) for k in flat_keys}
    pool["returns"] = returns.reshape(n, *returns.shape[2:])
    pool["advantages"] = advantages.reshape(n, *advantages.shape[2:])
    if include_values:
        pool["values"] = values.reshape(n, *values.shape[2:])
    return pool
