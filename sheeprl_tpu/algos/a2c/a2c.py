"""A2C, coupled training loop (reference: sheeprl/algos/a2c/a2c.py:26-440).

Same rollout/GAE structure as PPO (the reference reuses the PPO agent,
a2c.py:14), but the update is a single pass with gradients ACCUMULATED over
minibatches and one optimizer step (reference: no_backward_sync accumulation,
a2c.py:64-112). Here that is a `lax.scan` over minibatches summing gradients,
followed by one `tx.update` — all inside one jitted call.
"""

from __future__ import annotations

import os
import warnings
from functools import partial
from typing import Any, Dict

import gymnasium as gym
import jax
import jax.numpy as jnp
import numpy as np
import optax

from sheeprl_tpu.algos.a2c.loss import policy_loss, value_loss
from sheeprl_tpu.algos.a2c.utils import prepare_obs, test
from sheeprl_tpu.algos.ppo.agent import PPOAgent, actions_metadata, build_agent
from sheeprl_tpu.algos.ppo.loss import entropy_loss
from sheeprl_tpu.config.instantiate import instantiate, locate
from sheeprl_tpu.core.interact import InteractionPipeline
from sheeprl_tpu.core.mesh import DATA_AXIS
from sheeprl_tpu.core.player import PlayerPlacement
from sheeprl_tpu.core.rollout import fuse_gae_pool, ship_rollout
from sheeprl_tpu.data.buffers import ReplayBuffer
from sheeprl_tpu.registry import register_algorithm
from sheeprl_tpu.utils.checkpoint import load_checkpoint, restore_opt_state, save_checkpoint
from sheeprl_tpu.utils.env import make_vector_env
from sheeprl_tpu.utils.logger import get_log_dir, get_logger
from sheeprl_tpu.utils.metric import MetricAggregator
from sheeprl_tpu.utils.ops import normalize_tensor
from sheeprl_tpu.utils.timer import timer
from sheeprl_tpu.utils.utils import polynomial_decay, save_configs


def make_train_step(agent: PPOAgent, tx: optax.GradientTransformation, cfg: Dict[str, Any], mesh):
    """One jitted update for the WHOLE iteration: bootstrap values for the
    last observation, GAE over the rollout, then a scan over minibatches
    accumulating grads into a single optimizer step.

    Fusing the bootstrap+GAE into the update (instead of separate
    `get_values`/`gae` dispatches whose returns/advantages round-tripped
    through the host) matters precisely on this algorithm: at the benchmark
    shape (5-step rollouts) A2C runs one update per 5 env steps, so
    per-iteration dispatch overhead is 1/25th of PPO's amortization — the
    audit VERDICT r4 weak #2 asked for. One dispatch, zero host fetches on
    the update path."""
    from jax.sharding import NamedSharding, PartitionSpec as P

    mb_size = int(cfg.algo.per_rank_batch_size)
    obs_keys = list(cfg.algo.mlp_keys.encoder)
    normalize_advantages = bool(cfg.algo.get("normalize_advantages", False))
    reduction = cfg.algo.loss_reduction
    vf_coef = float(cfg.algo.vf_coef)
    ent_coef = float(cfg.algo.get("ent_coef", 0.0))
    gamma = float(cfg.algo.gamma)
    gae_lambda = float(cfg.algo.gae_lambda)

    def loss_fn(params, batch):
        obs = {k: batch[k] for k in obs_keys}
        logprobs, entropy, new_values = agent.evaluate_actions(params, obs, batch["actions"])
        advantages = batch["advantages"]
        if normalize_advantages:
            advantages = normalize_tensor(advantages)
        pg_loss = policy_loss(logprobs, advantages, reduction)
        v_loss = value_loss(new_values, batch["returns"], reduction)
        ent_loss = entropy_loss(entropy, reduction)
        total = pg_loss + vf_coef * v_loss + ent_coef * ent_loss
        return total, (pg_loss, v_loss)

    batch_sharding = NamedSharding(mesh, P(DATA_AXIS))

    @partial(jax.jit, donate_argnums=(0, 1))
    def train_step(params, opt_state, data, next_obs, key):
        # data is (T, E, ...) env-sharded (core/rollout.py); bootstrap +
        # GAE + flattening happen in-graph via the shared prologue.
        pool = fuse_gae_pool(
            agent, params, data, next_obs, (*obs_keys, "actions"),
            gamma, gae_lambda,
        )
        n = pool["actions"].shape[0]
        next_key, key = jax.random.split(key)
        num_mb = max(1, -(-n // mb_size))
        perm = jax.random.permutation(key, n)
        idx = perm[jnp.arange(num_mb * mb_size) % n].reshape(num_mb, mb_size)
        zero_grads = jax.tree_util.tree_map(jnp.zeros_like, params)

        def mb_body(grads_acc, mb_idx):
            batch = {k: jnp.take(v, mb_idx, axis=0) for k, v in pool.items()}
            batch = jax.lax.with_sharding_constraint(batch, {k: batch_sharding for k in batch})
            (_, (pg, vl)), grads = jax.value_and_grad(loss_fn, has_aux=True)(params, batch)
            return jax.tree_util.tree_map(jnp.add, grads_acc, grads), jnp.stack([pg, vl])

        grads_sum, metrics = jax.lax.scan(mb_body, zero_grads, idx)
        updates, opt_state = tx.update(grads_sum, opt_state, params)
        params = optax.apply_updates(params, updates)
        m = metrics.mean(0)
        return params, opt_state, {"policy_loss": m[0], "value_loss": m[1]}, next_key

    return train_step


@register_algorithm()
def main(runtime, cfg: Dict[str, Any]):
    mesh = runtime.mesh
    rank = runtime.global_rank

    state = None
    if cfg.checkpoint.resume_from:
        state = load_checkpoint(cfg.checkpoint.resume_from)

    logger = get_logger(runtime, cfg)
    if logger is not None:
        logger.log_hyperparams(cfg.as_dict() if hasattr(cfg, "as_dict") else dict(cfg))
    log_dir = get_log_dir(runtime, cfg.root_dir, cfg.run_name, logger=logger)
    telemetry = runtime.telemetry.open(log_dir, rank_zero=runtime.is_global_zero, device=runtime.device)
    guard = runtime.resilience.guard(rank_zero=runtime.is_global_zero)
    health = runtime.health
    runtime.print(f"Log dir: {log_dir}")

    envs = make_vector_env(cfg, rank, log_dir)
    observation_space = envs.single_observation_space
    if not isinstance(observation_space, gym.spaces.Dict):
        raise RuntimeError(f"Unexpected observation type, should be of type Dict, got: {observation_space}")
    if len(cfg.algo.mlp_keys.encoder) == 0:
        raise RuntimeError("You should specify at least one MLP key for the A2C agent: `algo.mlp_keys.encoder=[state]`")
    if cfg.metric.log_level > 0:
        runtime.print("Encoder MLP keys:", cfg.algo.mlp_keys.encoder)
    obs_keys = list(cfg.algo.mlp_keys.encoder)

    actions_dim, is_continuous = actions_metadata(envs.single_action_space)

    # Eager flax/optax init runs host-side (each eager dispatch pays a
    # host-device round trip); the finished trees then move to the mesh.
    with runtime.host_init():
        agent, params = build_agent(
            runtime, actions_dim, is_continuous, cfg, observation_space,
            state["agent"] if state is not None else None,
        )

        optim_cfg = dict(cfg.algo.optimizer)
        optim_target = optim_cfg.pop("_target_")
        base_lr = float(optim_cfg.pop("lr"))

        def make_tx(lr):
            inner = locate(optim_target)(lr=lr, **optim_cfg)
            if cfg.algo.max_grad_norm > 0.0:
                return optax.chain(optax.clip_by_global_norm(cfg.algo.max_grad_norm), inner)
            return inner

        tx = optax.inject_hyperparams(make_tx)(lr=base_lr)
        opt_state = tx.init(params)
        if state is not None:
            opt_state = restore_opt_state(opt_state, state["optimizer"])
    params = runtime.shard_params(params)
    opt_state = runtime.shard_params(opt_state)

    if runtime.is_global_zero:
        save_configs(cfg, log_dir)

    aggregator = None
    if not MetricAggregator.disabled:
        aggregator: MetricAggregator = instantiate(cfg.metric.aggregator)

    if cfg.buffer.size < cfg.algo.rollout_steps:
        raise ValueError(
            f"The size of the buffer ({cfg.buffer.size}) cannot be lower "
            f"than the rollout steps ({cfg.algo.rollout_steps})"
        )
    rb = ReplayBuffer(
        cfg.buffer.size,
        cfg.env.num_envs,
        memmap=cfg.buffer.memmap,
        memmap_dir=os.path.join(log_dir, "memmap_buffer", f"rank_{rank}"),
        obs_keys=obs_keys,
    )

    world_size = jax.process_count()
    last_train = 0
    train_step_count = 0
    start_iter = (state["iter_num"] // world_size) + 1 if state is not None else 1
    policy_step = state["iter_num"] * cfg.env.num_envs * cfg.algo.rollout_steps if state is not None else 0
    last_log = state["last_log"] if state is not None else 0
    last_checkpoint = state["last_checkpoint"] if state is not None else 0
    policy_steps_per_iter = int(cfg.env.num_envs * cfg.algo.rollout_steps * world_size)
    total_iters = cfg.algo.total_steps // policy_steps_per_iter if not cfg.dry_run else 1
    if state is not None:
        cfg.algo.per_rank_batch_size = state["batch_size"] // world_size

    if cfg.metric.log_level > 0 and cfg.metric.log_every % policy_steps_per_iter != 0:
        warnings.warn(
            f"The metric.log_every parameter ({cfg.metric.log_every}) is not a multiple of the "
            f"policy_steps_per_iter value ({policy_steps_per_iter}), so "
            "the metrics will be logged at the nearest greater multiple of the policy_steps_per_iter value."
        )
    if cfg.checkpoint.every % policy_steps_per_iter != 0:
        warnings.warn(
            f"The checkpoint.every parameter ({cfg.checkpoint.every}) is not a multiple of the "
            f"policy_steps_per_iter value ({policy_steps_per_iter}), so "
            "the checkpoint will be saved at the nearest greater multiple of the policy_steps_per_iter value."
        )

    player_step_fn = jax.jit(agent.player_step)
    # get_values_fn survives only for the (rare) mid-rollout truncation
    # bootstrap; end-of-rollout bootstrap + GAE live inside train_fn.
    get_values_fn = jax.jit(agent.get_values)
    train_fn = make_train_step(agent, tx, cfg, mesh)

    # Latency-aware player placement (core/player.py); on-policy => fresh.
    placement = PlayerPlacement.resolve(
        cfg, mesh.devices.flat[0], params=params, force_fresh=True
    )
    placement.push(params)

    rollout_key, train_key = jax.random.split(jax.random.fold_in(runtime.root_key, rank))
    rollout_key = placement.put(rollout_key)

    # Async-capable action fetch (core/interact.py): with fabric.async_fetch
    # the D2H copy is submitted at dispatch time and harvested right before
    # envs.step; off it is op-for-op the old blocking fetch.
    pipeline = InteractionPipeline.from_config(cfg)

    # Coalesced loss fetch + interval bounding (telemetry/step_timer.py):
    # ONE block_until_ready + ONE device_get per log interval.
    train_timer = telemetry.step_timer("train", timer_key="Time/train_time")
    keep_train_metrics = (aggregator is not None and not aggregator.disabled) or health.enabled
    step_data = {}
    next_obs = envs.reset(seed=cfg.seed)[0]
    for k in obs_keys:
        step_data[k] = next_obs[k][np.newaxis]

    for iter_num in range(start_iter, total_iters + 1):
        telemetry.advance(policy_step)
        guard.advance(policy_step)
        for _ in range(0, cfg.algo.rollout_steps):
            policy_step += cfg.env.num_envs * world_size

            with timer("Time/env_interaction_time"):
                with placement.ctx():
                    # prepare_obs is numpy; PRNG split runs inside the jit —
                    # one dispatch, one host fetch per step.
                    np_obs = prepare_obs(next_obs, mlp_keys=obs_keys, num_envs=cfg.env.num_envs)
                    *step_out, rollout_key = player_step_fn(
                        placement.params(), np_obs, rollout_key
                    )
                    # Structural per-step sync (actions feed env.step):
                    # submitted at dispatch, harvested at the use site.
                    pending = pipeline.fetch(step_out, label="player_actions")
                actions, real_actions_np, logprobs, values = pending.harvest()

                obs, rewards, terminated, truncated, info = envs.step(
                    real_actions_np.reshape(envs.action_space.shape)
                )
                truncated_envs = np.nonzero(truncated)[0]
                if len(truncated_envs) > 0:
                    final_obs = info["final_obs"]
                    real_next_obs = {
                        k: np.stack([np.asarray(final_obs[e][k], np.float32) for e in truncated_envs])
                        for k in obs_keys
                    }
                    with placement.ctx():
                        jnp_next = prepare_obs(real_next_obs, mlp_keys=obs_keys, num_envs=len(truncated_envs))
                        vals_pending = pipeline.fetch(
                            get_values_fn(placement.params(), jnp_next), label="trunc_bootstrap"
                        )
                    vals = np.asarray(vals_pending.harvest())
                    rewards[truncated_envs] += cfg.algo.gamma * vals.reshape(rewards[truncated_envs].shape)
                dones = np.logical_or(terminated, truncated).reshape(cfg.env.num_envs, -1).astype(np.uint8)
                rewards = rewards.reshape(cfg.env.num_envs, -1).astype(np.float32)

            step_data["dones"] = dones[np.newaxis]
            step_data["values"] = values[np.newaxis]
            step_data["actions"] = actions[np.newaxis]
            step_data["logprobs"] = logprobs[np.newaxis]
            step_data["rewards"] = rewards[np.newaxis]
            # returns/advantages are computed INSIDE the train jit now — no
            # buffer placeholders, no host round-trip.

            rb.add(step_data, validate_args=cfg.buffer.validate_args)

            next_obs = {}
            for k in obs_keys:
                step_data[k] = obs[k][np.newaxis]
                next_obs[k] = obs[k]

            if cfg.metric.log_level > 0 and "final_info" in info:
                fi = info["final_info"]
                for i in np.nonzero(fi.get("_episode", []))[0]:
                    ep_rew = float(fi["episode"]["r"][i])
                    ep_len = float(fi["episode"]["l"][i])
                    if aggregator and "Rewards/rew_avg" in aggregator:
                        aggregator.update("Rewards/rew_avg", ep_rew)
                    if aggregator and "Game/ep_len_avg" in aggregator:
                        aggregator.update("Game/ep_len_avg", ep_len)
                    runtime.print(f"Rank-0: policy_step={policy_step}, reward_env_{i}={ep_rew}")

        # Ship the rollout ((T, E) tensors env-sharded — core/rollout.py);
        # the whole update is then ONE dispatch.
        local_data = rb.to_tensor()
        next_obs_np = prepare_obs(next_obs, mlp_keys=obs_keys, num_envs=cfg.env.num_envs)
        data, jnp_next = ship_rollout(
            runtime,
            local_data,
            (*obs_keys, "actions"),
            next_obs_np,
            share_data=bool(cfg.buffer.get("share_data", False)),
        )

        with timer("Time/train_time"):
            with train_timer.step():
                params, opt_state, train_metrics, train_key = train_fn(
                    params, opt_state, data, jnp_next, train_key
                )
            # No sync here: the StepTimer queues the loss scalars device-side
            # and bounds the interval with ONE block at the flush below.
            train_timer.pend(params, train_metrics if keep_train_metrics else None)
        placement.push(params)
        train_step_count += world_size

        should_log = cfg.metric.log_level > 0 and (
            policy_step - last_log >= cfg.metric.log_every or iter_num == total_iters
        )
        if should_log:
            # ONE bounding block + ONE device->host transfer for the whole
            # interval (StepTimer.flush) — the coalesced GL002 pattern.
            fetched_train_metrics = train_timer.flush()
            # Health sentinels inspect the same coalesced fetch — no extra
            # transfer; a nonfinite hit taints the run and escalates.
            health.observe(policy_step, fetched_train_metrics, telemetry=telemetry)
            if aggregator and not aggregator.disabled:
                for tm in fetched_train_metrics:
                    aggregator.update("Loss/policy_loss", tm["policy_loss"])
                    aggregator.update("Loss/value_loss", tm["value_loss"])
                # Collective when sync_on_compute is on: every rank joins;
                # only rank 0 (the only rank with a logger) writes.
                aggregator.log_and_reset(logger, policy_step)
            telemetry.log_counters(logger, policy_step)
        if cfg.metric.log_level > 0 and logger is not None:
            if should_log:
                if not timer.disabled:
                    timer_metrics = timer.compute()
                    if timer_metrics.get("Time/train_time", 0) > 0:
                        logger.log(
                            "Time/sps_train",
                            (train_step_count - last_train) / timer_metrics["Time/train_time"],
                            policy_step,
                        )
                    if timer_metrics.get("Time/env_interaction_time", 0) > 0:
                        logger.log(
                            "Time/sps_env_interaction",
                            ((policy_step - last_log) / world_size * cfg.env.action_repeat)
                            / timer_metrics["Time/env_interaction_time"],
                            policy_step,
                        )
                    timer.reset()
        if should_log:
            last_log = policy_step
            last_train = train_step_count

        if cfg.algo.anneal_lr:
            new_lr = polynomial_decay(iter_num, initial=base_lr, final=0.0, max_decay_steps=total_iters, power=1.0)
            opt_state.hyperparams["lr"] = jnp.asarray(new_lr, jnp.float32)

        if health.allow_save() and (
            (cfg.checkpoint.every > 0 and policy_step - last_checkpoint >= cfg.checkpoint.every)
            or ((iter_num == total_iters or guard.preempted) and cfg.checkpoint.save_last)
        ):
            last_checkpoint = policy_step
            ckpt_state = {
                "agent": params,
                "optimizer": opt_state,
                "iter_num": iter_num * world_size,
                "batch_size": cfg.algo.per_rank_batch_size * world_size,
                "last_log": last_log,
                "last_checkpoint": last_checkpoint,
            }
            ckpt_path = os.path.join(log_dir, f"checkpoint/ckpt_{policy_step}_{rank}.ckpt")
            if runtime.is_global_zero:
                save_checkpoint(ckpt_path, ckpt_state, keep_last=cfg.checkpoint.keep_last)

        if guard.preempted:
            runtime.print(f"Preemption: exiting cleanly after final checkpoint at policy step {policy_step}")
            break
    pipeline.publish()
    envs.close()
    if runtime.is_global_zero and cfg.algo.run_test and not guard.preempted:
        test(agent, params, runtime, cfg, log_dir, logger)

    guard.close()
    telemetry.close()
    if logger is not None:
        logger.close()
