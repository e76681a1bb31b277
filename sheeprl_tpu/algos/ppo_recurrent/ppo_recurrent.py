"""Recurrent PPO training loop (reference: sheeprl/algos/ppo_recurrent/ppo_recurrent.py:30-524).

TPU-first structure on the PPO loop's plan, plus BPTT:
- Rollout: the jitted length-1-sequence player threads the LSTM carry
  explicitly; prev_actions / prev_hx / prev_cx / dones are stored per step.
- Training: the rollout [T, N] is cut into FIXED-length chunks of
  `per_rank_sequence_length` (rollout_steps must be a multiple), each seeded
  with its stored initial carry; episode boundaries inside a chunk reset the
  carry in-scan via the shifted done flags. This replaces the reference's
  variable-length padded episode splitting (ppo_recurrent.py:414-444) with
  static shapes — no padding, no masks, every step is real.
- Update: epochs x minibatches of whole sequences inside ONE jitted call,
  batch sharded over the mesh's data axis.
"""

from __future__ import annotations

import copy
import os
import warnings
from functools import partial
from typing import Any, Dict

import gymnasium as gym
import jax
import jax.numpy as jnp
import numpy as np
import optax

from sheeprl_tpu.algos.ppo.agent import actions_metadata
from sheeprl_tpu.algos.ppo.loss import entropy_loss, policy_loss, value_loss
from sheeprl_tpu.algos.ppo.ppo import _current_lr
from sheeprl_tpu.algos.ppo.utils import normalize_obs, prepare_obs
from sheeprl_tpu.algos.ppo_recurrent.agent import RecurrentPPOAgent, build_agent
from sheeprl_tpu.algos.ppo_recurrent.utils import test
from sheeprl_tpu.config.instantiate import instantiate
from sheeprl_tpu.core.interact import InteractionPipeline
from sheeprl_tpu.core.mesh import DATA_AXIS
from sheeprl_tpu.core.player import PlayerPlacement
from sheeprl_tpu.data.buffers import ReplayBuffer
from sheeprl_tpu.registry import register_algorithm
from sheeprl_tpu.utils.checkpoint import load_checkpoint, restore_opt_state, save_checkpoint
from sheeprl_tpu.utils.env import make_vector_env
from sheeprl_tpu.utils.logger import get_log_dir, get_logger
from sheeprl_tpu.utils.metric import MetricAggregator
from sheeprl_tpu.utils.ops import gae, normalize_tensor
from sheeprl_tpu.utils.timer import timer
from sheeprl_tpu.utils.utils import polynomial_decay, save_configs


def make_train_step(agent: RecurrentPPOAgent, tx: optax.GradientTransformation, cfg: Dict[str, Any], mesh):
    """Build the jitted full-update over [S, sl, ...] sequence data."""
    from jax.sharding import NamedSharding, PartitionSpec as P

    update_epochs = int(cfg.algo.update_epochs)
    num_batches = max(1, int(cfg.algo.per_rank_num_batches))
    cnn_keys = list(cfg.algo.cnn_keys.encoder)
    obs_keys = cnn_keys + list(cfg.algo.mlp_keys.encoder)
    normalize_advantages = bool(cfg.algo.normalize_advantages)
    clip_vloss = bool(cfg.algo.clip_vloss)
    reduction = cfg.algo.loss_reduction
    vf_coef = float(cfg.algo.vf_coef)

    def loss_fn(params, batch, clip_coef, ent_coef):
        # batch arrays are [sl, mb, ...]
        obs = normalize_obs({k: batch[k] for k in obs_keys}, cnn_keys, obs_keys)
        carry = (batch["cx0"], batch["hx0"])
        new_logprobs, entropy, new_values = agent.evaluate_sequence(
            params, obs, batch["prev_actions"], carry, batch["prev_dones"], batch["actions"]
        )
        advantages = batch["advantages"]
        if normalize_advantages:
            advantages = normalize_tensor(advantages)
        pg_loss = policy_loss(new_logprobs, batch["logprobs"], advantages, clip_coef, reduction)
        v_loss = value_loss(new_values, batch["values"], batch["returns"], clip_coef, clip_vloss, reduction)
        ent_loss = entropy_loss(entropy, reduction)
        total = pg_loss + vf_coef * v_loss + ent_coef * ent_loss
        return total, (pg_loss, v_loss, ent_loss)

    seq_sharding = NamedSharding(mesh, P(DATA_AXIS))

    @partial(jax.jit, donate_argnums=(0, 1))
    def train_step(params, opt_state, data, key, clip_coef, ent_coef):
        """data: dict of [S, ...] arrays — sequence-major; hx0/cx0 are [S, H]."""
        next_key, key = jax.random.split(key)
        n = data["actions"].shape[0]
        mb_size = max(1, n // num_batches)
        num_mb = max(1, -(-n // mb_size))

        def epoch_body(carry, epoch_key):
            params, opt_state = carry
            perm = jax.random.permutation(epoch_key, n)
            idx = jnp.arange(num_mb * mb_size) % n
            idx = perm[idx].reshape(num_mb, mb_size)

            def mb_body(carry, mb_idx):
                params, opt_state = carry
                batch = {k: jnp.take(v, mb_idx, axis=0) for k, v in data.items()}
                batch = jax.lax.with_sharding_constraint(batch, {k: seq_sharding for k in batch})
                # sequence-major -> time-major for the in-loss scan
                batch = {
                    k: (jnp.moveaxis(v, 0, 1) if k not in ("hx0", "cx0") else v)
                    for k, v in batch.items()
                }
                (loss, (pg, vl, ent)), grads = jax.value_and_grad(loss_fn, has_aux=True)(
                    params, batch, clip_coef, ent_coef
                )
                updates, opt_state = tx.update(grads, opt_state, params)
                params = optax.apply_updates(params, updates)
                return (params, opt_state), jnp.stack([pg, vl, ent])

            (params, opt_state), metrics = jax.lax.scan(mb_body, (params, opt_state), idx)
            return (params, opt_state), metrics.mean(0)

        keys = jax.random.split(key, update_epochs)
        (params, opt_state), metrics = jax.lax.scan(epoch_body, (params, opt_state), keys)
        m = metrics.mean(0)
        return params, opt_state, {"policy_loss": m[0], "value_loss": m[1], "entropy_loss": m[2]}, next_key

    return train_step


def _to_sequences(arr: np.ndarray, chunks: int, sl: int) -> np.ndarray:
    """[T, N, ...] -> [chunks*N, sl, ...] (sequence-major fixed chunks)."""
    n = arr.shape[1]
    arr = arr.reshape(chunks, sl, n, *arr.shape[2:])
    arr = np.moveaxis(arr, 2, 1)  # [chunks, N, sl, ...]
    return arr.reshape(chunks * n, sl, *arr.shape[3:])


@register_algorithm()
def main(runtime, cfg: Dict[str, Any]):
    if "minedojo" in str(cfg.env.wrapper.get("_target_", "")).lower():
        raise ValueError(
            "MineDojo is not currently supported by PPO agent, since it does not take "
            "into consideration the action masks provided by the environment, but needed "
            "in order to play correctly the game. "
            "As an alternative you can use one of the Dreamers' agents."
        )
    if cfg.algo.rollout_steps % cfg.algo.per_rank_sequence_length != 0:
        raise ValueError(
            f"rollout_steps ({cfg.algo.rollout_steps}) must be a multiple of "
            f"per_rank_sequence_length ({cfg.algo.per_rank_sequence_length})"
        )

    initial_ent_coef = float(cfg.algo.ent_coef)
    initial_clip_coef = float(cfg.algo.clip_coef)
    mesh = runtime.mesh

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

    rank = runtime.global_rank
    world_size = jax.process_count()
    envs = make_vector_env(cfg, rank, log_dir)
    observation_space = envs.single_observation_space
    if not isinstance(observation_space, gym.spaces.Dict):
        raise RuntimeError(f"Unexpected observation type, should be of type Dict, got: {observation_space}")
    if cfg.algo.cnn_keys.encoder + cfg.algo.mlp_keys.encoder == []:
        raise RuntimeError(
            "You should specify at least one CNN keys or MLP keys from the cli: "
            "`algo.cnn_keys.encoder=[rgb]` or `algo.mlp_keys.encoder=[state]`"
        )
    if cfg.metric.log_level > 0:
        runtime.print("Encoder CNN keys:", cfg.algo.cnn_keys.encoder)
        runtime.print("Encoder MLP keys:", cfg.algo.mlp_keys.encoder)
    obs_keys = cfg.algo.cnn_keys.encoder + cfg.algo.mlp_keys.encoder
    cnn_keys = cfg.algo.cnn_keys.encoder

    actions_dim, is_continuous = actions_metadata(envs.single_action_space)
    clip_rewards_fn = (lambda r: np.tanh(r)) if cfg.env.clip_rewards else (lambda r: r)

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
            from sheeprl_tpu.config.instantiate import locate

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

    last_train = 0
    train_step_count = 0
    start_iter = (state["iter_num"] // world_size) + 1 if state is not None else 1
    policy_step = state["iter_num"] * cfg.env.num_envs * cfg.algo.rollout_steps if state is not None else 0
    last_log = state["last_log"] if state is not None else 0
    last_checkpoint = state["last_checkpoint"] if state is not None else 0
    policy_steps_per_iter = int(cfg.env.num_envs * cfg.algo.rollout_steps * world_size)
    total_iters = cfg.algo.total_steps // policy_steps_per_iter if not cfg.dry_run else 1
    if state is not None:
        cfg.algo.per_rank_num_batches = state["batch_size"] // world_size

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
    get_values_fn = jax.jit(agent.get_values)
    reset_states_fn = jax.jit(agent.reset_states)
    gae_fn = jax.jit(
        lambda rewards, values, dones, next_values: gae(
            rewards, values, dones, next_values, cfg.algo.gamma, cfg.algo.gae_lambda
        )
    )
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

    # ----------------------------------------------------------------- loop
    step_data = {}
    next_obs = envs.reset(seed=cfg.seed)[0]
    for k in obs_keys:
        step_data[k] = next_obs[k][np.newaxis]
    with placement.ctx():
        carry = agent.initial_states(cfg.env.num_envs)
    prev_actions = np.zeros((cfg.env.num_envs, int(np.sum(actions_dim))), np.float32)

    # Coalesced loss fetch + interval bounding (telemetry/step_timer.py):
    # ONE block_until_ready + ONE device_get per log interval.
    train_timer = telemetry.step_timer("train", timer_key="Time/train_time")
    keep_train_metrics = (aggregator is not None and not aggregator.disabled) or health.enabled
    for iter_num in range(start_iter, total_iters + 1):
        telemetry.advance(policy_step)
        guard.advance(policy_step)
        for _ in range(0, cfg.algo.rollout_steps):
            policy_step += cfg.env.num_envs * world_size

            with timer("Time/env_interaction_time"):
                with placement.ctx():
                    jnp_obs = prepare_obs(next_obs, cnn_keys=cnn_keys, num_envs=cfg.env.num_envs)
                    prev_carry = carry
                    actions_j, real_actions_j, logprobs_j, values_j, carry, rollout_key = player_step_fn(
                        placement.params(), jnp_obs, jnp.asarray(prev_actions), carry, rollout_key
                    )
                # Single host fetch for the step outputs AND the pre-step
                # carry snapshot the buffer stores (the post-step carry stays
                # on device) — one device->host roundtrip instead of six.
                # Submitted at dispatch, harvested at the use site.
                pending = pipeline.fetch(
                    (actions_j, real_actions_j, logprobs_j, values_j, prev_carry[0], prev_carry[1]),
                    label="player_actions",
                )
                actions, real_actions_np, logprobs, values, prev_cx_np, prev_hx_np = pending.harvest()

                obs, rewards, terminated, truncated, info = envs.step(
                    real_actions_np.reshape(envs.action_space.shape)
                )
                truncated_envs = np.nonzero(truncated)[0]
                if len(truncated_envs) > 0:
                    # Bootstrap truncated episodes with V(final_obs) using the
                    # post-step carry (reference: ppo_recurrent.py:313-336).
                    final_obs = info["final_obs"]
                    real_next_obs = {
                        k: np.stack([np.asarray(final_obs[e][k], np.float32) for e in truncated_envs])
                        for k in obs_keys
                    }
                    with placement.ctx():
                        jnp_next = prepare_obs(real_next_obs, cnn_keys=cnn_keys, num_envs=len(truncated_envs))
                        trunc_carry = tuple(s[truncated_envs] for s in carry)
                        vals_pending = pipeline.fetch(
                            get_values_fn(
                                placement.params(),
                                jnp_next,
                                jnp.asarray(actions[truncated_envs]),
                                trunc_carry,
                            ),
                            label="trunc_bootstrap",
                        )
                    vals = np.asarray(vals_pending.harvest())
                    rewards[truncated_envs] += cfg.algo.gamma * vals.reshape(rewards[truncated_envs].shape)
                dones = np.logical_or(terminated, truncated).reshape(cfg.env.num_envs, -1).astype(np.float32)
                rewards = clip_rewards_fn(rewards).reshape(cfg.env.num_envs, -1).astype(np.float32)

            step_data["dones"] = dones[np.newaxis]
            step_data["values"] = values[np.newaxis]
            step_data["actions"] = actions[np.newaxis]
            step_data["logprobs"] = logprobs[np.newaxis]
            step_data["rewards"] = rewards[np.newaxis]
            step_data["prev_hx"] = prev_hx_np[np.newaxis]
            step_data["prev_cx"] = prev_cx_np[np.newaxis]
            step_data["prev_actions"] = prev_actions[np.newaxis]
            if cfg.buffer.memmap:
                step_data["returns"] = np.zeros_like(rewards, shape=(1, *rewards.shape))
                step_data["advantages"] = np.zeros_like(rewards, shape=(1, *rewards.shape))

            rb.add(step_data, validate_args=cfg.buffer.validate_args)

            # A done resets the next step's previous action and carry
            # (reference: ppo_recurrent.py:357-372).
            prev_actions = ((1 - dones) * actions).astype(np.float32)
            if cfg.algo.reset_recurrent_state_on_done:
                with placement.ctx():
                    carry = reset_states_fn(carry, jnp.asarray(dones))

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

        # ------------------------------------------------- GAE + chunking
        local_data = rb.to_tensor()
        with placement.ctx():
            jnp_obs = prepare_obs(next_obs, cnn_keys=cnn_keys, num_envs=cfg.env.num_envs)
            next_values = get_values_fn(placement.params(), jnp_obs, jnp.asarray(prev_actions), carry)
            returns, advantages = gae_fn(
                jnp.asarray(np.asarray(local_data["rewards"]), jnp.float32),
                jnp.asarray(np.asarray(local_data["values"]), jnp.float32),
                jnp.asarray(np.asarray(local_data["dones"]), jnp.float32),
                next_values,
            )
        local_data["returns"] = np.asarray(returns)
        local_data["advantages"] = np.asarray(advantages)

        sl = int(cfg.algo.per_rank_sequence_length)
        T = int(cfg.algo.rollout_steps)
        chunks = T // sl
        n_envs = cfg.env.num_envs

        # Shifted dones drive the in-scan reset, matching what the player did
        # during the rollout; each chunk's stored initial carry already
        # includes the reset from the step before it. With
        # reset_recurrent_state_on_done=False the player never reset, so
        # training must not either.
        dones_arr = np.asarray(local_data["dones"], np.float32)  # [T, N, 1]
        if cfg.algo.reset_recurrent_state_on_done:
            shifted = np.concatenate([np.zeros_like(dones_arr[:1]), dones_arr[:-1]], 0)
            shifted = shifted.reshape(chunks, sl, n_envs, 1)
            shifted[:, 0] = 0.0
        else:
            shifted = np.zeros_like(dones_arr).reshape(chunks, sl, n_envs, 1)

        # Only what the loss consumes travels into the jitted update.
        loss_keys = set(obs_keys) | {
            "prev_actions", "actions", "logprobs", "values", "advantages", "returns"
        }
        seq_data = {
            k: _to_sequences(np.asarray(v, np.float32), chunks, sl)
            for k, v in local_data.items()
            if k in loss_keys
        }
        seq_data["prev_dones"] = _to_sequences(shifted.reshape(T, n_envs, 1), chunks, sl)
        hx = np.asarray(local_data["prev_hx"], np.float32).reshape(chunks, sl, n_envs, -1)
        cx = np.asarray(local_data["prev_cx"], np.float32).reshape(chunks, sl, n_envs, -1)
        # hx[:, 0] is [chunks, N, H]; flattening chunk-major matches the
        # sequence ordering produced by _to_sequences.
        seq_data["hx0"] = hx[:, 0].reshape(chunks * n_envs, -1)
        seq_data["cx0"] = cx[:, 0].reshape(chunks * n_envs, -1)

        with timer("Time/train_time"):
            with train_timer.step():
                params, opt_state, train_metrics, train_key = train_fn(
                    params,
                    opt_state,
                    seq_data,
                    train_key,
                    np.asarray(cfg.algo.clip_coef, np.float32),
                    np.asarray(cfg.algo.ent_coef, np.float32),
                )
            # No sync here: the StepTimer queues the loss scalars device-side
            # and bounds the interval with ONE block at the flush below.
            train_timer.pend(params, train_metrics if keep_train_metrics else None)
        placement.push(params)
        train_step_count += world_size

        # ------------------------------------------------------- logging
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
                    aggregator.update("Loss/entropy_loss", tm["entropy_loss"])
                # Collective when sync_on_compute is on: every rank joins;
                # only rank 0 (the only rank with a logger) writes.
                aggregator.log_and_reset(logger, policy_step)
            telemetry.log_counters(logger, policy_step)
        if cfg.metric.log_level > 0 and logger is not None:
            logger.log("Info/learning_rate", _current_lr(opt_state, base_lr), policy_step)
            logger.log("Info/clip_coef", cfg.algo.clip_coef, policy_step)
            logger.log("Info/ent_coef", cfg.algo.ent_coef, policy_step)

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

        # ----------------------------------------------------- annealing
        if cfg.algo.anneal_lr:
            new_lr = polynomial_decay(iter_num, initial=base_lr, final=0.0, max_decay_steps=total_iters, power=1.0)
            opt_state.hyperparams["lr"] = jnp.asarray(new_lr, jnp.float32)
        if cfg.algo.anneal_clip_coef:
            cfg.algo.clip_coef = polynomial_decay(
                iter_num, initial=initial_clip_coef, final=0.0, max_decay_steps=total_iters, power=1.0
            )
        if cfg.algo.anneal_ent_coef:
            cfg.algo.ent_coef = polynomial_decay(
                iter_num, initial=initial_ent_coef, final=0.0, max_decay_steps=total_iters, power=1.0
            )

        # ---------------------------------------------------- checkpoint
        if health.allow_save() and (
            (cfg.checkpoint.every > 0 and policy_step - last_checkpoint >= cfg.checkpoint.every)
            or ((iter_num == total_iters or guard.preempted) and cfg.checkpoint.save_last)
        ):
            last_checkpoint = policy_step
            ckpt_state = {
                "agent": params,
                "optimizer": opt_state,
                "iter_num": iter_num * world_size,
                "batch_size": cfg.algo.per_rank_num_batches * world_size,
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
