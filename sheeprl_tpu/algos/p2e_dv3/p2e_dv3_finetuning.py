"""Plan2Explore (DreamerV3) — finetuning phase
(reference: sheeprl/algos/p2e_dv3/p2e_dv3_finetuning.py:28-477).

Starts from an exploration-phase checkpoint (``checkpoint.exploration_ckpt_path``,
model/env hyperparameters inherited by the CLI — cli.py's p2e chaining) and
trains world model + TASK actor/critic with the plain DreamerV3 gradient step
on environment reward. The player acts with the exploration actor until
``learning_starts`` and then switches to the task actor (reference:
p2e_dv3_finetuning.py:350-353); optionally the exploration replay buffer is
carried over (``buffer.load_from_exploration``).
"""

from __future__ import annotations

import copy
import os
import warnings
from typing import Any, Dict

import gymnasium as gym
import jax
import jax.numpy as jnp
import numpy as np

from sheeprl_tpu.algos.dreamer_v3.agent import build_agent as dv3_build_agent
from sheeprl_tpu.algos.dreamer_v3.dreamer_v3 import _make_optimizer, make_train_step
from sheeprl_tpu.algos.p2e_dv3.utils import normalize_player_obs, prepare_obs, test
from sheeprl_tpu.algos.ppo.agent import actions_metadata
from sheeprl_tpu.config.instantiate import instantiate
from sheeprl_tpu.core.interact import InteractionPipeline
from sheeprl_tpu.core.player import PlayerPlacement
from sheeprl_tpu.data.infeed import ReplayInfeed
from sheeprl_tpu.data.buffers import EnvIndependentReplayBuffer, SequentialReplayBuffer
from sheeprl_tpu.core.runtime import DispatchThrottle
from sheeprl_tpu.registry import register_algorithm
from sheeprl_tpu.utils.checkpoint import load_checkpoint, restore_opt_state, save_checkpoint
from sheeprl_tpu.utils.env import make_vector_env
from sheeprl_tpu.utils.logger import get_log_dir, get_logger
from sheeprl_tpu.utils.metric import MetricAggregator
from sheeprl_tpu.utils.ops import init_moments
from sheeprl_tpu.utils.timer import timer
from sheeprl_tpu.utils.utils import Ratio, save_configs


def _inherit_exploration_hparams(cfg, exploration_cfg) -> None:
    """The finetuned models must match the exploration-phase architecture
    (reference: p2e_dv3_finetuning.py:46-70)."""
    cfg.algo.gamma = exploration_cfg.algo.gamma
    cfg.algo.lmbda = exploration_cfg.algo.lmbda
    cfg.algo.horizon = exploration_cfg.algo.horizon
    cfg.algo.dense_units = exploration_cfg.algo.dense_units
    cfg.algo.mlp_layers = exploration_cfg.algo.mlp_layers
    cfg.algo.dense_act = exploration_cfg.algo.dense_act
    cfg.algo.cnn_act = exploration_cfg.algo.cnn_act
    cfg.algo.unimix = exploration_cfg.algo.unimix
    cfg.algo.world_model = exploration_cfg.algo.world_model
    cfg.algo.actor = exploration_cfg.algo.actor
    cfg.algo.critic = exploration_cfg.algo.critic
    cfg.env.clip_rewards = exploration_cfg.env.clip_rewards
    if cfg.buffer.load_from_exploration and exploration_cfg.buffer.checkpoint:
        cfg.env.num_envs = exploration_cfg.env.num_envs
    cfg.algo.cnn_keys = exploration_cfg.algo.cnn_keys
    cfg.algo.mlp_keys = exploration_cfg.algo.mlp_keys


@register_algorithm(name="p2e_dv3_finetuning")
def main(runtime, cfg: Dict[str, Any], exploration_cfg: Dict[str, Any] = None):
    mesh = runtime.mesh
    rank = runtime.global_rank
    world_size = jax.process_count()

    resume_from_checkpoint = bool(cfg.checkpoint.resume_from)
    if resume_from_checkpoint:
        state_ckpt = load_checkpoint(cfg.checkpoint.resume_from)
    else:
        state_ckpt = load_checkpoint(cfg.checkpoint.exploration_ckpt_path)
    if exploration_cfg is not None:
        _inherit_exploration_hparams(cfg, exploration_cfg)

    cfg.env.frame_stack = -1

    logger = get_logger(runtime, cfg)
    if logger is not None:
        logger.log_hyperparams(cfg.as_dict() if hasattr(cfg, "as_dict") else dict(cfg))
    log_dir = get_log_dir(runtime, cfg.root_dir, cfg.run_name, logger=logger)
    runtime.print(f"Log dir: {log_dir}")
    telemetry = runtime.telemetry.open(log_dir, rank_zero=runtime.is_global_zero, device=runtime.device)
    guard = runtime.resilience.guard(rank_zero=runtime.is_global_zero)
    health = runtime.health

    envs = make_vector_env(cfg, rank, log_dir, restart_on_exception=True)
    action_space = envs.single_action_space
    observation_space = envs.single_observation_space

    actions_dim, is_continuous = actions_metadata(action_space)
    clip_rewards_fn = (lambda r: np.tanh(r)) if cfg.env.clip_rewards else (lambda r: r)
    if not isinstance(observation_space, gym.spaces.Dict):
        raise RuntimeError(f"Unexpected observation type, should be of type Dict, got: {observation_space}")
    if (
        len(set(cfg.algo.cnn_keys.encoder).intersection(set(cfg.algo.cnn_keys.decoder))) == 0
        and len(set(cfg.algo.mlp_keys.encoder).intersection(set(cfg.algo.mlp_keys.decoder))) == 0
    ):
        raise RuntimeError("The CNN keys or the MLP keys of the encoder and decoder must not be disjointed")
    obs_keys = list(cfg.algo.cnn_keys.encoder) + list(cfg.algo.mlp_keys.encoder)

    # Task models drive the DV3 train step; the exploration actor only plays.
    # Eager flax/optax init runs host-side (each eager dispatch pays a host-device round trip); shard_params then moves the finished trees to the mesh.
    with runtime.host_init():
        agent, agent_state = dv3_build_agent(
            runtime,
            actions_dim,
            is_continuous,
            cfg,
            observation_space,
            state_ckpt["world_model"],
            state_ckpt["actor_task"],
            state_ckpt["critic_task"],
            state_ckpt["target_critic_task"],
        )
        actor_exploration_params = jax.tree_util.tree_map(
            jnp.asarray, state_ckpt["actor_exploration"]
        )

        txs = {
            "world_model": _make_optimizer(cfg.algo.world_model.optimizer, cfg.algo.world_model.clip_gradients),
            "actor": _make_optimizer(cfg.algo.actor.optimizer, cfg.algo.actor.clip_gradients),
            "critic": _make_optimizer(cfg.algo.critic.optimizer, cfg.algo.critic.clip_gradients),
        }
        opt_states = {
            "world_model": txs["world_model"].init(agent_state["world_model"]),
            "actor": txs["actor"].init(agent_state["actor"]),
            "critic": txs["critic"].init(agent_state["critic"]),
        }
        if resume_from_checkpoint:
            for name, ckpt_key in (
                ("world_model", "world_optimizer"),
                ("actor", "actor_task_optimizer"),
                ("critic", "critic_task_optimizer"),
            ):
                opt_states[name] = restore_opt_state(opt_states[name], state_ckpt[ckpt_key])

    agent_state = runtime.shard_params(agent_state)
    opt_states = runtime.shard_params(opt_states)
    actor_exploration_params = runtime.shard_params(actor_exploration_params)

    # Moments: the exploration ckpt nests {"task", "exploration"}; a
    # finetuning ckpt stores the task tracker directly.
    moments_state = init_moments()
    ckpt_moments = state_ckpt.get("moments")
    if ckpt_moments is not None:
        if isinstance(ckpt_moments, dict) and "task" in ckpt_moments and "low" not in ckpt_moments:
            ckpt_moments = ckpt_moments["task"]
        moments_state = jax.tree_util.tree_map(jnp.asarray, ckpt_moments)

    if runtime.is_global_zero:
        save_configs(cfg, log_dir)

    aggregator = None
    if not MetricAggregator.disabled:
        aggregator: MetricAggregator = instantiate(cfg.metric.aggregator)

    buffer_size = cfg.buffer.size // int(cfg.env.num_envs * world_size) if not cfg.dry_run else 2
    rb = EnvIndependentReplayBuffer(
        buffer_size,
        n_envs=cfg.env.num_envs,
        memmap=cfg.buffer.memmap,
        memmap_dir=os.path.join(log_dir, "memmap_buffer", f"rank_{rank}"),
        buffer_cls=SequentialReplayBuffer,
    )
    load_rb = resume_from_checkpoint or (
        cfg.buffer.load_from_exploration
        and exploration_cfg is not None
        and exploration_cfg.buffer.checkpoint
    )
    if load_rb and state_ckpt.get("rb") is not None:
        rb = state_ckpt["rb"]

    train_step_count = 0
    last_train = 0
    start_iter = (state_ckpt["iter_num"] // world_size) + 1 if resume_from_checkpoint else 1
    policy_step = state_ckpt["iter_num"] * cfg.env.num_envs if resume_from_checkpoint else 0
    last_log = state_ckpt["last_log"] if resume_from_checkpoint else 0
    last_checkpoint = state_ckpt["last_checkpoint"] if resume_from_checkpoint else 0
    policy_steps_per_iter = int(cfg.env.num_envs * world_size)
    total_iters = int(cfg.algo.total_steps // policy_steps_per_iter) if not cfg.dry_run else 1
    learning_starts = cfg.algo.learning_starts // policy_steps_per_iter if not cfg.dry_run else 0
    prefill_steps = learning_starts - int(learning_starts > 0)
    if resume_from_checkpoint:
        cfg.algo.per_rank_batch_size = state_ckpt["batch_size"] // world_size
        learning_starts += start_iter
        prefill_steps += start_iter

    ratio = Ratio(cfg.algo.replay_ratio, pretrain_steps=cfg.algo.per_rank_pretrain_steps)
    if resume_from_checkpoint:
        ratio.load_state_dict(state_ckpt["ratio"])

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

    train_fn = make_train_step(agent, txs, cfg, mesh)
    player_cnn_keys = tuple(cfg.algo.cnn_keys.encoder)

    def _player_step(wm, a, s, o, k):
        # PRNG split + obs normalization in-graph: ONE dispatch per env step.
        next_k, sub = jax.random.split(k)
        out = agent.player_step(
            wm, a, s, normalize_player_obs(o, player_cnn_keys), sub, greedy=False
        )
        return (*out, next_k)

    player_step_fn = jax.jit(_player_step)
    init_player_fn = jax.jit(agent.init_player_state, static_argnums=(1,))
    reset_player_fn = jax.jit(agent.reset_player_state)
    # Exploration actor plays until training starts, then the task actor
    # takes over (reference: p2e_dv3_finetuning.py:350-353).
    player_actor_type = cfg.algo.player.actor_type

    # Latency-aware player placement (core/player.py); off-policy: honors
    # fabric.player_sync=async. The frozen exploration actor is mirrored once;
    # the trained world model + task actor refresh after every train call.
    placement = PlayerPlacement.resolve(
        cfg, runtime.mesh.devices.flat[0],
        params={"world_model": agent_state["world_model"], "actor": agent_state["actor"]},
    )
    placement.push({"world_model": agent_state["world_model"], "actor": agent_state["actor"]})
    player_actor_exploration = placement.put(actor_exploration_params)


    # Async infeed (data/infeed.py): the next train call's sampled batches
    # are copied host->device by a worker thread while envs step, so the
    # pixel-batch H2D never sits on the critical path.
    infeed = ReplayInfeed(
        rb,
        cfg.algo.per_rank_batch_size,
        cfg.algo.per_rank_sequence_length,
        cfg.algo.cnn_keys.encoder,
        enabled=cfg.buffer.get("prefetch", True),
    )

    rollout_key, train_key = jax.random.split(jax.random.fold_in(runtime.root_key, rank))
    rollout_key = placement.put(rollout_key)

    # Async-capable action fetch (core/interact.py): with fabric.async_fetch
    # the D2H copy is submitted at dispatch time and harvested right before
    # envs.step; off it is op-for-op the old blocking fetch.
    pipeline = InteractionPipeline.from_config(cfg)

    step_data = {}
    obs = envs.reset(seed=cfg.seed)[0]
    for k in obs_keys:
        step_data[k] = obs[k][np.newaxis]
    step_data["rewards"] = np.zeros((1, cfg.env.num_envs, 1), np.float32)
    step_data["truncated"] = np.zeros((1, cfg.env.num_envs, 1), np.float32)
    step_data["terminated"] = np.zeros((1, cfg.env.num_envs, 1), np.float32)
    step_data["is_first"] = np.ones_like(step_data["terminated"])
    with placement.ctx():
        player_state = init_player_fn(placement.params()["world_model"], cfg.env.num_envs)

    cumulative_per_rank_gradient_steps = 0
    # Bound async in-flight train dispatches (core/runtime.py: an
    # unbounded queue pins every pending call's sampled batch on host).
    dispatch_throttle = DispatchThrottle()
    # Coalesced loss fetch + interval bounding (telemetry/step_timer.py):
    # ONE block_until_ready + ONE device_get per log interval.
    train_timer = telemetry.step_timer("train", timer_key="Time/train_time")
    keep_train_metrics = (
        aggregator is not None and not aggregator.disabled and cfg.metric.log_level > 0
    ) or health.enabled
    for iter_num in range(start_iter, total_iters + 1):
        policy_step += policy_steps_per_iter
        telemetry.advance(policy_step)
        guard.advance(policy_step)

        with timer("Time/env_interaction_time"):
            with placement.ctx():
                pp = placement.params()
                player_actor = (
                    player_actor_exploration if player_actor_type == "exploration" else pp["actor"]
                )
                np_obs = prepare_obs(obs, cnn_keys=cfg.algo.cnn_keys.encoder, num_envs=cfg.env.num_envs)
                actions_cat, real_actions_j, player_state, rollout_key = player_step_fn(
                    pp["world_model"], player_actor, player_state, np_obs, rollout_key
                )
            # One host fetch for both arrays (single roundtrip): submitted
            # at dispatch, harvested at the use site.
            pending = pipeline.fetch((actions_cat, real_actions_j), label="player_actions")

            actions, real_actions = pending.harvest()
            step_data["actions"] = actions.reshape((1, cfg.env.num_envs, -1))
            rb.add(step_data, validate_args=cfg.buffer.validate_args)

            next_obs, rewards, terminated, truncated, infos = envs.step(
                real_actions.reshape(envs.action_space.shape)
            )
            dones = np.logical_or(terminated, truncated).astype(np.uint8)

        step_data["is_first"] = np.zeros_like(step_data["terminated"])
        if "restart_on_exception" in infos:
            for i, agent_roe in enumerate(infos["restart_on_exception"]):
                if agent_roe and not dones[i]:
                    last_inserted_idx = (rb.buffer[i]._pos - 1) % rb.buffer[i].buffer_size
                    rb.buffer[i]["terminated"][last_inserted_idx] = np.zeros_like(
                        rb.buffer[i]["terminated"][last_inserted_idx]
                    )
                    rb.buffer[i]["truncated"][last_inserted_idx] = np.ones_like(
                        rb.buffer[i]["truncated"][last_inserted_idx]
                    )
                    rb.buffer[i]["is_first"][last_inserted_idx] = np.zeros_like(
                        rb.buffer[i]["is_first"][last_inserted_idx]
                    )
                    step_data["is_first"][:, i] = np.ones_like(step_data["is_first"][:, i])

        if cfg.metric.log_level > 0 and "final_info" in infos:
            fi = infos["final_info"]
            for i in np.nonzero(fi.get("_episode", []))[0]:
                ep_rew = float(fi["episode"]["r"][i])
                ep_len = float(fi["episode"]["l"][i])
                if aggregator and not aggregator.disabled:
                    aggregator.update("Rewards/rew_avg", ep_rew)
                    aggregator.update("Game/ep_len_avg", ep_len)
                runtime.print(f"Rank-0: policy_step={policy_step}, reward_env_{i}={ep_rew}")

        real_next_obs = copy.deepcopy(next_obs)
        if "final_obs" in infos:
            for idx in np.nonzero(dones)[0]:
                final = infos["final_obs"][idx]
                if final is not None:
                    for k, v in final.items():
                        real_next_obs[k][idx] = v

        for k in obs_keys:
            step_data[k] = next_obs[k][np.newaxis]
        obs = next_obs

        rewards = rewards.reshape((1, cfg.env.num_envs, -1))
        step_data["terminated"] = terminated.reshape((1, cfg.env.num_envs, -1)).astype(np.float32)
        step_data["truncated"] = truncated.reshape((1, cfg.env.num_envs, -1)).astype(np.float32)
        step_data["rewards"] = clip_rewards_fn(rewards).astype(np.float32)

        dones_idxes = dones.nonzero()[0].tolist()
        reset_envs = len(dones_idxes)
        if reset_envs > 0:
            reset_data = {}
            for k in obs_keys:
                reset_data[k] = (real_next_obs[k][dones_idxes])[np.newaxis]
            reset_data["terminated"] = step_data["terminated"][:, dones_idxes]
            reset_data["truncated"] = step_data["truncated"][:, dones_idxes]
            reset_data["actions"] = np.zeros((1, reset_envs, int(np.sum(actions_dim))), np.float32)
            reset_data["rewards"] = step_data["rewards"][:, dones_idxes]
            reset_data["is_first"] = np.zeros_like(reset_data["terminated"])
            rb.add(reset_data, dones_idxes, validate_args=cfg.buffer.validate_args)

            step_data["rewards"][:, dones_idxes] = np.zeros_like(reset_data["rewards"])
            step_data["terminated"][:, dones_idxes] = np.zeros_like(step_data["terminated"][:, dones_idxes])
            step_data["truncated"][:, dones_idxes] = np.zeros_like(step_data["truncated"][:, dones_idxes])
            step_data["is_first"][:, dones_idxes] = np.ones_like(step_data["is_first"][:, dones_idxes])
            reset_mask = np.zeros((cfg.env.num_envs,), np.float32)
            reset_mask[dones_idxes] = 1.0
            with placement.ctx():
                player_state = reset_player_fn(
                    placement.params()["world_model"], player_state, jnp.asarray(reset_mask)
                )

        # ------------------------------------------------------- training
        if iter_num >= learning_starts:
            if player_actor_type != "task":
                # Hand the environment over to the task policy.
                player_actor_type = "task"
            ratio_steps = policy_step - prefill_steps * policy_steps_per_iter
            per_rank_gradient_steps = ratio(ratio_steps / world_size)
            if per_rank_gradient_steps > 0:
                batches = infeed.take_or_sample(per_rank_gradient_steps)
                with timer("Time/train_time"):
                    for i in range(per_rank_gradient_steps):
                        if (
                            cumulative_per_rank_gradient_steps
                            % cfg.algo.critic.per_rank_target_network_update_freq
                            == 0
                        ):
                            tau = 1.0 if cumulative_per_rank_gradient_steps == 0 else cfg.algo.critic.tau
                        else:
                            tau = 0.0
                        batch = batches[i]
                        with train_timer.step():
                            agent_state, opt_states, moments_state, train_metrics, train_key = train_fn(
                                agent_state, opt_states, moments_state, batch, train_key,
                                np.asarray(tau, np.float32),
                            )
                        # No sync here: the StepTimer queues the loss
                        # scalars device-side and bounds the interval with
                        # ONE block at the log-interval flush.
                        train_timer.pend(
                            agent_state["world_model"],
                            train_metrics if keep_train_metrics else None,
                        )
                        dispatch_throttle.add(train_metrics)
                        cumulative_per_rank_gradient_steps += 1
                    placement.push(
                        {"world_model": agent_state["world_model"], "actor": agent_state["actor"]}
                    )
                    train_step_count += world_size
                # Sample on the main thread (no buffer race); stage the device
                # copies to overlap the next env-step phase.
                infeed.stage(per_rank_gradient_steps)


        # -------------------------------------------------------- logging
        should_log = cfg.metric.log_level > 0 and (
            policy_step - last_log >= cfg.metric.log_every or iter_num == total_iters
        )
        if should_log:
            # The interval's losses in ONE bounding block + ONE device->host
            # transfer (StepTimer.flush) — the coalesced pattern GL002 asks
            # for, now owned by telemetry.
            fetched_train_metrics = train_timer.flush()
            # Health sentinels inspect the same coalesced fetch — no extra
            # transfer; a nonfinite hit taints the run and escalates.
            health.observe(policy_step, fetched_train_metrics, telemetry=telemetry)
            if aggregator and not aggregator.disabled:
                for m in fetched_train_metrics:
                    for k, v in m.items():
                        if k in aggregator:
                            aggregator.update(k, v)
                # Collective when sync_on_compute is on: every rank joins;
                # only rank 0 (the only rank with a logger) writes.
                aggregator.log_and_reset(logger, policy_step)
            telemetry.log_counters(logger, policy_step)
        if should_log and logger is not None:
            if policy_step > 0:
                logger.log(
                    "Params/replay_ratio",
                    cumulative_per_rank_gradient_steps * world_size / policy_step,
                    policy_step,
                )
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

        # ----------------------------------------------------- checkpoint
        if health.allow_save() and (
            (cfg.checkpoint.every > 0 and policy_step - last_checkpoint >= cfg.checkpoint.every)
            or ((iter_num == total_iters or guard.preempted) and cfg.checkpoint.save_last)
        ):
            last_checkpoint = policy_step
            ckpt_state = {
                "world_model": agent_state["world_model"],
                "actor_task": agent_state["actor"],
                "critic_task": agent_state["critic"],
                "target_critic_task": agent_state["target_critic"],
                "actor_exploration": actor_exploration_params,
                "world_optimizer": opt_states["world_model"],
                "actor_task_optimizer": opt_states["actor"],
                "critic_task_optimizer": opt_states["critic"],
                "moments": moments_state,
                "ratio": ratio.state_dict(),
                "iter_num": iter_num * world_size,
                "batch_size": cfg.algo.per_rank_batch_size * world_size,
                "last_log": last_log,
                "last_checkpoint": last_checkpoint,
            }
            if cfg.buffer.checkpoint:
                ckpt_state["rb"] = rb
            ckpt_path = os.path.join(log_dir, f"checkpoint/ckpt_{policy_step}_{rank}.ckpt")
            if runtime.is_global_zero:
                save_checkpoint(ckpt_path, ckpt_state, keep_last=cfg.checkpoint.keep_last)

        if guard.preempted:
            runtime.print(f"Preemption: exiting cleanly after final checkpoint at policy step {policy_step}")
            break
    infeed.close()
    pipeline.publish()
    envs.close()
    if runtime.is_global_zero and cfg.algo.run_test and not guard.preempted:
        test(agent, agent_state, runtime, cfg, log_dir, logger)

    guard.close()
    telemetry.close()
    if logger is not None:
        logger.close()
