"""PPO, decoupled player/trainer loop (reference: sheeprl/algos/ppo/ppo_decoupled.py:33-670).

TPU-native redesign on the same plan as `sac_decoupled`: the reference's
rank-0 player + DDP trainer group, `scatter_object_list` batch shipping, and
flat-parameter broadcast become a device partition inside one controller
process — device 0 plays (policy inference, GAE bootstrap), devices 1..N-1
form the trainer mesh that runs the epochs x minibatches update scan.

Unlike off-policy SAC, PPO is inherently lockstep: the next rollout must use
the just-updated policy, so the player's first inference of iteration k+1
waits on the weight copy enqueued after iteration k's update — exactly the
synchronization the reference implements with a blocking broadcast, here a
device-to-device copy XLA overlaps with the host's env bookkeeping.
"""

from __future__ import annotations

import os
import warnings
from typing import Any, Dict

import gymnasium as gym
import jax
import jax.numpy as jnp
import numpy as np
import optax

from sheeprl_tpu.algos.ppo.agent import actions_metadata, build_agent
from sheeprl_tpu.algos.ppo.ppo import _current_lr, make_train_step
from sheeprl_tpu.core.player import ParamMirror
from sheeprl_tpu.algos.ppo.utils import prepare_obs, test
from sheeprl_tpu.config.instantiate import instantiate
from sheeprl_tpu.core import fleet as fleet_lib
from sheeprl_tpu.core import mesh as mesh_lib
from sheeprl_tpu.core.mesh import DATA_AXIS, split_player_trainer
from sheeprl_tpu.data.buffers import ReplayBuffer
from sheeprl_tpu.registry import register_algorithm
from sheeprl_tpu.utils.checkpoint import (
    load_checkpoint,
    load_recorded_shardings,
    place_with_recorded_shardings,
    restore_opt_state,
    save_checkpoint,
)
from sheeprl_tpu.utils.env import make_vector_env
from sheeprl_tpu.utils.logger import get_log_dir, get_logger
from sheeprl_tpu.utils.metric import MetricAggregator
from sheeprl_tpu.utils.ops import gae
from sheeprl_tpu.utils.timer import timer
from sheeprl_tpu.utils.utils import polynomial_decay, save_configs


@register_algorithm(decoupled=True)
def main(runtime, cfg: Dict[str, Any]):
    # The player/trainer split happens after the agent is built, so the
    # auto placement's AUTO_MAX_PARAM_BYTES guard sees the real agent size.
    player_mode = cfg.fabric.get("player_device", "auto") or "auto"
    rank = runtime.global_rank

    initial_ent_coef = float(cfg.algo.ent_coef)
    initial_clip_coef = float(cfg.algo.clip_coef)

    state = None
    if cfg.checkpoint.resume_from:
        state = load_checkpoint(cfg.checkpoint.resume_from)

    logger = get_logger(runtime, cfg)
    if logger is not None:
        logger.log_hyperparams(cfg.as_dict() if hasattr(cfg, "as_dict") else dict(cfg))
    log_dir = get_log_dir(runtime, cfg.root_dir, cfg.run_name, logger=logger)
    runtime.print(f"Log dir: {log_dir}")
    telemetry = runtime.telemetry.open(log_dir, rank_zero=runtime.is_global_zero, device=runtime.device)
    telemetry.set_run_info(algo="ppo_decoupled", rank=rank)
    guard = runtime.resilience.guard(rank_zero=runtime.is_global_zero)
    health = runtime.health

    # ----------------------------------------------------------------- envs
    # Fleet mode moves the rollout collection into supervised actor-replica
    # processes (core/fleet.py); the local vector env is then only the probe
    # the agent build and validation key off.
    use_fleet = fleet_lib.fleet_active(cfg)
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

    fleet_sup = None
    if use_fleet:
        envs.close()  # the probe served its purpose; replicas own the envs
        fleet_sup = fleet_lib.FleetSupervisor.from_config(
            cfg,
            "sheeprl_tpu.algos.ppo.fleet_actor:actor_loop",
            seed=int(cfg.seed),
            log_dir=log_dir,
        )
        fleet_sup.start()
        runtime.print(
            f"Fleet: {fleet_sup.replicas} actor replica(s), quorum {int(cfg.fleet.quorum)}"
        )

    # ---------------------------------------------------------------- agent
    # Eager flax/optax init runs host-side (each eager dispatch pays a
    # host-device round trip); replicate() then moves the trees to the mesh.
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

        # Trainer copy on the trainer mesh, player copy on the player device
        # (the reference's "first weights" broadcast, ppo_decoupled.py:124-127).
    # Split now that the player-visible params exist: auto applies its size
    # guard (an oversized agent stays on-mesh rather than paying a packed
    # host transfer after every update).
    player_device, trainer_mesh = split_player_trainer(runtime.mesh, player_mode, params=params)
    n_trainers = int(trainer_mesh.shape[DATA_AXIS])
    runtime.print(f"Decoupled PPO: player on {player_device}, {n_trainers} trainer device(s)")
    # shard_wide_params == replicate when model_axis is 1; with a model
    # axis it shards wide dense stacks tensor-parallel over the trainers.
    # A resumed run prefers the checkpoint manifest's recorded per-leaf
    # shardings replayed against THIS mesh (utils/checkpoint.py) — the
    # elastic-resume path: an 8-device save restarts bit-compatibly on 4.
    recorded = (
        load_recorded_shardings(cfg.checkpoint.resume_from)
        if cfg.checkpoint.resume_from
        else None
    )
    if recorded:
        def _wide(leaf):
            return mesh_lib.shard_wide_params(leaf, trainer_mesh)

        params = place_with_recorded_shardings(
            params, recorded, trainer_mesh, prefix="agent", default=_wide
        )
        opt_state = place_with_recorded_shardings(
            opt_state, recorded, trainer_mesh, prefix="optimizer", default=_wide
        )
    else:
        params = mesh_lib.shard_wide_params(params, trainer_mesh)
        opt_state = mesh_lib.shard_wide_params(opt_state, trainer_mesh)
    # Per-shard goodput over the TRAINER partition + the topology/layout
    # records behind `python -m sheeprl_tpu.telemetry mesh`.
    telemetry.set_mesh(trainer_mesh)
    telemetry.record_param_layouts(params)
    # Trainer->player weight broadcast as a packed single-transfer mirror
    # (core/player.py). On-policy: always fresh — the next rollout must see
    # the post-update weights, exactly like the reference's blocking
    # broadcast (ppo_decoupled.py:302).
    params_mirror = ParamMirror(
        # Same-silicon passthrough only for a single-device trainer partition
        # (see sac_decoupled.py: multi-device-replicated params can't be
        # shared with the player's single-device inputs inside jit).
        None
        if trainer_mesh.devices.size == 1 and player_device == trainer_mesh.devices.flat[0]
        else player_device,
        sync="fresh",
    )
    params_mirror.push(params)

    if runtime.is_global_zero:
        save_configs(cfg, log_dir)

    aggregator = None
    if not MetricAggregator.disabled:
        aggregator: MetricAggregator = instantiate(cfg.metric.aggregator)

    # --------------------------------------------------------------- buffer
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

    # ------------------------------------------------------------- counters
    last_train = 0
    train_step_count = 0
    start_iter = state["iter_num"] + 1 if state is not None else 1
    policy_steps_per_iter = int(cfg.env.num_envs * cfg.algo.rollout_steps)
    if use_fleet:
        # Each iteration gathers one rollout segment per replica.
        policy_steps_per_iter *= int(cfg.fleet.replicas)
    policy_step = state["iter_num"] * policy_steps_per_iter if state is not None else 0
    last_log = state["last_log"] if state is not None else 0
    last_checkpoint = state["last_checkpoint"] if state is not None else 0
    total_iters = cfg.algo.total_steps // policy_steps_per_iter if not cfg.dry_run else 1
    if state is not None:
        cfg.algo.per_rank_batch_size = state["batch_size"]

    rollout_size = int(cfg.algo.rollout_steps * cfg.env.num_envs)
    if rollout_size % int(cfg.algo.per_rank_batch_size) != 0:
        warnings.warn(
            f"rollout size ({rollout_size}) is not divisible by per_rank_batch_size "
            f"({cfg.algo.per_rank_batch_size}): static minibatch shapes require wrapping the "
            "index permutation, so a few samples will be used twice per epoch."
        )
    if rollout_size % n_trainers != 0:
        # Sharded device_put needs the batch dim evenly split over the trainer
        # mesh; fail upfront instead of after the first rollout.
        raise RuntimeError(
            f"The rollout size (rollout_steps*num_envs = {rollout_size}) must be divisible "
            f"by the number of trainer devices ({n_trainers}) so the batch can be sharded "
            "over the trainer mesh. Adjust env.num_envs / algo.rollout_steps / fabric.devices."
        )

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

    # ---------------------------------------------------------- jitted fns
    player_step_fn = jax.jit(agent.player_step)
    get_values_fn = jax.jit(agent.get_values)
    gae_fn = jax.jit(
        lambda rewards, values, dones, next_values: gae(
            rewards, values, dones, next_values, cfg.algo.gamma, cfg.algo.gae_lambda
        )
    )
    # fused_gae=False: decoupled keeps GAE on the PLAYER device (it owns
    # the rollout) and scatters the finished flat pool to the trainers.
    train_fn = make_train_step(agent, tx, cfg, trainer_mesh, fused_gae=False)
    batch_sharding = mesh_lib.batch_sharding(trainer_mesh)

    rollout_key, train_key = jax.random.split(jax.random.fold_in(runtime.root_key, rank))
    rollout_key = jax.device_put(rollout_key, player_device)

    # --------------------------------------------------------------- loop
    # Coalesced loss fetch + interval bounding (telemetry/step_timer.py):
    # ONE block_until_ready + ONE device_get per log interval.
    train_timer = telemetry.step_timer("train", timer_key="Time/train_time")
    perf = telemetry.perf
    keep_train_metrics = (aggregator is not None and not aggregator.disabled) or health.enabled
    step_data = {}
    if not use_fleet:
        next_obs = envs.reset(seed=cfg.seed)[0]
        for k in obs_keys:
            step_data[k] = next_obs[k][np.newaxis]

    for iter_num in range(start_iter, total_iters + 1):
        telemetry.advance(policy_step)
        guard.advance(policy_step)
        flat = None
        if use_fleet:
            with timer("Time/env_interaction_time"), perf.infeed():
                # Round k: broadcast version k, then gather one version-k
                # rollout segment per live replica — the lockstep the
                # in-process loop gets from the blocking mirror copy,
                # stretched across the process boundary. A replica that
                # dies mid-round shrinks the round (graceful degradation);
                # its supervised restart joins the next one.
                # copy=True: np.asarray of a CPU jax array can alias device
                # memory, and the pump threads pickle it off-thread while the
                # train step donates/overwrites those buffers.
                fleet_sup.push_params(
                    jax.tree_util.tree_map(lambda a: np.array(a, copy=True), params),
                    version=iter_num,
                )
                gathered = {}
                while not guard.preempted:
                    need = fleet_sup.live_replicas
                    if need == 0 or len(gathered) >= need:
                        break
                    shipment = fleet_sup.recv(timeout=0.5)
                    if shipment is None or shipment.kind != "rollout":
                        continue
                    if int(shipment.meta.get("version", -1)) != iter_num:
                        continue  # stale straggler from an earlier round
                    gathered[shipment.replica] = shipment
                    policy_step += shipment.env_steps
                    if cfg.metric.log_level > 0:
                        for ep_rew, ep_len in shipment.episodes:
                            if aggregator and "Rewards/rew_avg" in aggregator:
                                aggregator.update("Rewards/rew_avg", ep_rew)
                            if aggregator and "Game/ep_len_avg" in aggregator:
                                aggregator.update("Game/ep_len_avg", ep_len)
                            runtime.print(
                                f"Rank-0: policy_step={policy_step}, "
                                f"reward_replica_{shipment.replica}={ep_rew}"
                            )
            if gathered and not guard.preempted:
                # Concat along the env axis: per-replica [T, E, ...] rows
                # (returns/advantages already computed replica-side) become
                # one [T*E*live, ...] flat pool. The per-replica rollout
                # size is n_trainers-divisible (checked above), so any live
                # subset shards evenly; a changed live count recompiles
                # train_fn once per distinct count, bounded by replicas.
                def _flatten(arr):
                    arr = np.asarray(arr)
                    return arr.reshape(-1, *arr.shape[2:])

                keys = next(iter(gathered.values())).rows.keys()
                flat = mesh_lib.put_sharded(
                    {
                        k: np.concatenate([_flatten(s.rows[k]) for s in gathered.values()])
                        for k in keys
                    },
                    batch_sharding,
                )
        else:
            for _ in range(0, cfg.algo.rollout_steps):
                policy_step += cfg.env.num_envs

                with timer("Time/env_interaction_time"), perf.infeed():
                    with jax.default_device(player_device):
                        # prepare_obs is numpy; PRNG split + normalization run
                        # inside the jit — one dispatch, one host fetch per step.
                        np_obs = prepare_obs(next_obs, cnn_keys=cnn_keys, num_envs=cfg.env.num_envs)
                        *step_out, rollout_key = player_step_fn(
                            params_mirror.get(), np_obs, rollout_key
                        )
                    # Structural per-step sync (actions feed env.step): accounted
                    # through the telemetry fetch.
                    actions, real_actions_np, logprobs, values = telemetry.fetch(
                        step_out, label="player_actions"
                    )

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
                        with jax.default_device(player_device):
                            jnp_next = prepare_obs(real_next_obs, cnn_keys=cnn_keys, num_envs=len(truncated_envs))
                            vals = np.asarray(get_values_fn(params_mirror.get(), jnp_next))
                        rewards[truncated_envs] += cfg.algo.gamma * vals.reshape(rewards[truncated_envs].shape)
                    dones = np.logical_or(terminated, truncated).reshape(cfg.env.num_envs, -1).astype(np.uint8)
                    rewards = clip_rewards_fn(rewards).reshape(cfg.env.num_envs, -1).astype(np.float32)

                step_data["dones"] = dones[np.newaxis]
                step_data["values"] = values[np.newaxis]
                step_data["actions"] = actions[np.newaxis]
                step_data["logprobs"] = logprobs[np.newaxis]
                step_data["rewards"] = rewards[np.newaxis]
                if cfg.buffer.memmap:
                    step_data["returns"] = np.zeros_like(rewards, shape=(1, *rewards.shape))
                    step_data["advantages"] = np.zeros_like(rewards, shape=(1, *rewards.shape))

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

            # ----------------------------------- GAE (player device) + ship
            local_data = rb.to_tensor()
            with jax.default_device(player_device):
                jnp_obs = prepare_obs(next_obs, cnn_keys=cnn_keys, num_envs=cfg.env.num_envs)
                next_values = get_values_fn(params_mirror.get(), jnp_obs)
                returns, advantages = gae_fn(
                    jnp.asarray(np.asarray(local_data["rewards"], np.float32)),
                    jnp.asarray(np.asarray(local_data["values"], np.float32)),
                    jnp.asarray(np.asarray(local_data["dones"], np.float32)),
                    next_values,
                )
            local_data["returns"] = np.asarray(returns)
            local_data["advantages"] = np.asarray(advantages)

            # The scatter: flatten [T, N_envs] -> [T*N_envs] and place directly
            # sharded over the trainer mesh (the reference permutes + splits +
            # scatter_object_list, ppo_decoupled.py:295-300; the in-jit epoch
            # permutation already randomizes minibatch membership).
            # Accounted scatter (core/mesh.put_sharded): H2D bytes land on the
            # transfer ledger; a layout mismatch would tick reshard_events.
            flat = mesh_lib.put_sharded(
                {k: np.asarray(v).reshape(-1, *np.asarray(v).shape[2:]) for k, v in local_data.items()},
                batch_sharding,
            )

        if flat is not None:
            with timer("Time/train_time"):
                clip_arr = np.asarray(cfg.algo.clip_coef, np.float32)
                ent_arr = np.asarray(cfg.algo.ent_coef, np.float32)
                # Goodput accounting BEFORE the dispatch: arg shape specs must be
                # captured while the buffers are alive (the jit donates them).
                perf.note(
                    "train/update", train_fn,
                    (params, opt_state, flat, train_key, clip_arr, ent_arr),
                    steps=float(cfg.algo.update_epochs),
                )
                with train_timer.step():
                    params, opt_state, train_metrics, train_key = train_fn(
                        params,
                        opt_state,
                        flat,
                        train_key,
                        clip_arr,
                        ent_arr,
                    )
                # The broadcast back: the player's next rollout waits on this copy.
                params_mirror.push(params)
                # No sync here (PPO is lockstep anyway — the next rollout waits on
                # the mirror copy): the StepTimer queues the loss scalars and
                # bounds the interval with ONE block at the flush below.
                train_timer.pend(params, train_metrics if keep_train_metrics else None)
            train_step_count += n_trainers

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
                            ((policy_step - last_log) * cfg.env.action_repeat)
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
        if guard.preempted and use_fleet:
            # Drain before the final save: stop broadcasts, collect the byes,
            # account any rows still in flight as dropped — the checkpoint
            # then captures a quiesced fleet.
            fleet_sup.drain_and_stop()
        if health.allow_save() and (
            (cfg.checkpoint.every > 0 and policy_step - last_checkpoint >= cfg.checkpoint.every)
            or ((iter_num == total_iters or guard.preempted) and cfg.checkpoint.save_last)
        ):
            last_checkpoint = policy_step
            ckpt_state = {
                "agent": params,
                "optimizer": opt_state,
                "iter_num": iter_num,
                "batch_size": cfg.algo.per_rank_batch_size,
                "last_log": last_log,
                "last_checkpoint": last_checkpoint,
            }
            ckpt_path = os.path.join(log_dir, f"checkpoint/ckpt_{policy_step}_{rank}.ckpt")
            if runtime.is_global_zero:
                save_checkpoint(ckpt_path, ckpt_state, keep_last=cfg.checkpoint.keep_last)

        if guard.preempted:
            runtime.print(f"Preemption: exiting cleanly after final checkpoint at policy step {policy_step}")
            break
    if use_fleet:
        fleet_sup.close()
    else:
        envs.close()
    if runtime.is_global_zero and cfg.algo.run_test and not guard.preempted:
        test(agent, params_mirror.get(), runtime, cfg, log_dir, logger)

    guard.close()
    telemetry.close()
    if logger is not None:
        logger.close()
