"""SAC, decoupled player/trainer loop (reference: sheeprl/algos/sac/sac_decoupled.py:33-588).

TPU-native redesign, not a port. The reference splits player and trainers
across *processes*: rank 0 steps the envs and owns the replay buffer, ranks
1..N-1 form a DDP optimization group; `scatter_object_list` ships sampled
batches player->trainers and a flat-parameter broadcast ships actor weights
trainers->player every iteration.

Here both partitions live in ONE controller process over a partitioned device
set: device 0 is the *player device*, devices 1..N-1 form the *trainer mesh*.
The object-list collectives become device-to-device transfers:

- batches: host sample -> `device_put` sharded over the trainer mesh's data
  axis (the scatter),
- weights: `device_put(actor_params, player_device)` after each train call
  (the broadcast).

Dispatch is async: the controller enqueues the G-step train scan on the
trainer devices and immediately enqueues the actor-weight copy; the player's
next inference waits only on that copy, and host env stepping overlaps trainer
compute. The pipelining the reference builds out of processes and blocking
collectives falls out of XLA's asynchronous dispatch.
"""

from __future__ import annotations

import copy
import os
import time
import warnings
from typing import Any, Dict

import gymnasium as gym
import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import NamedSharding, PartitionSpec as P

from sheeprl_tpu.algos.sac.agent import build_agent
from sheeprl_tpu.algos.sac.sac import _make_optimizer, make_train_step
from sheeprl_tpu.algos.sac.utils import prepare_obs, test
from sheeprl_tpu.core.player import ParamMirror
from sheeprl_tpu.config.instantiate import instantiate
from sheeprl_tpu.core import fleet as fleet_lib
from sheeprl_tpu.core import mesh as mesh_lib
from sheeprl_tpu.core.mesh import DATA_AXIS, split_player_trainer
from sheeprl_tpu.data.buffers import ReplayBuffer
from sheeprl_tpu.core.runtime import DispatchThrottle
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
from sheeprl_tpu.utils.timer import timer
from sheeprl_tpu.utils.utils import Ratio, save_configs


@register_algorithm(decoupled=True)
def main(runtime, cfg: Dict[str, Any]):
    # The player/trainer split happens after the agent is built, so the
    # auto placement's AUTO_MAX_PARAM_BYTES guard sees the real actor size.
    player_mode = cfg.fabric.get("player_device", "auto") or "auto"
    rank = runtime.global_rank

    state_ckpt = None
    if cfg.checkpoint.resume_from:
        state_ckpt = load_checkpoint(cfg.checkpoint.resume_from)

    if len(cfg.algo.cnn_keys.encoder) > 0:
        warnings.warn("SAC algorithm cannot allow to use images as observations, the CNN keys will be ignored")
        cfg.algo.cnn_keys.encoder = []

    logger = get_logger(runtime, cfg)
    if logger is not None:
        logger.log_hyperparams(cfg.as_dict() if hasattr(cfg, "as_dict") else dict(cfg))
    log_dir = get_log_dir(runtime, cfg.root_dir, cfg.run_name, logger=logger)
    telemetry = runtime.telemetry.open(log_dir, rank_zero=runtime.is_global_zero, device=runtime.device)
    telemetry.set_run_info(algo="sac_decoupled", rank=rank)
    guard = runtime.resilience.guard(rank_zero=runtime.is_global_zero)
    health = runtime.health
    runtime.print(f"Log dir: {log_dir}")

    # ------------------------------------------------------------ environment
    # Fleet mode moves env stepping into supervised actor-replica processes
    # (core/fleet.py); the learner keeps one short-lived local vector env
    # purely as the space probe its agent/validation code keys off.
    use_fleet = fleet_lib.fleet_active(cfg)
    envs = make_vector_env(cfg, rank, log_dir)
    action_space = envs.single_action_space
    observation_space = envs.single_observation_space
    if not isinstance(action_space, gym.spaces.Box):
        raise ValueError("Only continuous action space is supported for the SAC agent")
    if not isinstance(observation_space, gym.spaces.Dict):
        raise RuntimeError(f"Unexpected observation type, should be of type Dict, got: {observation_space}")
    if len(cfg.algo.mlp_keys.encoder) == 0:
        raise RuntimeError("You should specify at least one MLP key for the encoder: `mlp_keys.encoder=[state]`")
    for k in cfg.algo.mlp_keys.encoder:
        if len(observation_space[k].shape) > 1:
            raise ValueError(
                "Only environments with vector-only observations are supported by the SAC agent. "
                f"The observation with key '{k}' has shape {observation_space[k].shape}. "
                f"Provided environment: {cfg.env.id}"
            )
    if cfg.metric.log_level > 0:
        runtime.print("Encoder MLP keys:", cfg.algo.mlp_keys.encoder)
    mlp_keys = list(cfg.algo.mlp_keys.encoder)

    fleet_sup = None
    if use_fleet:
        envs.close()  # the probe served its purpose; replicas own the envs
        fleet_sup = fleet_lib.FleetSupervisor.from_config(
            cfg,
            "sheeprl_tpu.algos.sac.fleet_actor:actor_loop",
            seed=int(cfg.seed),
            log_dir=log_dir,
        )
        fleet_sup.start()
        runtime.print(
            f"Fleet: {fleet_sup.replicas} actor replica(s), quorum {int(cfg.fleet.quorum)}"
        )

    # ------------------------------------------------------- agent + optimizers
    # Eager flax/optax init runs host-side (each eager dispatch pays a
    # host-device round trip); replicate() then moves the trees to the mesh.
    with runtime.host_init():
        agent, agent_state = build_agent(
            runtime, cfg, observation_space, action_space,
            state_ckpt["agent"] if state_ckpt is not None else None,
        )

        txs = {
            "qf": _make_optimizer(cfg.algo.critic.optimizer),
            "actor": _make_optimizer(cfg.algo.actor.optimizer),
            "alpha": _make_optimizer(cfg.algo.alpha.optimizer),
        }
        opt_states = {
            "qf": txs["qf"].init(agent_state["qfs"]),
            "actor": txs["actor"].init(agent_state["actor"]),
            "alpha": txs["alpha"].init(agent_state["log_alpha"]),
        }
        if state_ckpt is not None:
            for name, ckpt_key in (("qf", "qf_optimizer"), ("actor", "actor_optimizer"), ("alpha", "alpha_optimizer")):
                opt_states[name] = restore_opt_state(opt_states[name], state_ckpt[ckpt_key])

        # Trainer state lives replicated on the trainer mesh; the player keeps its
        # own committed copy of the actor params on the player device (the
        # "first weights" broadcast of the reference, sac_decoupled.py:227-230).
    # Split now that the player-visible actor exists: auto applies its size
    # guard (an oversized actor stays on-mesh rather than paying a packed
    # host transfer after every update).
    player_device, trainer_mesh = split_player_trainer(
        runtime.mesh, player_mode, params=agent_state["actor"]
    )
    n_trainers = int(trainer_mesh.shape[DATA_AXIS])
    runtime.print(f"Decoupled SAC: player on {player_device}, {n_trainers} trainer device(s)")
    # shard_wide_params == replicate when model_axis is 1; with a model
    # axis it shards wide dense stacks tensor-parallel over the trainers.
    # A resumed run prefers the checkpoint manifest's recorded per-leaf
    # shardings (utils/checkpoint.py): the layout intent of the saving mesh,
    # replayed against THIS mesh — the elastic-resume path that makes an
    # 8-device save restart bit-compatibly on 4 (or 1) devices.
    recorded = (
        load_recorded_shardings(cfg.checkpoint.resume_from)
        if cfg.checkpoint.resume_from
        else None
    )
    if recorded:
        def _wide(leaf):
            return mesh_lib.shard_wide_params(leaf, trainer_mesh)

        agent_state = place_with_recorded_shardings(
            agent_state, recorded, trainer_mesh, prefix="agent", default=_wide
        )
        opt_states = {
            name: place_with_recorded_shardings(
                opt_states[name], recorded, trainer_mesh, prefix=ckpt_key, default=_wide
            )
            for name, ckpt_key in (
                ("qf", "qf_optimizer"),
                ("actor", "actor_optimizer"),
                ("alpha", "alpha_optimizer"),
            )
        }
    else:
        agent_state = mesh_lib.shard_wide_params(agent_state, trainer_mesh)
        opt_states = mesh_lib.shard_wide_params(opt_states, trainer_mesh)
    # Per-shard goodput over the TRAINER partition (the player device is
    # accounted by its own fetch/infeed spans), plus the topology + layout
    # records behind `python -m sheeprl_tpu.telemetry mesh`.
    telemetry.set_mesh(trainer_mesh)
    telemetry.record_param_layouts(agent_state)
    # The trainer->player weight broadcast as a packed single-transfer mirror
    # (core/player.py): honors fabric.player_sync — "fresh" makes the next
    # inference wait for the post-update actor, "async" serves the newest
    # snapshot whose transfer finished (the reference's non-blocking
    # broadcast, sac_decoupled.py:260-263).
    actor_mirror = ParamMirror(
        # Same-silicon passthrough ONLY when the trainer partition is that
        # single device: with more trainer devices the params are replicated
        # over a multi-device mesh and the player needs its own committed
        # copy (a shared multi-device array clashes with the player's
        # single-device inputs inside jit).
        None
        if trainer_mesh.devices.size == 1 and player_device == trainer_mesh.devices.flat[0]
        else player_device,
        sync=str(cfg.fabric.get("player_sync", "fresh") or "fresh"),
    )
    actor_mirror.push(agent_state["actor"])

    if runtime.is_global_zero:
        save_configs(cfg, log_dir)

    aggregator = None
    if not MetricAggregator.disabled:
        aggregator: MetricAggregator = instantiate(cfg.metric.aggregator)

    # ------------------------------------------------------------ replay buffer
    buffer_size = cfg.buffer.size // int(cfg.env.num_envs) if not cfg.dry_run else 1
    rb = ReplayBuffer(
        buffer_size,
        cfg.env.num_envs,
        memmap=cfg.buffer.memmap,
        memmap_dir=os.path.join(log_dir, "memmap_buffer", f"rank_{rank}"),
    )
    if state_ckpt is not None and cfg.buffer.checkpoint and state_ckpt.get("rb") is not None:
        rb = state_ckpt["rb"]

    # ------------------------------------------------------------ counters
    last_train = 0
    train_step_count = 0
    start_iter = state_ckpt["iter_num"] + 1 if state_ckpt is not None else 1
    policy_step = state_ckpt["iter_num"] * cfg.env.num_envs if state_ckpt is not None else 0
    last_log = state_ckpt["last_log"] if state_ckpt is not None else 0
    last_checkpoint = state_ckpt["last_checkpoint"] if state_ckpt is not None else 0
    policy_steps_per_iter = int(cfg.env.num_envs)
    total_iters = int(cfg.algo.total_steps // policy_steps_per_iter) if not cfg.dry_run else 1
    learning_starts = cfg.algo.learning_starts // policy_steps_per_iter if not cfg.dry_run else 0
    prefill_steps = learning_starts - int(learning_starts > 0)
    if state_ckpt is not None:
        cfg.algo.per_rank_batch_size = state_ckpt["batch_size"] // n_trainers
        if not cfg.buffer.checkpoint:
            learning_starts += start_iter
            prefill_steps += start_iter

    ratio = Ratio(cfg.algo.replay_ratio, pretrain_steps=cfg.algo.per_rank_pretrain_steps)
    if state_ckpt is not None:
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

    # The same jitted G-step scan as coupled SAC, compiled over the trainer
    # mesh only (its `data` axis is the trainer partition).
    train_fn = make_train_step(agent, txs, cfg, trainer_mesh)
    def _player(p, o, k):
        next_k, sub = jax.random.split(k)
        return agent.get_actions(p, o, sub, greedy=False), next_k

    player_fn = jax.jit(_player)
    batch_sharding = NamedSharding(trainer_mesh, P(None, DATA_AXIS))
    target_freq_iters = cfg.algo.critic.target_network_frequency // policy_steps_per_iter + 1

    rollout_key, train_key = jax.random.split(jax.random.fold_in(runtime.root_key, rank))
    rollout_key = jax.device_put(rollout_key, player_device)

    step_data = {}
    obs = envs.reset(seed=cfg.seed)[0] if not use_fleet else None
    fleet_sync_every = max(1, int(cfg.fleet.param_sync_every)) if use_fleet else 0

    cumulative_per_rank_gradient_steps = 0
    # Bound async in-flight train dispatches (core/runtime.py: an
    # unbounded queue pins every pending call's sampled batch on host).
    dispatch_throttle = DispatchThrottle()
    # Coalesced loss fetch + interval bounding (telemetry/step_timer.py):
    # ONE block_until_ready + ONE device_get per log interval.
    train_timer = telemetry.step_timer("train", timer_key="Time/train_time")
    perf = telemetry.perf
    keep_train_metrics = (aggregator is not None and not aggregator.disabled) or health.enabled
    for iter_num in range(start_iter, total_iters + 1):
        policy_step += policy_steps_per_iter
        telemetry.advance(policy_step)
        guard.advance(policy_step)

        if use_fleet:
            # The replicas step the envs; the learner's "env interaction" is
            # one admitted shipment per iteration. Supervision (liveness,
            # restarts, quorum) runs inside recv — the bounded timeout keeps
            # the preemption flag honored even when the whole fleet is quiet.
            with timer("Time/env_interaction_time"), perf.infeed():
                shipment = None
                # A preempted learner still ingests THIS iteration's shipment
                # when the fleet can provide one (bounded grace): the in-place
                # signal handler semantics of the non-fleet path, where the
                # interrupted iteration completes before the final save. That
                # keeps the preempt checkpoint's iter_num/replay position
                # identical to the no-fault run — resume-to-parity, not
                # resume-minus-one-shipment.
                grace = time.monotonic() + 5.0
                while shipment is None:
                    if guard.preempted and (
                        fleet_sup.live_replicas == 0 or time.monotonic() > grace
                    ):
                        break
                    shipment = fleet_sup.recv(timeout=0.5)
            if shipment is not None:
                rb.add(shipment.rows, validate_args=cfg.buffer.validate_args)
                if cfg.metric.log_level > 0:
                    for ep_rew, ep_len in shipment.episodes:
                        if aggregator and not aggregator.disabled:
                            aggregator.update("Rewards/rew_avg", ep_rew)
                            aggregator.update("Game/ep_len_avg", ep_len)
                        runtime.print(
                            f"Rank-0: policy_step={policy_step}, "
                            f"reward_replica_{shipment.replica}={ep_rew}"
                        )
        else:
            with timer("Time/env_interaction_time"), perf.infeed():
                if iter_num <= learning_starts:
                    actions = envs.action_space.sample()
                else:
                    with jax.default_device(player_device):
                        np_obs = prepare_obs(obs, mlp_keys=mlp_keys, num_envs=cfg.env.num_envs)
                        actions_j, rollout_key = player_fn(actor_mirror.get(), np_obs, rollout_key)
                    # Structural per-step sync (actions feed env.step): accounted
                    # through the telemetry fetch.
                    actions = telemetry.fetch(actions_j, label="player_actions")
                next_obs, rewards, terminated, truncated, infos = envs.step(
                    actions.reshape(envs.action_space.shape)
                )
                rewards = rewards.reshape(cfg.env.num_envs, -1)

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
                done_mask = np.logical_or(terminated, truncated)
                for idx in np.nonzero(done_mask)[0]:
                    final = infos["final_obs"][idx]
                    if final is not None:
                        for k, v in final.items():
                            real_next_obs[k][idx] = v
            real_next_obs_cat = np.concatenate([real_next_obs[k] for k in mlp_keys], axis=-1).astype(np.float32)

            step_data["terminated"] = terminated.reshape(1, cfg.env.num_envs, -1).astype(np.uint8)
            step_data["truncated"] = truncated.reshape(1, cfg.env.num_envs, -1).astype(np.uint8)
            step_data["actions"] = actions.reshape(1, cfg.env.num_envs, -1)
            step_data["observations"] = np.concatenate([obs[k] for k in mlp_keys], axis=-1).astype(np.float32)[np.newaxis]
            if not cfg.buffer.sample_next_obs:
                step_data["next_observations"] = real_next_obs_cat[np.newaxis]
            step_data["rewards"] = rewards[np.newaxis].astype(np.float32)
            rb.add(step_data, validate_args=cfg.buffer.validate_args)

            obs = next_obs

        # ------------------------------------------------- trainer partition
        if iter_num >= learning_starts and not (use_fleet and shipment is None):
            ratio_steps = policy_step - prefill_steps * policy_steps_per_iter
            per_rank_gradient_steps = ratio(ratio_steps / n_trainers)
            if per_rank_gradient_steps > 0:
                # The scatter: one host sample covering every trainer's share,
                # placed directly sharded over the trainer mesh (the reference
                # chunks + scatter_object_list, sac_decoupled.py:243-257).
                global_batch = cfg.algo.per_rank_batch_size * n_trainers
                sample = rb.sample_tensors(
                    batch_size=per_rank_gradient_steps * global_batch,
                    sample_next_obs=cfg.buffer.sample_next_obs,
                )
                # Accounted scatter (core/mesh.put_sharded): the H2D bytes
                # land on the transfer ledger, and a layout mismatch would
                # surface as transfer/reshard_events instead of hiding.
                data = mesh_lib.put_sharded(
                    {
                        k: np.asarray(v)
                        .astype(np.float32)
                        .reshape(per_rank_gradient_steps, global_batch, *np.asarray(v).shape[2:])
                        for k, v in sample.items()
                    },
                    batch_sharding,
                )
                with timer("Time/train_time"):
                    do_ema = iter_num % target_freq_iters == 0
                    tau_arr = np.asarray(agent.tau if do_ema else 0.0, np.float32)
                    # Goodput accounting BEFORE the dispatch: arg shape specs
                    # must be captured while the buffers are alive (donated).
                    perf.note(
                        f"train/g{per_rank_gradient_steps}", train_fn,
                        (agent_state, opt_states, data, train_key, tau_arr),
                        steps=per_rank_gradient_steps,
                    )
                    with train_timer.step():
                        agent_state, opt_states, train_metrics, train_key = train_fn(
                            agent_state,
                            opt_states,
                            data,
                            train_key,
                            tau_arr,
                        )
                    # No sync here: the StepTimer queues the loss scalars
                    # device-side and bounds the interval with ONE block at
                    # the log-interval flush.
                    train_timer.pend(
                        agent_state["actor"], train_metrics if keep_train_metrics else None
                    )
                    dispatch_throttle.add(train_metrics)
                    # The broadcast back: enqueue the packed weight copy and
                    # return to env stepping.
                    actor_mirror.push(agent_state["actor"])
                    cumulative_per_rank_gradient_steps += per_rank_gradient_steps
                train_step_count += n_trainers
                if use_fleet and iter_num % fleet_sync_every == 0:
                    # Cross-process weight broadcast: one host pull, fanned
                    # out by the per-replica pump threads (a dead replica's
                    # pump dies with its pipe instead of blocking this call).
                    # copy=True is load-bearing: np.asarray of a CPU jax
                    # array can be a zero-copy view, and the pump threads
                    # pickle asynchronously while the next train step DONATES
                    # these buffers.
                    fleet_sup.push_params(
                        jax.tree_util.tree_map(
                            lambda a: np.array(a, copy=True), agent_state["actor"]
                        ),
                        version=iter_num,
                    )

        # ------------------------------------------------------------ logging
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
                    aggregator.update("Loss/value_loss", tm["value_loss"])
                    aggregator.update("Loss/policy_loss", tm["policy_loss"])
                    aggregator.update("Loss/alpha_loss", tm["alpha_loss"])
                # Collective when sync_on_compute is on: every rank joins;
                # only rank 0 (the only rank with a logger) writes.
                aggregator.log_and_reset(logger, policy_step)
            telemetry.log_counters(logger, policy_step)
        if should_log and logger is not None:
            if policy_step > 0:
                logger.log(
                    "Params/replay_ratio",
                    cumulative_per_rank_gradient_steps * n_trainers / policy_step,
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
                        ((policy_step - last_log) * cfg.env.action_repeat)
                        / timer_metrics["Time/env_interaction_time"],
                        policy_step,
                    )
                timer.reset()
        if should_log:
            last_log = policy_step
            last_train = train_step_count

        # --------------------------------------------------------- checkpoint
        if health.allow_save() and (
            (
                iter_num >= learning_starts
                and cfg.checkpoint.every > 0
                and policy_step - last_checkpoint >= cfg.checkpoint.every
            )
            or ((iter_num == total_iters or guard.preempted) and cfg.checkpoint.save_last)
        ):
            if guard.preempted and use_fleet:
                # Whole-fleet drain BEFORE the final save: replicas get stop,
                # their byes are collected, stragglers' in-flight rows are
                # accounted dropped — then the learner commits and exits.
                fleet_sup.drain_and_stop()
            last_checkpoint = policy_step
            ckpt_state = {
                "agent": agent_state,
                "qf_optimizer": opt_states["qf"],
                "actor_optimizer": opt_states["actor"],
                "alpha_optimizer": opt_states["alpha"],
                "ratio": ratio.state_dict(),
                "iter_num": iter_num,
                "batch_size": cfg.algo.per_rank_batch_size * n_trainers,
                "last_log": last_log,
                "last_checkpoint": last_checkpoint,
            }
            saved_tail = None
            tail = (rb._pos - 1) % rb.buffer_size
            if cfg.buffer.checkpoint:
                # Buffer-tail consistency trick, as in coupled SAC
                # (reference: callback.py:87-142).
                if rb["truncated"] is not None:
                    saved_tail = np.asarray(rb["truncated"][tail, :]).copy()
                    rb["truncated"][tail, :] = 1
                ckpt_state["rb"] = rb
            ckpt_path = os.path.join(log_dir, f"checkpoint/ckpt_{policy_step}_{rank}.ckpt")
            if runtime.is_global_zero:
                save_checkpoint(ckpt_path, ckpt_state, keep_last=cfg.checkpoint.keep_last)
            if saved_tail is not None:
                rb["truncated"][tail, :] = saved_tail

        if guard.preempted:
            runtime.print(f"Preemption: exiting cleanly after final checkpoint at policy step {policy_step}")
            break
    if use_fleet:
        fleet_sup.close()  # idempotent after a preemption drain
    else:
        envs.close()
    if runtime.is_global_zero and cfg.algo.run_test and not guard.preempted:
        # flush: serve the final trained weights, not a stale async snapshot
        test(agent, {"actor": actor_mirror.flush()}, runtime, cfg, log_dir, logger)

    guard.close()
    telemetry.close()
    if logger is not None:
        logger.close()
