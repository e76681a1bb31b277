"""SAC-AE training loop (reference: sheeprl/algos/sac_ae/sac_ae.py:35-502).

SAC from pixels with a regularized autoencoder (https://arxiv.org/abs/1910.01741):

- critic update differentiates BOTH the shared encoder and the Q ensemble
  (one param group, one optimizer — the reference puts the encoder inside
  SACAECritic for the same effect);
- actor/alpha updates see stop-gradient encoder features (the reference's
  detach_encoder_features) on their own cadence;
- the decoder reconstructs bit-reduced, dequantized observations from the
  latent with an L2 latent penalty, updating encoder + decoder;
- target critic AND target encoder EMA with separate taus on the critic's
  cadence.

Update cadences are static python flags per gradient step, so each of the
four (actor x ema x decoder) combinations jit-specializes once.
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

from sheeprl_tpu.algos.sac.loss import critic_loss, entropy_loss, policy_loss
from sheeprl_tpu.algos.sac.sac import _make_optimizer
from sheeprl_tpu.algos.sac_ae.agent import SACAEAgent, build_agent
from sheeprl_tpu.algos.sac_ae.utils import normalize_pixels, prepare_obs, preprocess_obs, test
from sheeprl_tpu.config.instantiate import instantiate
from sheeprl_tpu.core.interact import InteractionPipeline
from sheeprl_tpu.core.mesh import DATA_AXIS
from sheeprl_tpu.core.player import PlayerPlacement
from sheeprl_tpu.data.buffers import ReplayBuffer
from sheeprl_tpu.core.runtime import DispatchThrottle
from sheeprl_tpu.registry import register_algorithm
from sheeprl_tpu.utils.checkpoint import load_checkpoint, restore_opt_state, save_checkpoint
from sheeprl_tpu.utils.env import make_vector_env
from sheeprl_tpu.utils.logger import get_log_dir, get_logger
from sheeprl_tpu.utils.metric import MetricAggregator
from sheeprl_tpu.utils.timer import timer
from sheeprl_tpu.utils.utils import Ratio, save_configs


def make_train_step(agent: SACAEAgent, txs: Dict[str, optax.GradientTransformation], cfg: Dict[str, Any], mesh):
    """Build the jitted per-minibatch update, specialized on cadence flags."""
    from jax.sharding import NamedSharding, PartitionSpec as P

    gamma = float(cfg.algo.gamma)
    l2_lambda = float(cfg.algo.decoder.l2_lambda)
    cnn_keys = list(cfg.algo.cnn_keys.encoder)
    mlp_keys = list(cfg.algo.mlp_keys.encoder)
    cnn_dec_keys = list(cfg.algo.cnn_keys.decoder)
    mlp_dec_keys = list(cfg.algo.mlp_keys.decoder)
    flat_sharding = NamedSharding(mesh, P(DATA_AXIS))

    @partial(jax.jit, donate_argnums=(0, 1), static_argnums=(4, 5, 6))
    def train_step(state, opt_states, batch, key, update_actor, update_ema, update_decoder):
        next_key, key = jax.random.split(key)
        batch = jax.lax.with_sharding_constraint(batch, {k: flat_sharding for k in batch})
        obs = normalize_pixels({k: batch[k] for k in cnn_keys + mlp_keys}, cnn_keys)
        next_obs = normalize_pixels(
            {k: batch[f"next_{k}"] for k in cnn_keys + mlp_keys}, cnn_keys
        )
        k_target, k_actor, k_rec = jax.random.split(key, 3)
        sg = jax.lax.stop_gradient

        # ------------------------- critic update (encoder + Q ensemble)
        next_target = agent.next_target_q_values(
            state, next_obs, batch["rewards"], batch["terminated"], gamma, k_target
        )

        def qf_loss_fn(params):
            features = agent.encode(params["encoder"], obs)
            qf_values = agent.q_values(params["qfs"], features, batch["actions"])
            return critic_loss(qf_values, next_target, agent.num_critics)

        qf_group = {"encoder": state["encoder"], "qfs": state["qfs"]}
        qf_l, qf_grads = jax.value_and_grad(qf_loss_fn)(qf_group)
        qf_updates, qf_opt = txs["qf"].update(qf_grads, opt_states["qf"], qf_group)
        qf_group = optax.apply_updates(qf_group, qf_updates)
        state["encoder"] = qf_group["encoder"]
        state["qfs"] = qf_group["qfs"]
        opt_states = dict(opt_states, qf=qf_opt)

        # --------------------------------------- target EMAs (own taus)
        if update_ema:
            state["qfs_target"] = jax.tree_util.tree_map(
                lambda p, tp: agent.tau * p + (1 - agent.tau) * tp,
                state["qfs"], state["qfs_target"],
            )
            state["encoder_target"] = jax.tree_util.tree_map(
                lambda p, tp: agent.encoder_tau * p + (1 - agent.encoder_tau) * tp,
                state["encoder"], state["encoder_target"],
            )

        metrics = {"value_loss": qf_l, "policy_loss": jnp.zeros(()), "alpha_loss": jnp.zeros(()),
                   "reconstruction_loss": jnp.zeros(())}

        # ------------------------- actor + alpha (frozen encoder features)
        if update_actor:
            features = sg(agent.encode(state["encoder"], obs))
            alpha = jnp.exp(state["log_alpha"])

            def actor_loss_fn(actor_params):
                actions, logprobs = agent.actions_and_log_probs(actor_params, features, k_actor)
                qf_values = agent.q_values(state["qfs"], features, actions)
                min_qf = jnp.min(qf_values, axis=-1, keepdims=True)
                return policy_loss(alpha, logprobs, min_qf), logprobs

            (actor_l, logprobs), actor_grads = jax.value_and_grad(actor_loss_fn, has_aux=True)(state["actor"])
            actor_updates, actor_opt = txs["actor"].update(actor_grads, opt_states["actor"], state["actor"])
            state["actor"] = optax.apply_updates(state["actor"], actor_updates)

            def alpha_loss_fn(log_alpha):
                return entropy_loss(log_alpha, logprobs, agent.target_entropy)

            alpha_l, alpha_grads = jax.value_and_grad(alpha_loss_fn)(state["log_alpha"])
            alpha_updates, alpha_opt = txs["alpha"].update(
                alpha_grads, opt_states["alpha"], state["log_alpha"]
            )
            state["log_alpha"] = optax.apply_updates(state["log_alpha"], alpha_updates)
            opt_states = dict(opt_states, actor=actor_opt, alpha=alpha_opt)
            metrics["policy_loss"] = actor_l
            metrics["alpha_loss"] = alpha_l

        # ----------------------------- autoencoder (encoder + decoder)
        if update_decoder:
            def rec_loss_fn(params):
                hidden = agent.encode(params["encoder"], obs)
                reconstruction = agent.decode(params["decoder"], hidden)
                loss = 0.0
                for k in cnn_dec_keys + mlp_dec_keys:
                    target = (
                        preprocess_obs(batch[k], k_rec, bits=5)
                        if k in cnn_dec_keys
                        else batch[k]
                    )
                    rec = reconstruction[k]
                    if k in mlp_dec_keys:
                        target = target.reshape(rec.shape)
                    loss += ((target - rec) ** 2).mean()
                # Latent L2 penalty applied ONCE (documented divergence: the
                # reference adds it inside the per-key loop, sac_ae.py:105-111,
                # scaling the regularizer with the number of decoder keys;
                # identical for the usual single-key configs).
                return loss + l2_lambda * 0.5 * (hidden**2).sum(-1).mean()

            rec_group = {"encoder": state["encoder"], "decoder": state["decoder"]}
            rec_l, rec_grads = jax.value_and_grad(rec_loss_fn)(rec_group)
            enc_updates, enc_opt = txs["encoder"].update(
                rec_grads["encoder"], opt_states["encoder"], state["encoder"]
            )
            state["encoder"] = optax.apply_updates(state["encoder"], enc_updates)
            dec_updates, dec_opt = txs["decoder"].update(
                rec_grads["decoder"], opt_states["decoder"], state["decoder"]
            )
            state["decoder"] = optax.apply_updates(state["decoder"], dec_updates)
            opt_states = dict(opt_states, encoder=enc_opt, decoder=dec_opt)
            metrics["reconstruction_loss"] = rec_l

        return state, opt_states, metrics, next_key

    return train_step


@register_algorithm()
def main(runtime, cfg: Dict[str, Any]):
    mesh = runtime.mesh
    rank = runtime.global_rank
    world_size = jax.process_count()

    if "minedojo" in str(cfg.env.wrapper.get("_target_", "")).lower():
        raise ValueError(
            "MineDojo is not currently supported by SAC-AE agent, since it does not take "
            "into consideration the action masks provided by the environment, but needed "
            "in order to play correctly the game. "
            "As an alternative you can use one of the Dreamers' agents."
        )

    state_ckpt = None
    if cfg.checkpoint.resume_from:
        state_ckpt = load_checkpoint(cfg.checkpoint.resume_from)

    # These arguments cannot be changed (reference: sac_ae.py:137-138)
    cfg.env.screen_size = 64

    logger = get_logger(runtime, cfg)
    if logger is not None:
        logger.log_hyperparams(cfg.as_dict() if hasattr(cfg, "as_dict") else dict(cfg))
    log_dir = get_log_dir(runtime, cfg.root_dir, cfg.run_name, logger=logger)
    telemetry = runtime.telemetry.open(log_dir, rank_zero=runtime.is_global_zero, device=runtime.device)
    guard = runtime.resilience.guard(rank_zero=runtime.is_global_zero)
    health = runtime.health
    runtime.print(f"Log dir: {log_dir}")

    envs = make_vector_env(cfg, rank, log_dir)
    action_space = envs.single_action_space
    observation_space = envs.single_observation_space
    if not isinstance(action_space, gym.spaces.Box):
        raise RuntimeError(
            f"Unexpected action space, should be of type continuous (of type Box), got: {action_space}"
        )
    if not isinstance(observation_space, gym.spaces.Dict):
        raise RuntimeError(f"Unexpected observation type, should be of type Dict, got: {observation_space}")
    if (
        len(set(cfg.algo.cnn_keys.encoder).intersection(set(cfg.algo.cnn_keys.decoder))) == 0
        and len(set(cfg.algo.mlp_keys.encoder).intersection(set(cfg.algo.mlp_keys.decoder))) == 0
    ):
        raise RuntimeError("The CNN keys or the MLP keys of the encoder and decoder must not be disjoint")
    if len(set(cfg.algo.cnn_keys.decoder) - set(cfg.algo.cnn_keys.encoder)) > 0:
        raise RuntimeError(
            "The CNN keys of the decoder must be contained in the encoder ones, "
            f"got: decoder = {cfg.algo.cnn_keys.decoder}, encoder = {cfg.algo.cnn_keys.encoder}"
        )
    if len(set(cfg.algo.mlp_keys.decoder) - set(cfg.algo.mlp_keys.encoder)) > 0:
        raise RuntimeError(
            "The MLP keys of the decoder must be contained in the encoder ones, "
            f"got: decoder = {cfg.algo.mlp_keys.decoder}, encoder = {cfg.algo.mlp_keys.encoder}"
        )
    if cfg.metric.log_level > 0:
        runtime.print("Encoder CNN keys:", cfg.algo.cnn_keys.encoder)
        runtime.print("Encoder MLP keys:", cfg.algo.mlp_keys.encoder)
    cnn_keys = list(cfg.algo.cnn_keys.encoder)
    mlp_keys = list(cfg.algo.mlp_keys.encoder)
    obs_keys = cnn_keys + mlp_keys

    # Eager flax/optax init runs host-side (each eager dispatch pays a
    # host-device round trip); the finished trees then move to the mesh.
    with runtime.host_init():
        agent, agent_state = build_agent(
            runtime, cfg, observation_space, action_space,
            state_ckpt["agent"] if state_ckpt is not None else None,
        )

        txs = {
            "qf": _make_optimizer(cfg.algo.critic.optimizer),
            "actor": _make_optimizer(cfg.algo.actor.optimizer),
            "alpha": _make_optimizer(cfg.algo.alpha.optimizer),
            "encoder": _make_optimizer(cfg.algo.encoder.optimizer),
            "decoder": _make_optimizer(cfg.algo.decoder.optimizer),
        }
        opt_states = {
            "qf": txs["qf"].init({"encoder": agent_state["encoder"], "qfs": agent_state["qfs"]}),
            "actor": txs["actor"].init(agent_state["actor"]),
            "alpha": txs["alpha"].init(agent_state["log_alpha"]),
            "encoder": txs["encoder"].init(agent_state["encoder"]),
            "decoder": txs["decoder"].init(agent_state["decoder"]),
        }
        if state_ckpt is not None:
            for name, ckpt_key in (
                ("qf", "qf_optimizer"),
                ("actor", "actor_optimizer"),
                ("alpha", "alpha_optimizer"),
                ("encoder", "encoder_optimizer"),
                ("decoder", "decoder_optimizer"),
            ):
                opt_states[name] = restore_opt_state(opt_states[name], state_ckpt[ckpt_key])
    agent_state = runtime.shard_params(agent_state)
    opt_states = runtime.shard_params(opt_states)

    if runtime.is_global_zero:
        save_configs(cfg, log_dir)

    aggregator = None
    if not MetricAggregator.disabled:
        aggregator: MetricAggregator = instantiate(cfg.metric.aggregator)

    buffer_size = cfg.buffer.size // int(cfg.env.num_envs * world_size) if not cfg.dry_run else 1
    rb = ReplayBuffer(
        buffer_size,
        cfg.env.num_envs,
        memmap=cfg.buffer.memmap,
        memmap_dir=os.path.join(log_dir, "memmap_buffer", f"rank_{rank}"),
        obs_keys=obs_keys,
    )
    if state_ckpt is not None and cfg.buffer.checkpoint and state_ckpt.get("rb") is not None:
        rb = state_ckpt["rb"]

    last_train = 0
    train_step_count = 0
    start_iter = (state_ckpt["iter_num"] // world_size) + 1 if state_ckpt is not None else 1
    policy_step = state_ckpt["iter_num"] * cfg.env.num_envs if state_ckpt is not None else 0
    last_log = state_ckpt["last_log"] if state_ckpt is not None else 0
    last_checkpoint = state_ckpt["last_checkpoint"] if state_ckpt is not None else 0
    policy_steps_per_iter = int(cfg.env.num_envs * world_size)
    total_iters = int(cfg.algo.total_steps // policy_steps_per_iter) if not cfg.dry_run else 1
    learning_starts = cfg.algo.learning_starts // policy_steps_per_iter if not cfg.dry_run else 0
    prefill_steps = learning_starts - int(learning_starts > 0)
    if state_ckpt is not None:
        cfg.algo.per_rank_batch_size = state_ckpt["batch_size"] // world_size
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

    def _player(s, o, k):
        # PRNG split + pixel normalization in-graph: ONE dispatch per step.
        next_k, sub = jax.random.split(k)
        return agent.get_actions(s, normalize_pixels(o, cnn_keys), sub, greedy=False), next_k

    player_fn = jax.jit(_player)
    train_fn = make_train_step(agent, txs, cfg, mesh)

    # Latency-aware player placement (core/player.py); off-policy: honors
    # fabric.player_sync=async. get_actions reads only encoder+actor, so
    # only that sub-tree is mirrored (critics/decoder never make the copy).
    def _player_view(state):
        return {"encoder": state["encoder"], "actor": state["actor"]}

    placement = PlayerPlacement.resolve(
        cfg, mesh.devices.flat[0], params=_player_view(agent_state)
    )
    placement.push(_player_view(agent_state))

    rollout_key, train_key = jax.random.split(jax.random.fold_in(runtime.root_key, rank))
    rollout_key = placement.put(rollout_key)

    # Pipelined interaction (core/interact.py): per-slice policy dispatch +
    # async action fetch + double-buffered obs staging. slices=1/async off is
    # bit-identical to the serial loop. (No train overlap: sac_ae's train
    # step is not fused, so the dispatch itself is the host work.)
    pipeline = InteractionPipeline.from_config(cfg)
    pipeline.set_key(rollout_key)
    single_action_shape = envs.single_action_space.shape

    def _pipeline_policy(np_obs, state, key):
        with placement.ctx():
            actions_j, next_key = player_fn(placement.params(), np_obs, key)
        return actions_j, state, next_key

    def _prepare_slice(obs_slice, out=None):
        n = len(next(iter(obs_slice.values())))
        return prepare_obs(obs_slice, cnn_keys=cnn_keys, mlp_keys=mlp_keys, num_envs=n, out=out)

    def _to_env_actions(host_actions, n_envs):
        return host_actions.reshape((n_envs, *single_action_shape))

    step_data = {}
    obs = pipeline.stash_obs(envs.reset(seed=cfg.seed)[0])

    cumulative_per_rank_gradient_steps = 0
    # Bound async in-flight train dispatches (core/runtime.py: an
    # unbounded queue pins every pending call's sampled batch on host).
    dispatch_throttle = DispatchThrottle()
    # Coalesced loss fetch + interval bounding (telemetry/step_timer.py):
    # ONE block_until_ready + ONE device_get per log interval.
    train_timer = telemetry.step_timer("train", timer_key="Time/train_time")
    keep_train_metrics = (aggregator is not None and not aggregator.disabled) or health.enabled
    for iter_num in range(start_iter, total_iters + 1):
        policy_step += policy_steps_per_iter
        telemetry.advance(policy_step)
        guard.advance(policy_step)

        with timer("Time/env_interaction_time"):
            if iter_num <= learning_starts:
                actions = envs.action_space.sample()
                next_obs, rewards, terminated, truncated, infos = envs.step(
                    actions.reshape(envs.action_space.shape)
                )
                next_obs = pipeline.stash_obs(next_obs)
            else:
                res = pipeline.interact(
                    envs,
                    obs,
                    _pipeline_policy,
                    prepare=_prepare_slice,
                    to_env_actions=_to_env_actions,
                )
                actions, next_obs, rewards, terminated, truncated, infos = (
                    res.outputs,
                    res.obs,
                    res.rewards,
                    res.terminated,
                    res.truncated,
                    res.infos,
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

        for k in obs_keys:
            step_data[k] = obs[k][np.newaxis]
            if not cfg.buffer.sample_next_obs:
                step_data[f"next_{k}"] = real_next_obs[k][np.newaxis]
        step_data["terminated"] = terminated.reshape(1, cfg.env.num_envs, -1).astype(np.float32)
        step_data["truncated"] = truncated.reshape(1, cfg.env.num_envs, -1).astype(np.float32)
        step_data["actions"] = actions.reshape(1, cfg.env.num_envs, -1).astype(np.float32)
        step_data["rewards"] = rewards[np.newaxis].astype(np.float32)
        rb.add(step_data, validate_args=cfg.buffer.validate_args)

        obs = next_obs

        if iter_num >= learning_starts:
            per_rank_gradient_steps = ratio((policy_step - prefill_steps + policy_steps_per_iter) / world_size)
            if per_rank_gradient_steps > 0:
                sample = rb.sample_tensors(
                    batch_size=per_rank_gradient_steps * cfg.algo.per_rank_batch_size,
                    sample_next_obs=cfg.buffer.sample_next_obs,
                )
                data = {
                    k: np.asarray(v).reshape(
                        per_rank_gradient_steps, cfg.algo.per_rank_batch_size, *np.asarray(v).shape[2:]
                    )
                    for k, v in sample.items()
                }
                data = {
                    k: v if k.removeprefix("next_") in cnn_keys else v.astype(np.float32)
                    for k, v in data.items()
                }
                with timer("Time/train_time"):
                    for i in range(per_rank_gradient_steps):
                        batch = {k: jnp.asarray(v[i]) for k, v in data.items()}
                        update_actor = (
                            cumulative_per_rank_gradient_steps % cfg.algo.actor.per_rank_update_freq == 0
                        )
                        update_ema = (
                            cumulative_per_rank_gradient_steps
                            % cfg.algo.critic.per_rank_target_network_update_freq
                            == 0
                        )
                        update_decoder = (
                            cumulative_per_rank_gradient_steps % cfg.algo.decoder.per_rank_update_freq == 0
                        )
                        with train_timer.step():
                            agent_state, opt_states, train_metrics, train_key = train_fn(
                                agent_state, opt_states, batch, train_key, update_actor, update_ema, update_decoder
                            )
                        # No sync here: the StepTimer queues the loss scalars
                        # (plus the which-updates-ran flags, which device_get
                        # passes through) and bounds the interval with ONE
                        # block at the log-interval flush.
                        train_timer.pend(
                            agent_state["actor"],
                            (train_metrics, update_actor, update_decoder)
                            if keep_train_metrics
                            else None,
                        )
                        dispatch_throttle.add(train_metrics)
                        cumulative_per_rank_gradient_steps += 1
                    placement.push(_player_view(agent_state))
                train_step_count += world_size

        should_log = cfg.metric.log_level > 0 and (
            policy_step - last_log >= cfg.metric.log_every or iter_num == total_iters
        )
        if should_log:
            # ONE bounding block + ONE device->host transfer for the whole
            # interval (StepTimer.flush) — the coalesced GL002 pattern. Only
            # losses whose update actually ran are fed to the aggregator —
            # the skipped branches report placeholder zeros.
            fetched_train_metrics = train_timer.flush()
            # Health sentinels inspect the same coalesced fetch — no extra
            # transfer; a nonfinite hit taints the run and escalates.
            health.observe(policy_step, fetched_train_metrics, telemetry=telemetry)
            if aggregator and not aggregator.disabled:
                for m, did_actor, did_decoder in fetched_train_metrics:
                    aggregator.update("Loss/value_loss", m["value_loss"])
                    if did_actor:
                        aggregator.update("Loss/policy_loss", m["policy_loss"])
                        aggregator.update("Loss/alpha_loss", m["alpha_loss"])
                    if did_decoder:
                        aggregator.update("Loss/reconstruction_loss", m["reconstruction_loss"])
                # Collective when sync_on_compute is on: every rank joins;
                # only rank 0 (the only rank with a logger) writes.
                aggregator.log_and_reset(logger, policy_step)
            telemetry.log_counters(logger, policy_step)
        if should_log and logger is not None:
            logger.log(
                "Params/replay_ratio", cumulative_per_rank_gradient_steps * world_size / policy_step, policy_step
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

        if health.allow_save() and (
            (cfg.checkpoint.every > 0 and policy_step - last_checkpoint >= cfg.checkpoint.every)
            or ((iter_num == total_iters or guard.preempted) and cfg.checkpoint.save_last)
        ):
            last_checkpoint = policy_step
            ckpt_state = {
                "agent": agent_state,
                "qf_optimizer": opt_states["qf"],
                "actor_optimizer": opt_states["actor"],
                "alpha_optimizer": opt_states["alpha"],
                "encoder_optimizer": opt_states["encoder"],
                "decoder_optimizer": opt_states["decoder"],
                "ratio": ratio.state_dict(),
                "iter_num": iter_num * world_size,
                "batch_size": cfg.algo.per_rank_batch_size * world_size,
                "last_log": last_log,
                "last_checkpoint": last_checkpoint,
            }
            ckpt_path = os.path.join(log_dir, f"checkpoint/ckpt_{policy_step}_{rank}.ckpt")
            saved_tail = None
            tail = (rb._pos - 1) % rb.buffer_size
            if cfg.buffer.checkpoint:
                if rb["truncated"] is not None:
                    saved_tail = np.asarray(rb["truncated"][tail, :]).copy()
                    rb["truncated"][tail, :] = 1
                ckpt_state["rb"] = rb
            if runtime.is_global_zero:
                save_checkpoint(ckpt_path, ckpt_state, keep_last=cfg.checkpoint.keep_last)
            if saved_tail is not None:
                rb["truncated"][tail, :] = saved_tail

        if guard.preempted:
            runtime.print(f"Preemption: exiting cleanly after final checkpoint at policy step {policy_step}")
            break
    pipeline.publish()
    envs.close()
    if runtime.is_global_zero and cfg.algo.run_test and not guard.preempted:
        test(agent, agent_state, runtime, cfg, log_dir, logger)

    guard.close()
    telemetry.close()
    if logger is not None:
        logger.close()
