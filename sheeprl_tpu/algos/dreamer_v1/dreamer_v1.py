"""DreamerV1 training loop (reference: sheeprl/algos/dreamer_v1/dreamer_v1.py).

Same TPU-first shape as the V2/V3 loops in this package: RSSM dynamic
learning as one `lax.scan`, imagination as a second scan, λ-targets as a
reverse scan, one jitted donated gradient step. DV1 specifics: continuous
Normal latents with free-nats KL (loss.py), no is_first reset handling
(reference RSSM.dynamic has none), actor loss = -mean(discount · λ-values)
(pure dynamics backprop, Eq. 7), critic without a target network, and
exploration noise added by the player (expl_amount=0.3 schedule,
dreamer_v1.py:582).
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

from sheeprl_tpu.algos.dreamer_v1.agent import DV1Agent, DV1WorldModel, build_agent
from sheeprl_tpu.algos.dreamer_v1.loss import actor_loss, critic_loss, reconstruction_loss
from sheeprl_tpu.algos.dreamer_v1.utils import (
    compute_lambda_values,
    exploration_amount,
    normalize_player_obs,
    prepare_obs,
    test,
)
from sheeprl_tpu.algos.dreamer_v2.dreamer_v2 import _make_optimizer
from sheeprl_tpu.algos.ppo.agent import actions_metadata
from sheeprl_tpu.config.instantiate import instantiate
from sheeprl_tpu.core.interact import InteractionPipeline
from sheeprl_tpu.core.mesh import DATA_AXIS
from sheeprl_tpu.core.player import PlayerPlacement
from sheeprl_tpu.data.infeed import ReplayInfeed
from sheeprl_tpu.data.buffers import EnvIndependentReplayBuffer, SequentialReplayBuffer
from sheeprl_tpu.core.runtime import DispatchThrottle
from sheeprl_tpu.registry import register_algorithm
from sheeprl_tpu.utils.checkpoint import load_checkpoint, restore_opt_state, save_checkpoint
from sheeprl_tpu.utils.distribution import BernoulliSafeMode, Independent, Normal
from sheeprl_tpu.utils.env import make_vector_env
from sheeprl_tpu.utils.logger import get_log_dir, get_logger
from sheeprl_tpu.utils.metric import MetricAggregator
from sheeprl_tpu.utils.timer import timer
from sheeprl_tpu.utils.utils import Ratio, save_configs


def make_train_step(agent: DV1Agent, txs: Dict[str, optax.GradientTransformation], cfg: Dict[str, Any], mesh):
    """Build the jitted single-gradient-step function over a [T, B] batch."""
    from jax.sharding import NamedSharding, PartitionSpec as P

    wm_cfg = cfg.algo.world_model
    cnn_keys = list(cfg.algo.cnn_keys.encoder)
    mlp_keys = list(cfg.algo.mlp_keys.encoder)
    stochastic_size = int(wm_cfg.stochastic_size)
    recurrent_state_size = int(wm_cfg.recurrent_model.recurrent_state_size)
    horizon = int(cfg.algo.horizon)
    gamma = float(cfg.algo.gamma)
    lmbda = float(cfg.algo.lmbda)
    use_continues = bool(wm_cfg.use_continues)
    spec = agent.actor_spec

    batch_sharding = NamedSharding(mesh, P(None, DATA_AXIS))

    def world_loss_fn(wm_params, data, batch_obs, keys):
        T, B = data["rewards"].shape[:2]
        embedded = agent.wm(wm_params, batch_obs, method="embed_obs")

        h0 = jnp.zeros((B, recurrent_state_size), embedded.dtype)
        z0 = jnp.zeros((B, stochastic_size), embedded.dtype)

        def step(carry, x):
            h, z = carry
            action, emb, key = x
            h, post, prior, post_ms, prior_ms = agent.world_model.apply(
                wm_params, z, h, action, emb, key, method=DV1WorldModel.dynamic
            )
            return (h, post), (h, post, post_ms[0], post_ms[1], prior_ms[0], prior_ms[1])

        (_, _), (recurrent_states, posteriors, post_means, post_stds, prior_means, prior_stds) = (
            jax.lax.scan(step, (h0, z0), (data["actions"], embedded, keys))
        )
        latent_states = jnp.concatenate([posteriors, recurrent_states], -1)

        reconstructed_obs = agent.wm(wm_params, latent_states, method="decode")
        qo = {
            k: Independent(Normal(v, jnp.ones_like(v)), len(v.shape[2:]))
            for k, v in reconstructed_obs.items()
        }
        qr = Independent(Normal(agent.wm(wm_params, latent_states, method="reward"), 1.0), 1)
        if use_continues:
            qc = Independent(
                BernoulliSafeMode(logits=agent.wm(wm_params, latent_states, method="continue_logits")), 1
            )
            continues_targets = (1 - data["terminated"]) * gamma
        else:
            qc = continues_targets = None

        posteriors_dist = Independent(Normal(post_means, post_stds), 1)
        priors_dist = Independent(Normal(prior_means, prior_stds), 1)
        rec_loss, kl, state_loss, reward_loss, observation_loss, continue_loss = reconstruction_loss(
            qo,
            batch_obs,
            qr,
            data["rewards"],
            posteriors_dist,
            priors_dist,
            wm_cfg.kl_free_nats,
            wm_cfg.kl_regularizer,
            qc,
            continues_targets,
            wm_cfg.continue_scale_factor,
        )
        aux = {
            "posteriors": posteriors,
            "recurrent_states": recurrent_states,
            "post_entropy": posteriors_dist.entropy().mean(),
            "prior_entropy": priors_dist.entropy().mean(),
            "kl": kl,
            "state_loss": state_loss,
            "reward_loss": reward_loss,
            "observation_loss": observation_loss,
            "continue_loss": continue_loss,
        }
        return rec_loss, aux

    @partial(jax.jit, donate_argnums=(0, 1))
    def train_step(state, opt_states, data, key):
        next_key, key = jax.random.split(key)
        T, B = data["rewards"].shape[:2]
        data = jax.lax.with_sharding_constraint(data, {k: batch_sharding for k in data})
        batch_obs = {k: data[k] / 255.0 - 0.5 for k in cnn_keys}
        batch_obs.update({k: data[k] for k in mlp_keys})

        k_dyn, k_img = jax.random.split(key)
        dyn_keys = jax.random.split(k_dyn, T)

        # ---------------------------------------------- world model update
        (rec_loss, aux), wm_grads = jax.value_and_grad(world_loss_fn, has_aux=True)(
            state["world_model"], data, batch_obs, dyn_keys
        )
        wm_updates, wm_opt = txs["world_model"].update(
            wm_grads, opt_states["world_model"], state["world_model"]
        )
        state["world_model"] = optax.apply_updates(state["world_model"], wm_updates)

        # --------------------------------------------- behaviour learning
        sg = jax.lax.stop_gradient
        imagined_prior0 = sg(aux["posteriors"]).reshape(-1, stochastic_size)
        recurrent_state0 = sg(aux["recurrent_states"]).reshape(-1, recurrent_state_size)
        latent0 = jnp.concatenate([imagined_prior0, recurrent_state0], -1)

        from sheeprl_tpu.algos.dreamer_v2.agent import dv2_actor_forward

        def actor_sample(actor_params, latent, k):
            pre = agent.actor.apply(actor_params, sg(latent))
            actions, _ = dv2_actor_forward(pre, spec, k, greedy=False)
            return jnp.concatenate(actions, -1)

        def imagine_loss_fn(actor_params):
            # H imagined states, no initial latent stored
            # (reference: dreamer_v1.py:232-251).
            def img_step(carry, k):
                prior, h, latent = carry
                k_act, k_wm = jax.random.split(k)
                actions = actor_sample(actor_params, latent, k_act)
                prior, h = agent.world_model.apply(
                    state["world_model"], prior, h, actions, k_wm, method=DV1WorldModel.imagination
                )
                latent = jnp.concatenate([prior, h], -1)
                return (prior, h, latent), latent

            img_keys = jax.random.split(k_img, horizon)
            _, imagined_trajectories = jax.lax.scan(
                img_step, (imagined_prior0, recurrent_state0, latent0), img_keys
            )  # [H, TB, L]

            predicted_values = agent.critic_value(state["critic"], imagined_trajectories)
            predicted_rewards = agent.wm(
                state["world_model"], imagined_trajectories, method="reward"
            )
            if use_continues:
                continues = jax.nn.sigmoid(
                    agent.wm(state["world_model"], imagined_trajectories, method="continue_logits")
                )
            else:
                continues = jnp.ones_like(sg(predicted_rewards)) * gamma

            lambda_values = compute_lambda_values(
                predicted_rewards, predicted_values, continues,
                last_values=predicted_values[-1], lmbda=lmbda,
            )  # [H-1, TB, 1]
            discount = sg(
                jnp.cumprod(jnp.concatenate([jnp.ones_like(continues[:1]), continues[:-2]], 0), 0)
            )  # [H-1, TB, 1]
            policy_loss = actor_loss(discount * lambda_values)
            img_aux = {
                "imagined_trajectories": sg(imagined_trajectories),
                "lambda_values": sg(lambda_values),
                "discount": discount,
            }
            return policy_loss, img_aux

        (policy_loss, img_aux), actor_grads = jax.value_and_grad(imagine_loss_fn, has_aux=True)(
            state["actor"]
        )
        actor_updates, actor_opt = txs["actor"].update(actor_grads, opt_states["actor"], state["actor"])
        state["actor"] = optax.apply_updates(state["actor"], actor_updates)

        # ------------------------------------------------- critic update
        traj = img_aux["imagined_trajectories"]
        lambda_values = img_aux["lambda_values"]
        discount = img_aux["discount"]

        def critic_loss_fn(critic_params):
            qv = Independent(Normal(agent.critic_value(critic_params, traj)[:-1], 1.0), 1)
            return critic_loss(qv, lambda_values, discount[..., 0])

        value_loss, critic_grads = jax.value_and_grad(critic_loss_fn)(state["critic"])
        critic_updates, critic_opt = txs["critic"].update(
            critic_grads, opt_states["critic"], state["critic"]
        )
        state["critic"] = optax.apply_updates(state["critic"], critic_updates)

        opt_states = {"world_model": wm_opt, "actor": actor_opt, "critic": critic_opt}
        metrics = {
            "Loss/world_model_loss": rec_loss,
            "Loss/observation_loss": aux["observation_loss"],
            "Loss/reward_loss": aux["reward_loss"],
            "Loss/state_loss": aux["state_loss"],
            "Loss/continue_loss": aux["continue_loss"],
            "State/kl": aux["kl"],
            "State/post_entropy": aux["post_entropy"],
            "State/prior_entropy": aux["prior_entropy"],
            "Loss/policy_loss": policy_loss,
            "Loss/value_loss": value_loss,
            "Grads/world_model": optax.global_norm(wm_grads),
            "Grads/actor": optax.global_norm(actor_grads),
            "Grads/critic": optax.global_norm(critic_grads),
        }
        return state, opt_states, metrics, next_key

    return train_step


@register_algorithm()
def main(runtime, cfg: Dict[str, Any]):
    rank = runtime.global_rank
    world_size = jax.process_count()

    state_ckpt = None
    if cfg.checkpoint.resume_from:
        state_ckpt = load_checkpoint(cfg.checkpoint.resume_from)

    # These arguments cannot be changed (reference: dreamer_v1.py:398-400)
    cfg.env.screen_size = 64
    cfg.env.frame_stack = 1

    logger = get_logger(runtime, cfg)
    if logger is not None:
        logger.log_hyperparams(cfg.as_dict() if hasattr(cfg, "as_dict") else dict(cfg))
    log_dir = get_log_dir(runtime, cfg.root_dir, cfg.run_name, logger=logger)
    runtime.print(f"Log dir: {log_dir}")
    telemetry = runtime.telemetry.open(log_dir, rank_zero=runtime.is_global_zero, device=runtime.device)
    guard = runtime.resilience.guard(rank_zero=runtime.is_global_zero)
    health = runtime.health

    envs = make_vector_env(cfg, rank, log_dir)
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

    # Eager flax/optax init runs host-side (each eager dispatch pays a host-device round trip); shard_params then moves the finished trees to the mesh.
    with runtime.host_init():
        agent, agent_state = build_agent(
            runtime,
            actions_dim,
            is_continuous,
            cfg,
            observation_space,
            state_ckpt["world_model"] if state_ckpt is not None else None,
            state_ckpt["actor"] if state_ckpt is not None else None,
            state_ckpt["critic"] if state_ckpt is not None else None,
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
        if state_ckpt is not None:
            for name, ckpt_key in (
                ("world_model", "world_optimizer"),
                ("actor", "actor_optimizer"),
                ("critic", "critic_optimizer"),
            ):
                opt_states[name] = restore_opt_state(opt_states[name], state_ckpt[ckpt_key])

    agent_state = runtime.shard_params(agent_state)
    opt_states = runtime.shard_params(opt_states)

    if runtime.is_global_zero:
        save_configs(cfg, log_dir)

    aggregator = None
    if not MetricAggregator.disabled:
        aggregator: MetricAggregator = instantiate(cfg.metric.aggregator)

    buffer_size = cfg.buffer.size // int(cfg.env.num_envs * world_size) if not cfg.dry_run else 2
    rb = EnvIndependentReplayBuffer(
        buffer_size,
        n_envs=cfg.env.num_envs,
        obs_keys=obs_keys,
        memmap=cfg.buffer.memmap,
        memmap_dir=os.path.join(log_dir, "memmap_buffer", f"rank_{rank}"),
        buffer_cls=SequentialReplayBuffer,
    )
    if state_ckpt is not None and cfg.buffer.checkpoint and state_ckpt.get("rb") is not None:
        rb = state_ckpt["rb"]

    train_step_count = 0
    last_train = 0
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

    train_fn = make_train_step(agent, txs, cfg, runtime.mesh)
    player_cnn_keys = tuple(cfg.algo.cnn_keys.encoder)

    def _player_step(wm, a, s, o, k, amount):
        # PRNG split + obs normalization in-graph: ONE dispatch per env step.
        next_k, sub = jax.random.split(k)
        out = agent.player_step(
            wm, a, s, normalize_player_obs(o, player_cnn_keys), sub, greedy=False, expl_amount=amount
        )
        return (*out, next_k)

    player_step_fn = jax.jit(_player_step
    )
    init_player_fn = jax.jit(agent.init_player_state, static_argnums=(1,))
    reset_player_fn = jax.jit(agent.reset_player_state)

    # Latency-aware player placement (core/player.py); off-policy: honors
    # fabric.player_sync=async. Mirror = world model + actor.
    placement = PlayerPlacement.resolve(
        cfg, runtime.mesh.devices.flat[0],
        params={"world_model": agent_state["world_model"], "actor": agent_state["actor"]},
    )
    placement.push({"world_model": agent_state["world_model"], "actor": agent_state["actor"]})


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
    step_data["terminated"] = np.zeros((1, cfg.env.num_envs, 1), np.float32)
    step_data["truncated"] = np.zeros((1, cfg.env.num_envs, 1), np.float32)
    step_data["actions"] = np.zeros((1, cfg.env.num_envs, int(np.sum(actions_dim))), np.float32)
    step_data["rewards"] = np.zeros((1, cfg.env.num_envs, 1), np.float32)
    rb.add(step_data, validate_args=cfg.buffer.validate_args)
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
            if iter_num <= learning_starts and cfg.checkpoint.resume_from is None:
                real_actions = actions = np.array(envs.action_space.sample())
                if not is_continuous:
                    actions = np.concatenate(
                        [
                            np.eye(act_dim, dtype=np.float32)[act]
                            for act, act_dim in zip(actions.reshape(len(actions_dim), -1), actions_dim)
                        ],
                        axis=-1,
                    )
            else:
                with placement.ctx():
                    np_obs = prepare_obs(obs, cnn_keys=cfg.algo.cnn_keys.encoder, num_envs=cfg.env.num_envs)
                    amount = exploration_amount(agent.actor_spec, policy_step)
                    pp = placement.params()
                    actions_cat, real_actions_j, player_state, rollout_key = player_step_fn(
                        pp["world_model"],
                        pp["actor"],
                        player_state,
                        np_obs,
                        rollout_key,
                        np.asarray(amount, np.float32),
                    )
                # One host fetch for both arrays: each separate np.asarray
                # is a full device->host roundtrip that blocks the
                # host. Submitted at dispatch, harvested at the last moment
                # so the copy rides under the host bookkeeping in between.
                pending = pipeline.fetch((actions_cat, real_actions_j), label="player_actions")
                if aggregator and not aggregator.disabled:
                    aggregator.update("Params/exploration_amount", amount)
                actions, real_actions = pending.harvest()

            next_obs, rewards, terminated, truncated, infos = envs.step(
                real_actions.reshape(envs.action_space.shape)
            )
            dones = np.logical_or(terminated, truncated).astype(np.uint8)

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
            step_data[k] = real_next_obs[k][np.newaxis]
        obs = next_obs

        step_data["terminated"] = terminated.reshape((1, cfg.env.num_envs, -1)).astype(np.float32)
        step_data["truncated"] = truncated.reshape((1, cfg.env.num_envs, -1)).astype(np.float32)
        step_data["actions"] = actions.reshape((1, cfg.env.num_envs, -1)).astype(np.float32)
        step_data["rewards"] = clip_rewards_fn(rewards).reshape((1, cfg.env.num_envs, -1)).astype(np.float32)
        rb.add(step_data, validate_args=cfg.buffer.validate_args)

        dones_idxes = dones.nonzero()[0].tolist()
        reset_envs = len(dones_idxes)
        if reset_envs > 0:
            reset_data = {}
            for k in obs_keys:
                reset_data[k] = (next_obs[k][dones_idxes])[np.newaxis]
            reset_data["terminated"] = np.zeros((1, reset_envs, 1), np.float32)
            reset_data["truncated"] = np.zeros((1, reset_envs, 1), np.float32)
            reset_data["actions"] = np.zeros((1, reset_envs, int(np.sum(actions_dim))), np.float32)
            reset_data["rewards"] = np.zeros((1, reset_envs, 1), np.float32)
            rb.add(reset_data, dones_idxes, validate_args=cfg.buffer.validate_args)
            for d in dones_idxes:
                step_data["terminated"][0, d] = np.zeros_like(step_data["terminated"][0, d])
                step_data["truncated"][0, d] = np.zeros_like(step_data["truncated"][0, d])
            reset_mask = np.zeros((cfg.env.num_envs,), np.float32)
            reset_mask[dones_idxes] = 1.0
            with placement.ctx():
                player_state = reset_player_fn(
                    placement.params()["world_model"], player_state, jnp.asarray(reset_mask)
                )

        # ------------------------------------------------------- training
        if iter_num >= learning_starts:
            ratio_steps = policy_step - prefill_steps * policy_steps_per_iter
            per_rank_gradient_steps = ratio(ratio_steps / world_size)
            if per_rank_gradient_steps > 0:
                batches = infeed.take_or_sample(per_rank_gradient_steps)
                with timer("Time/train_time"):
                    for i in range(per_rank_gradient_steps):
                        batch = batches[i]
                        with train_timer.step():
                            agent_state, opt_states, train_metrics, train_key = train_fn(
                                agent_state, opt_states, batch, train_key
                            )
                        # No sync here: the StepTimer queues the loss scalars
                        # device-side and bounds the interval with ONE block
                        # at the log-interval flush.
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
                "actor": agent_state["actor"],
                "critic": agent_state["critic"],
                "world_optimizer": opt_states["world_model"],
                "actor_optimizer": opt_states["actor"],
                "critic_optimizer": opt_states["critic"],
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
