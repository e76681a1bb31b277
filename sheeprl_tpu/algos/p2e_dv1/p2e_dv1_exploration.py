"""Plan2Explore (DreamerV1) — exploration phase
(reference: sheeprl/algos/p2e_dv1/p2e_dv1_exploration.py:41-801).

DreamerV1's jitted gradient step extended with the P2E phases: the ensemble
update (members regress the next OBSERVATION EMBEDDING; vmapped over stacked
params), an exploration actor/critic trained purely on ensemble-disagreement
reward, and the zero-shot task actor/critic on extrinsic reward. DV1-style
behaviour losses throughout: pure dynamics-backprop actor objective
(-mean(discount * lambda)) and Normal(.,1) critics without targets.
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

from sheeprl_tpu.algos.dreamer_v1.agent import DV1WorldModel
from sheeprl_tpu.algos.dreamer_v1.loss import actor_loss, critic_loss, reconstruction_loss
from sheeprl_tpu.algos.dreamer_v1.utils import compute_lambda_values, exploration_amount
from sheeprl_tpu.algos.dreamer_v2.agent import dv2_actor_forward
from sheeprl_tpu.algos.dreamer_v2.dreamer_v2 import _make_optimizer
from sheeprl_tpu.algos.p2e_dv1.agent import P2EDV1Agent, build_agent
from sheeprl_tpu.algos.p2e_dv1.utils import normalize_player_obs, prepare_obs, test
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
from sheeprl_tpu.utils.distribution import BernoulliSafeMode, Independent, MSEDistribution, Normal
from sheeprl_tpu.utils.env import make_vector_env
from sheeprl_tpu.utils.logger import get_log_dir, get_logger
from sheeprl_tpu.utils.metric import MetricAggregator
from sheeprl_tpu.utils.timer import timer
from sheeprl_tpu.utils.utils import Ratio, save_configs


def make_train_step(agent: P2EDV1Agent, txs: Dict[str, Any], cfg: Dict[str, Any], mesh):
    """Build the jitted P2E-DV1 gradient step over a [T, B] batch."""
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
    intrinsic_multiplier = float(cfg.algo.intrinsic_reward_multiplier)
    spec = agent.actor_spec
    dv1 = agent.dv1

    batch_sharding = NamedSharding(mesh, P(None, DATA_AXIS))

    def world_loss_fn(wm_params, data, batch_obs, keys):
        T, B = data["rewards"].shape[:2]
        embedded = dv1.wm(wm_params, batch_obs, method="embed_obs")
        h0 = jnp.zeros((B, recurrent_state_size), embedded.dtype)
        z0 = jnp.zeros((B, stochastic_size), embedded.dtype)

        def step(carry, x):
            h, z = carry
            action, emb, key = x
            h, post, prior, post_ms, prior_ms = dv1.world_model.apply(
                wm_params, z, h, action, emb, key, method=DV1WorldModel.dynamic
            )
            return (h, post), (h, post, post_ms[0], post_ms[1], prior_ms[0], prior_ms[1])

        (_, _), (recurrent_states, posteriors, post_means, post_stds, prior_means, prior_stds) = (
            jax.lax.scan(step, (h0, z0), (data["actions"], embedded, keys))
        )
        latent_states = jnp.concatenate([posteriors, recurrent_states], -1)

        reconstructed_obs = dv1.wm(wm_params, latent_states, method="decode")
        qo = {
            k: Independent(Normal(v, jnp.ones_like(v)), len(v.shape[2:]))
            for k, v in reconstructed_obs.items()
        }
        qr = Independent(Normal(dv1.wm(wm_params, latent_states, method="reward"), 1.0), 1)
        if use_continues:
            qc = Independent(
                BernoulliSafeMode(logits=dv1.wm(wm_params, latent_states, method="continue_logits")), 1
            )
            continues_targets = (1 - data["terminated"]) * gamma
        else:
            qc = continues_targets = None

        posteriors_dist = Independent(Normal(post_means, post_stds), 1)
        priors_dist = Independent(Normal(prior_means, prior_stds), 1)
        rec_loss, kl, state_loss, reward_loss, observation_loss, continue_loss = reconstruction_loss(
            qo, batch_obs, qr, data["rewards"], posteriors_dist, priors_dist,
            wm_cfg.kl_free_nats, wm_cfg.kl_regularizer, qc, continues_targets,
            wm_cfg.continue_scale_factor,
        )
        aux = {
            "posteriors": posteriors,
            "recurrent_states": recurrent_states,
            "embedded": embedded,
            "post_entropy": posteriors_dist.entropy().mean(),
            "prior_entropy": priors_dist.entropy().mean(),
            "kl": kl,
            "state_loss": state_loss,
            "reward_loss": reward_loss,
            "observation_loss": observation_loss,
            "continue_loss": continue_loss,
        }
        return rec_loss, aux

    def imagine_rollout(actor_params, wm_params, prior0, h0, latent0, k_img):
        """DV1-style rollout: action i is sampled FROM state i-1 and the
        trajectory excludes the seed latent. Returns ([H, TB, L], [H, TB, A])."""
        sg = jax.lax.stop_gradient

        def actor_sample(latent, k):
            pre = dv1.actor.apply(actor_params, sg(latent))
            actions, _ = dv2_actor_forward(pre, spec, k, greedy=False)
            return jnp.concatenate(actions, -1)

        def img_step(carry, k):
            prior, h, latent = carry
            k_act, k_wm = jax.random.split(k)
            actions = actor_sample(latent, k_act)
            prior, h = dv1.world_model.apply(
                wm_params, prior, h, actions, k_wm, method=DV1WorldModel.imagination
            )
            latent = jnp.concatenate([prior, h], -1)
            return (prior, h, latent), (latent, actions)

        _, (latents, img_actions) = jax.lax.scan(
            img_step, (prior0, h0, latent0), jax.random.split(k_img, horizon)
        )
        return latents, img_actions

    def imagined_continues(wm_params, trajectories, like):
        if use_continues:
            return jax.nn.sigmoid(dv1.wm(wm_params, trajectories, method="continue_logits"))
        return jnp.ones_like(like) * gamma

    @partial(jax.jit, donate_argnums=(0, 1))
    def train_step(state, opt_states, data, key):
        next_key, key = jax.random.split(key)
        T, B = data["rewards"].shape[:2]
        data = jax.lax.with_sharding_constraint(data, {k: batch_sharding for k in data})
        batch_obs = {k: data[k] / 255.0 - 0.5 for k in cnn_keys}
        batch_obs.update({k: data[k] for k in mlp_keys})
        sg = jax.lax.stop_gradient

        k_dyn, kimg_expl, kimg_task = jax.random.split(key, 3)
        dyn_keys = jax.random.split(k_dyn, T)

        # ---------------------------------------------- world model update
        (rec_loss, aux), wm_grads = jax.value_and_grad(world_loss_fn, has_aux=True)(
            state["world_model"], data, batch_obs, dyn_keys
        )
        wm_updates, wm_opt = txs["world_model"].update(
            wm_grads, opt_states["world_model"], state["world_model"]
        )
        state["world_model"] = optax.apply_updates(state["world_model"], wm_updates)

        posteriors = sg(aux["posteriors"])
        recurrent_states = sg(aux["recurrent_states"])
        embedded = sg(aux["embedded"])

        # ------------------------------------------------------- ensembles
        def ensemble_loss_fn(ens_params):
            # Only the first T-1 timesteps have a next-step target: slice
            # before the forward pass, not after.
            x = jnp.concatenate([posteriors, recurrent_states, sg(data["actions"])], -1)[:-1]
            preds = agent.ensemble_apply(ens_params, x)  # [N, T-1, B, E]
            target = embedded[1:]

            def member_loss(pred):
                return -Independent(Normal(pred, 1.0), 1).log_prob(target).mean()

            return jax.vmap(member_loss)(preds).sum()

        ensemble_loss, ens_grads = jax.value_and_grad(ensemble_loss_fn)(state["ensembles"])
        ens_updates, ens_opt = txs["ensembles"].update(ens_grads, opt_states["ensembles"], state["ensembles"])
        state["ensembles"] = optax.apply_updates(state["ensembles"], ens_updates)

        prior0 = posteriors.reshape(-1, stochastic_size)
        h0 = recurrent_states.reshape(-1, recurrent_state_size)
        latent0 = jnp.concatenate([prior0, h0], -1)

        # --------------------------------------- exploration behaviour
        def expl_loss_fn(actor_params):
            trajectories, imagined_actions = imagine_rollout(
                actor_params, state["world_model"], prior0, h0, latent0, kimg_expl
            )
            ens_in = jnp.concatenate([sg(trajectories), sg(imagined_actions)], -1)
            next_obs_pred = agent.ensemble_apply(state["ensembles"], ens_in)
            intrinsic_reward = (
                next_obs_pred.var(0).mean(-1, keepdims=True) * intrinsic_multiplier
            )
            values = dv1.critic_value(state["critic_exploration"], trajectories)
            continues = imagined_continues(state["world_model"], trajectories, sg(intrinsic_reward))
            lambda_values = compute_lambda_values(
                intrinsic_reward, values, continues, last_values=values[-1], lmbda=lmbda
            )
            discount = sg(
                jnp.cumprod(jnp.concatenate([jnp.ones_like(continues[:1]), continues[:-2]], 0), 0)
            )
            policy_loss = actor_loss(discount * lambda_values)
            aux_expl = {
                "trajectories": sg(trajectories),
                "lambda_values": sg(lambda_values),
                "discount": discount,
                "mean_intrinsic": sg(intrinsic_reward).mean(),
            }
            return policy_loss, aux_expl

        (policy_loss_expl, aux_expl), ae_grads = jax.value_and_grad(expl_loss_fn, has_aux=True)(
            state["actor_exploration"]
        )
        ae_updates, ae_opt = txs["actor_exploration"].update(
            ae_grads, opt_states["actor_exploration"], state["actor_exploration"]
        )
        state["actor_exploration"] = optax.apply_updates(state["actor_exploration"], ae_updates)

        def expl_critic_loss_fn(params):
            qv = Independent(
                Normal(dv1.critic_value(params, aux_expl["trajectories"][:-1]), 1.0), 1
            )
            return critic_loss(qv, aux_expl["lambda_values"], aux_expl["discount"][..., 0])

        value_loss_expl, ce_grads = jax.value_and_grad(expl_critic_loss_fn)(
            state["critic_exploration"]
        )
        ce_updates, ce_opt = txs["critic_exploration"].update(
            ce_grads, opt_states["critic_exploration"], state["critic_exploration"]
        )
        state["critic_exploration"] = optax.apply_updates(state["critic_exploration"], ce_updates)

        # ------------------------------------------------ task behaviour
        def task_loss_fn(actor_params):
            trajectories, _ = imagine_rollout(
                actor_params, state["world_model"], prior0, h0, latent0, kimg_task
            )
            values = dv1.critic_value(state["critic_task"], trajectories)
            rewards = dv1.wm(state["world_model"], trajectories, method="reward")
            continues = imagined_continues(state["world_model"], trajectories, sg(rewards))
            lambda_values = compute_lambda_values(
                rewards, values, continues, last_values=values[-1], lmbda=lmbda
            )
            discount = sg(
                jnp.cumprod(jnp.concatenate([jnp.ones_like(continues[:1]), continues[:-2]], 0), 0)
            )
            policy_loss = actor_loss(discount * lambda_values)
            aux_task = {
                "trajectories": sg(trajectories),
                "lambda_values": sg(lambda_values),
                "discount": discount,
            }
            return policy_loss, aux_task

        (policy_loss_task, aux_task), at_grads = jax.value_and_grad(task_loss_fn, has_aux=True)(
            state["actor_task"]
        )
        at_updates, at_opt = txs["actor_task"].update(
            at_grads, opt_states["actor_task"], state["actor_task"]
        )
        state["actor_task"] = optax.apply_updates(state["actor_task"], at_updates)

        def task_critic_loss_fn(params):
            qv = Independent(
                Normal(dv1.critic_value(params, aux_task["trajectories"][:-1]), 1.0), 1
            )
            return critic_loss(qv, aux_task["lambda_values"], aux_task["discount"][..., 0])

        value_loss_task, ct_grads = jax.value_and_grad(task_critic_loss_fn)(state["critic_task"])
        ct_updates, ct_opt = txs["critic_task"].update(
            ct_grads, opt_states["critic_task"], state["critic_task"]
        )
        state["critic_task"] = optax.apply_updates(state["critic_task"], ct_updates)

        opt_states = {
            "world_model": wm_opt,
            "actor_task": at_opt,
            "critic_task": ct_opt,
            "actor_exploration": ae_opt,
            "critic_exploration": ce_opt,
            "ensembles": ens_opt,
        }
        metrics = {
            "Loss/world_model_loss": rec_loss,
            "Loss/observation_loss": aux["observation_loss"],
            "Loss/reward_loss": aux["reward_loss"],
            "Loss/state_loss": aux["state_loss"],
            "Loss/continue_loss": aux["continue_loss"],
            "Loss/ensemble_loss": ensemble_loss,
            "State/kl": aux["kl"],
            "State/post_entropy": aux["post_entropy"],
            "State/prior_entropy": aux["prior_entropy"],
            "Loss/policy_loss_exploration": policy_loss_expl,
            "Loss/value_loss_exploration": value_loss_expl,
            "Loss/policy_loss_task": policy_loss_task,
            "Loss/value_loss_task": value_loss_task,
            "Rewards/intrinsic": aux_expl["mean_intrinsic"],
            "Grads/world_model": optax.global_norm(wm_grads),
            "Grads/actor_task": optax.global_norm(at_grads),
            "Grads/critic_task": optax.global_norm(ct_grads),
            "Grads/actor_exploration": optax.global_norm(ae_grads),
            "Grads/critic_exploration": optax.global_norm(ce_grads),
            "Grads/ensemble": optax.global_norm(ens_grads),
        }
        return state, opt_states, metrics, next_key

    return train_step


@register_algorithm(name="p2e_dv1_exploration")
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
            state_ckpt["ensembles"] if state_ckpt is not None else None,
            state_ckpt["actor_task"] if state_ckpt is not None else None,
            state_ckpt["critic_task"] if state_ckpt is not None else None,
            state_ckpt["actor_exploration"] if state_ckpt is not None else None,
            state_ckpt["critic_exploration"] if state_ckpt is not None else None,
        )

        txs = {
            "world_model": _make_optimizer(cfg.algo.world_model.optimizer, cfg.algo.world_model.clip_gradients),
            "actor_task": _make_optimizer(cfg.algo.actor.optimizer, cfg.algo.actor.clip_gradients),
            "critic_task": _make_optimizer(cfg.algo.critic.optimizer, cfg.algo.critic.clip_gradients),
            "actor_exploration": _make_optimizer(cfg.algo.actor.optimizer, cfg.algo.actor.clip_gradients),
            "critic_exploration": _make_optimizer(cfg.algo.critic.optimizer, cfg.algo.critic.clip_gradients),
            "ensembles": _make_optimizer(cfg.algo.ensembles.optimizer, cfg.algo.ensembles.clip_gradients),
        }
        opt_states = {
            "world_model": txs["world_model"].init(agent_state["world_model"]),
            "actor_task": txs["actor_task"].init(agent_state["actor_task"]),
            "critic_task": txs["critic_task"].init(agent_state["critic_task"]),
            "actor_exploration": txs["actor_exploration"].init(agent_state["actor_exploration"]),
            "critic_exploration": txs["critic_exploration"].init(agent_state["critic_exploration"]),
            "ensembles": txs["ensembles"].init(agent_state["ensembles"]),
        }
        if state_ckpt is not None:
            for name, ckpt_key in (
                ("world_model", "world_optimizer"),
                ("actor_task", "actor_task_optimizer"),
                ("critic_task", "critic_task_optimizer"),
                ("actor_exploration", "actor_exploration_optimizer"),
                ("critic_exploration", "critic_exploration_optimizer"),
                ("ensembles", "ensemble_optimizer"),
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
        out = agent.dv1.player_step(
            wm, a, s, normalize_player_obs(o, player_cnn_keys), sub, greedy=False, expl_amount=amount
        )
        return (*out, next_k)

    player_step_fn = jax.jit(_player_step
    )
    init_player_fn = jax.jit(agent.dv1.init_player_state, static_argnums=(1,))
    reset_player_fn = jax.jit(agent.dv1.reset_player_state)
    player_actor_key = (
        "actor_exploration" if cfg.algo.player.actor_type == "exploration" else "actor_task"
    )

    # Latency-aware player placement (core/player.py); off-policy: honors
    # fabric.player_sync=async. Mirror = world model + the player's actor.
    placement = PlayerPlacement.resolve(
        cfg, runtime.mesh.devices.flat[0],
        params={"world_model": agent_state["world_model"], "actor": agent_state[player_actor_key]},
    )
    placement.push(
        {"world_model": agent_state["world_model"], "actor": agent_state[player_actor_key]}
    )


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
                        {"world_model": agent_state["world_model"], "actor": agent_state[player_actor_key]}
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
                "actor_task": agent_state["actor_task"],
                "critic_task": agent_state["critic_task"],
                "actor_exploration": agent_state["actor_exploration"],
                "critic_exploration": agent_state["critic_exploration"],
                "ensembles": agent_state["ensembles"],
                "world_optimizer": opt_states["world_model"],
                "actor_task_optimizer": opt_states["actor_task"],
                "critic_task_optimizer": opt_states["critic_task"],
                "actor_exploration_optimizer": opt_states["actor_exploration"],
                "critic_exploration_optimizer": opt_states["critic_exploration"],
                "ensemble_optimizer": opt_states["ensembles"],
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
        test(
            agent.dv1,
            {"world_model": agent_state["world_model"], "actor": agent_state[player_actor_key]},
            runtime,
            cfg,
            log_dir,
            logger,
        )

    guard.close()
    telemetry.close()
    if logger is not None:
        logger.close()
