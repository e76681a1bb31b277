"""Plan2Explore (DreamerV3) — exploration phase
(reference: sheeprl/algos/p2e_dv3/p2e_dv3_exploration.py:41-1059).

One jitted, donated gradient step runs the four P2E phases:

1. world-model update — identical to DreamerV3 (RSSM scan + reconstruction
   loss);
2. ensemble update — N next-latent predictors regress the next posterior from
   (latent state, action); vmapped over the stacked member params;
3. exploration behaviour — imagination rollout with the exploration actor;
   each exploration critic contributes a weighted, Moments-normalized
   advantage, where "intrinsic" critics are trained on ensemble-disagreement
   reward (variance over members x multiplier) and "task" critics on the
   world model's reward head;
4. task behaviour (zero-shot) — the plain DreamerV3 actor/critic update on
   extrinsic reward, trained on the exploration data.

The per-critic structure is static config, so the loop over exploration
critics unrolls at trace time — no dynamic control flow reaches XLA.
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

from sheeprl_tpu.algos.dreamer_v3.agent import WorldModel, actor_forward, continuous_log_prob_and_entropy
from sheeprl_tpu.algos.dreamer_v3.dreamer_v3 import _make_optimizer
from sheeprl_tpu.algos.p2e_dv3.agent import P2EDV3Agent, build_agent
from sheeprl_tpu.algos.p2e_dv3.utils import normalize_player_obs, prepare_obs, test
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
from sheeprl_tpu.utils.distribution import (
    BernoulliSafeMode,
    Independent,
    MSEDistribution,
    OneHotCategorical,
    TwoHotEncodingDistribution,
)
from sheeprl_tpu.utils.env import make_vector_env
from sheeprl_tpu.utils.logger import get_log_dir, get_logger
from sheeprl_tpu.utils.metric import MetricAggregator
from sheeprl_tpu.utils.ops import compute_lambda_values, init_moments, update_moments
from sheeprl_tpu.utils.timer import timer
from sheeprl_tpu.utils.utils import Ratio, save_configs


def make_train_step(agent: P2EDV3Agent, txs: Dict[str, Any], cfg: Dict[str, Any], mesh):
    """Build the jitted P2E gradient step over a [T, B] batch."""
    from jax.sharding import NamedSharding, PartitionSpec as P

    # Reuse DreamerV3's world-model loss wholesale: it closes only over the
    # agent's dv3 view and static config.
    from sheeprl_tpu.algos.dreamer_v3.dreamer_v3 import make_train_step as _dv3_mts  # noqa: F401 (parity anchor)

    wm_cfg = cfg.algo.world_model
    cnn_keys = list(cfg.algo.cnn_keys.encoder)
    mlp_keys = list(cfg.algo.mlp_keys.encoder)
    stochastic_size = int(wm_cfg.stochastic_size)
    discrete_size = int(wm_cfg.discrete_size)
    stoch_state_size = stochastic_size * discrete_size
    recurrent_state_size = int(wm_cfg.recurrent_model.recurrent_state_size)
    horizon = int(cfg.algo.horizon)
    gamma = float(cfg.algo.gamma)
    lmbda = float(cfg.algo.lmbda)
    ent_coef = float(cfg.algo.actor.ent_coef)
    moments_cfg = cfg.algo.actor.moments
    intrinsic_multiplier = float(cfg.algo.intrinsic_reward_multiplier)
    spec = agent.actor_spec
    actions_dim = agent.actions_dim
    critic_names = sorted(agent.critics_exploration)
    weights_sum = sum(agent.critics_exploration[k]["weight"] for k in critic_names)
    dv3 = agent.dv3

    batch_sharding = NamedSharding(mesh, P(None, DATA_AXIS))

    # ---------------------------------------------------------- world model
    def world_loss_fn(wm_params, data, batch_obs, keys):
        T, B = data["rewards"].shape[:2]
        embedded = dv3.wm(wm_params, batch_obs, method="embed_obs")
        batch_actions = jnp.concatenate(
            [jnp.zeros_like(data["actions"][:1]), data["actions"][:-1]], axis=0
        )
        is_first = data["is_first"].at[0].set(1.0)
        h0 = jnp.zeros((B, recurrent_state_size), embedded.dtype)
        z0 = jnp.zeros((B, stoch_state_size), embedded.dtype)

        def step(carry, x):
            h, z = carry
            action, emb, first, key = x
            h, post, prior, post_logits, prior_logits = dv3.world_model.apply(
                wm_params, z, h, action, emb, first, key, method=WorldModel.dynamic
            )
            return (h, post), (h, post, post_logits, prior_logits)

        (_, _), (recurrent_states, posteriors, posteriors_logits, priors_logits) = jax.lax.scan(
            step, (h0, z0), (batch_actions, embedded, is_first, keys[:T])
        )
        latent_states = jnp.concatenate([posteriors, recurrent_states], -1)

        from sheeprl_tpu.algos.dreamer_v3.loss import reconstruction_loss
        from sheeprl_tpu.utils.distribution import SymlogDistribution

        reconstructed_obs = dv3.wm(wm_params, latent_states, method="decode")
        po = {
            k: MSEDistribution(reconstructed_obs[k], dims=len(reconstructed_obs[k].shape[2:]))
            for k in cfg.algo.cnn_keys.decoder
        }
        po.update(
            {
                k: SymlogDistribution(reconstructed_obs[k], dims=len(reconstructed_obs[k].shape[2:]))
                for k in cfg.algo.mlp_keys.decoder
            }
        )
        pr = TwoHotEncodingDistribution(dv3.wm(wm_params, latent_states, method="reward_logits"), dims=1)
        pc = Independent(
            BernoulliSafeMode(logits=dv3.wm(wm_params, latent_states, method="continue_logits")), 1
        )
        continues_targets = 1 - data["terminated"]
        pl = priors_logits.reshape(*priors_logits.shape[:-1], stochastic_size, discrete_size)
        pol = posteriors_logits.reshape(*posteriors_logits.shape[:-1], stochastic_size, discrete_size)
        rec_loss, kl, state_loss, reward_loss, observation_loss, continue_loss = reconstruction_loss(
            po, batch_obs, pr, data["rewards"], pl, pol,
            wm_cfg.kl_dynamic, wm_cfg.kl_representation, wm_cfg.kl_free_nats, wm_cfg.kl_regularizer,
            pc, continues_targets, wm_cfg.continue_scale_factor,
        )
        aux = {
            "posteriors": posteriors,
            "recurrent_states": recurrent_states,
            "posteriors_logits": pol,
            "priors_logits": pl,
            "kl": kl,
            "state_loss": state_loss,
            "reward_loss": reward_loss,
            "observation_loss": observation_loss,
            "continue_loss": continue_loss,
        }
        return rec_loss, aux

    # ------------------------------------------------------------ behaviour
    def imagine_rollout(actor_params, wm_params, prior0, h0, latent0, k0, k_img):
        """Shared imagination rollout: scan the RSSM prior forward, sampling
        actions from ``actor_params`` each step. Returns ([H+1, TB, L]
        trajectories, [H+1, TB, A] actions)."""
        sg = jax.lax.stop_gradient

        def actor_sample(latent, k):
            pre = dv3.actor.apply(actor_params, sg(latent))
            actions, _ = actor_forward(pre, spec, k, greedy=False)
            return jnp.concatenate(actions, -1)

        a0 = actor_sample(latent0, k0)

        def img_step(carry, k):
            prior, h, actions = carry
            k_wm, k_act = jax.random.split(k)
            prior, h = dv3.world_model.apply(
                wm_params, prior, h, actions, k_wm, method=WorldModel.imagination
            )
            latent = jnp.concatenate([prior, h], -1)
            next_actions = actor_sample(latent, k_act)
            return (prior, h, next_actions), (latent, next_actions)

        _, (latents, img_actions) = jax.lax.scan(img_step, (prior0, h0, a0), jax.random.split(k_img, horizon))
        trajectories = jnp.concatenate([latent0[None], latents], 0)
        actions = jnp.concatenate([a0[None], img_actions], 0)
        return trajectories, actions

    def actor_objective(policies, imagined_actions, advantage):
        sg = jax.lax.stop_gradient
        if spec.is_continuous:
            objective = advantage
            _, entropy = continuous_log_prob_and_entropy(policies[0], imagined_actions, spec)
            entropy = ent_coef * entropy if entropy is not None else jnp.zeros(advantage.shape[:-1])
        else:
            splits = np.cumsum(actions_dim)[:-1]
            per_dim = jnp.split(imagined_actions, splits, -1)
            logp = jnp.stack(
                [p.log_prob(sg(a))[..., None][:-1] for p, a in zip(policies, per_dim)], -1
            ).sum(-1)
            objective = logp * sg(advantage)
            entropy = ent_coef * jnp.stack([p.entropy() for p in policies], -1).sum(-1)
        return objective, entropy

    def predicted_continues(wm_params, trajectories, data):
        continues = Independent(
            BernoulliSafeMode(logits=dv3.wm(wm_params, trajectories, method="continue_logits")), 1
        ).mode
        true_continue = (1 - data["terminated"]).reshape(1, -1, 1)
        return jnp.concatenate([true_continue, continues[1:]], 0)

    @partial(jax.jit, donate_argnums=(0, 1, 2))
    def train_step(state, opt_states, moments, data, key, tau):
        next_key, key = jax.random.split(key)
        T, B = data["rewards"].shape[:2]
        data = jax.lax.with_sharding_constraint(data, {k: batch_sharding for k in data})
        batch_obs = {k: data[k] / 255.0 - 0.5 for k in cnn_keys}
        batch_obs.update({k: data[k] for k in mlp_keys})
        sg = jax.lax.stop_gradient

        k_dyn, k0_expl, kimg_expl, kpol_expl, k0_task, kimg_task, kpol_task = jax.random.split(key, 7)
        dyn_keys = jax.random.split(k_dyn, T + 1)

        # 1. ------------------------------------------------- world model
        (rec_loss, aux), wm_grads = jax.value_and_grad(world_loss_fn, has_aux=True)(
            state["world_model"], data, batch_obs, dyn_keys
        )
        wm_updates, wm_opt = txs["world_model"].update(
            wm_grads, opt_states["world_model"], state["world_model"]
        )
        state["world_model"] = optax.apply_updates(state["world_model"], wm_updates)

        posteriors = sg(aux["posteriors"])  # [T, B, S]
        recurrent_states = sg(aux["recurrent_states"])  # [T, B, R]

        # 2. --------------------------------------------------- ensembles
        def ensemble_loss_fn(ens_params):
            # Only the first T-1 timesteps have a next-step target: slice
            # before the forward pass, not after.
            x = jnp.concatenate([posteriors, recurrent_states, sg(data["actions"])], -1)[:-1]
            preds = agent.ensemble_apply(ens_params, x)  # [N, T-1, B, S]
            target = posteriors[1:]

            def member_loss(pred):
                return -MSEDistribution(pred, 1).log_prob(target).mean()

            return jax.vmap(member_loss)(preds).sum()

        ensemble_loss, ens_grads = jax.value_and_grad(ensemble_loss_fn)(state["ensembles"])
        ens_updates, ens_opt = txs["ensembles"].update(ens_grads, opt_states["ensembles"], state["ensembles"])
        state["ensembles"] = optax.apply_updates(state["ensembles"], ens_updates)

        # Shared imagination start: every (t, b) posterior becomes a rollout seed.
        prior0 = posteriors.reshape(-1, stoch_state_size)
        h0 = recurrent_states.reshape(-1, recurrent_state_size)
        latent0 = jnp.concatenate([prior0, h0], -1)

        # 3. --------------------------------------- exploration behaviour
        def expl_loss_fn(actor_params):
            trajectories, imagined_actions = imagine_rollout(
                actor_params, state["world_model"], prior0, h0, latent0, k0_expl, kimg_expl
            )
            continues = predicted_continues(state["world_model"], trajectories, data)
            discount = sg(jnp.cumprod(continues * gamma, 0) / gamma)

            # Intrinsic reward: ensemble disagreement on the imagined rollout.
            ens_in = jnp.concatenate([sg(trajectories), sg(imagined_actions)], -1)
            next_state_pred = agent.ensemble_apply(state["ensembles"], ens_in)  # [N, H+1, TB, S]
            intrinsic_reward = (
                next_state_pred.var(0).mean(-1, keepdims=True) * intrinsic_multiplier
            )
            extrinsic_reward = TwoHotEncodingDistribution(
                dv3.wm(state["world_model"], trajectories, method="reward_logits"), dims=1
            ).mean

            advantage = 0.0
            new_moments = {}
            per_critic = {}
            for name in critic_names:
                c = agent.critics_exploration[name]
                reward = intrinsic_reward if c["reward_type"] == "intrinsic" else extrinsic_reward
                values = TwoHotEncodingDistribution(
                    agent.exploration_critic_logits(state["critics_exploration"][name]["module"], trajectories),
                    dims=1,
                ).mean
                lambda_values = compute_lambda_values(
                    reward[1:], values[1:], continues[1:] * gamma, lmbda
                )
                m, (offset, invscale) = update_moments(
                    moments["exploration"][name],
                    lambda_values,
                    decay=moments_cfg.decay,
                    max_=moments_cfg.max,
                    percentile_low=moments_cfg.percentile.low,
                    percentile_high=moments_cfg.percentile.high,
                )
                new_moments[name] = m
                normed_lambda = (lambda_values - offset) / invscale
                normed_baseline = (values[:-1] - offset) / invscale
                advantage = advantage + (normed_lambda - normed_baseline) * (
                    c["weight"] / weights_sum
                )
                per_critic[name] = {
                    "lambda_values": sg(lambda_values),
                    "mean_value": sg(values).mean(),
                    "mean_intrinsic": sg(intrinsic_reward).mean()
                    if c["reward_type"] == "intrinsic"
                    else jnp.zeros(()),
                }

            pre = dv3.actor.apply(actor_params, sg(trajectories))
            _, policies = actor_forward(pre, spec, kpol_expl, greedy=False)
            objective, entropy = actor_objective(policies, imagined_actions, advantage)
            policy_loss = -jnp.mean(sg(discount[:-1]) * (objective + entropy[..., None][:-1]))
            aux_expl = {
                "trajectories": sg(trajectories),
                "discount": discount,
                "per_critic": per_critic,
                "moments": new_moments,
            }
            return policy_loss, aux_expl

        (policy_loss_expl, aux_expl), actor_expl_grads = jax.value_and_grad(expl_loss_fn, has_aux=True)(
            state["actor_exploration"]
        )
        ae_updates, ae_opt = txs["actor_exploration"].update(
            actor_expl_grads, opt_states["actor_exploration"], state["actor_exploration"]
        )
        state["actor_exploration"] = optax.apply_updates(state["actor_exploration"], ae_updates)
        moments_exploration = aux_expl["moments"]

        # Exploration critic updates (static unroll over the critic table).
        traj_expl = aux_expl["trajectories"][:-1]
        discount_expl = aux_expl["discount"]
        critic_metrics = {}
        new_critic_opts = {}
        for name in critic_names:
            lambda_values = aux_expl["per_critic"][name]["lambda_values"]
            target_values = TwoHotEncodingDistribution(
                agent.exploration_critic_logits(
                    state["critics_exploration"][name]["target_module"], traj_expl
                ),
                dims=1,
            ).mean

            def critic_loss_fn(params):
                qv = TwoHotEncodingDistribution(
                    agent.exploration_critic_logits(params, traj_expl), dims=1
                )
                loss = -qv.log_prob(lambda_values) - qv.log_prob(sg(target_values))
                return jnp.mean(loss * discount_expl[:-1].squeeze(-1))

            v_loss, c_grads = jax.value_and_grad(critic_loss_fn)(
                state["critics_exploration"][name]["module"]
            )
            c_updates, c_opt = txs["critics_exploration"].update(
                c_grads,
                opt_states["critics_exploration"][name],
                state["critics_exploration"][name]["module"],
            )
            state["critics_exploration"][name]["module"] = optax.apply_updates(
                state["critics_exploration"][name]["module"], c_updates
            )
            state["critics_exploration"][name]["target_module"] = jax.tree_util.tree_map(
                lambda p, tp: tau * p + (1 - tau) * tp,
                state["critics_exploration"][name]["module"],
                state["critics_exploration"][name]["target_module"],
            )
            new_critic_opts[name] = c_opt
            critic_metrics[f"Grads/critic_exploration_{name}"] = optax.global_norm(c_grads)
            critic_metrics[f"Loss/value_loss_exploration_{name}"] = v_loss
            critic_metrics[f"Values_exploration/predicted_values_{name}"] = aux_expl["per_critic"][name][
                "mean_value"
            ]
            critic_metrics[f"Values_exploration/lambda_values_{name}"] = lambda_values.mean()
            if agent.critics_exploration[name]["reward_type"] == "intrinsic":
                critic_metrics[f"Rewards/intrinsic_{name}"] = aux_expl["per_critic"][name]["mean_intrinsic"]

        # 4. ------------------------------------------------ task behaviour
        def task_loss_fn(actor_params):
            trajectories, imagined_actions = imagine_rollout(
                actor_params, state["world_model"], prior0, h0, latent0, k0_task, kimg_task
            )
            continues = predicted_continues(state["world_model"], trajectories, data)
            discount = sg(jnp.cumprod(continues * gamma, 0) / gamma)
            values = TwoHotEncodingDistribution(
                dv3.critic_logits(state["critic_task"], trajectories), dims=1
            ).mean
            rewards = TwoHotEncodingDistribution(
                dv3.wm(state["world_model"], trajectories, method="reward_logits"), dims=1
            ).mean
            lambda_values = compute_lambda_values(rewards[1:], values[1:], continues[1:] * gamma, lmbda)
            m, (offset, invscale) = update_moments(
                moments["task"],
                lambda_values,
                decay=moments_cfg.decay,
                max_=moments_cfg.max,
                percentile_low=moments_cfg.percentile.low,
                percentile_high=moments_cfg.percentile.high,
            )
            advantage = (lambda_values - offset) / invscale - (values[:-1] - offset) / invscale
            pre = dv3.actor.apply(actor_params, sg(trajectories))
            _, policies = actor_forward(pre, spec, kpol_task, greedy=False)
            objective, entropy = actor_objective(policies, imagined_actions, advantage)
            policy_loss = -jnp.mean(sg(discount[:-1]) * (objective + entropy[..., None][:-1]))
            aux_task = {
                "trajectories": sg(trajectories),
                "lambda_values": sg(lambda_values),
                "discount": discount,
                "moments": m,
            }
            return policy_loss, aux_task

        (policy_loss_task, aux_task), actor_task_grads = jax.value_and_grad(task_loss_fn, has_aux=True)(
            state["actor_task"]
        )
        at_updates, at_opt = txs["actor_task"].update(
            actor_task_grads, opt_states["actor_task"], state["actor_task"]
        )
        state["actor_task"] = optax.apply_updates(state["actor_task"], at_updates)
        moments_task = aux_task["moments"]

        traj_task = aux_task["trajectories"][:-1]
        target_values_task = TwoHotEncodingDistribution(
            dv3.critic_logits(state["target_critic_task"], traj_task), dims=1
        ).mean

        def task_critic_loss_fn(params):
            qv = TwoHotEncodingDistribution(dv3.critic_logits(params, traj_task), dims=1)
            loss = -qv.log_prob(aux_task["lambda_values"]) - qv.log_prob(sg(target_values_task))
            return jnp.mean(loss * aux_task["discount"][:-1].squeeze(-1))

        value_loss_task, ct_grads = jax.value_and_grad(task_critic_loss_fn)(state["critic_task"])
        ct_updates, ct_opt = txs["critic_task"].update(
            ct_grads, opt_states["critic_task"], state["critic_task"]
        )
        state["critic_task"] = optax.apply_updates(state["critic_task"], ct_updates)
        state["target_critic_task"] = jax.tree_util.tree_map(
            lambda p, tp: tau * p + (1 - tau) * tp, state["critic_task"], state["target_critic_task"]
        )

        opt_states = {
            "world_model": wm_opt,
            "actor_task": at_opt,
            "critic_task": ct_opt,
            "actor_exploration": ae_opt,
            "ensembles": ens_opt,
            "critics_exploration": new_critic_opts,
        }
        moments = {"task": moments_task, "exploration": moments_exploration}
        metrics = {
            "Loss/world_model_loss": rec_loss,
            "Loss/observation_loss": aux["observation_loss"],
            "Loss/reward_loss": aux["reward_loss"],
            "Loss/state_loss": aux["state_loss"],
            "Loss/continue_loss": aux["continue_loss"],
            "Loss/ensemble_loss": ensemble_loss,
            "State/kl": aux["kl"],
            "State/post_entropy": Independent(
                OneHotCategorical(logits=aux["posteriors_logits"]), 1
            ).entropy().mean(),
            "State/prior_entropy": Independent(
                OneHotCategorical(logits=aux["priors_logits"]), 1
            ).entropy().mean(),
            "Loss/policy_loss_exploration": policy_loss_expl,
            "Loss/policy_loss_task": policy_loss_task,
            "Loss/value_loss_task": value_loss_task,
            "Grads/world_model": optax.global_norm(wm_grads),
            "Grads/actor_task": optax.global_norm(actor_task_grads),
            "Grads/critic_task": optax.global_norm(ct_grads),
            "Grads/actor_exploration": optax.global_norm(actor_expl_grads),
            "Grads/ensemble": optax.global_norm(ens_grads),
            **critic_metrics,
        }
        return state, opt_states, moments, metrics, next_key

    return train_step


@register_algorithm(name="p2e_dv3_exploration")
def main(runtime, cfg: Dict[str, Any]):
    mesh = runtime.mesh
    rank = runtime.global_rank
    world_size = jax.process_count()

    state_ckpt = None
    if cfg.checkpoint.resume_from:
        state_ckpt = load_checkpoint(cfg.checkpoint.resume_from)

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
            state_ckpt["target_critic_task"] if state_ckpt is not None else None,
            state_ckpt["actor_exploration"] if state_ckpt is not None else None,
            state_ckpt["critics_exploration"] if state_ckpt is not None else None,
        )
        critic_names = sorted(agent.critics_exploration)

        txs = {
            "world_model": _make_optimizer(cfg.algo.world_model.optimizer, cfg.algo.world_model.clip_gradients),
            "actor_task": _make_optimizer(cfg.algo.actor.optimizer, cfg.algo.actor.clip_gradients),
            "critic_task": _make_optimizer(cfg.algo.critic.optimizer, cfg.algo.critic.clip_gradients),
            "actor_exploration": _make_optimizer(cfg.algo.actor.optimizer, cfg.algo.actor.clip_gradients),
            "critics_exploration": _make_optimizer(cfg.algo.critic.optimizer, cfg.algo.critic.clip_gradients),
            "ensembles": _make_optimizer(cfg.algo.ensembles.optimizer, cfg.algo.ensembles.clip_gradients),
        }
        opt_states = {
            "world_model": txs["world_model"].init(agent_state["world_model"]),
            "actor_task": txs["actor_task"].init(agent_state["actor_task"]),
            "critic_task": txs["critic_task"].init(agent_state["critic_task"]),
            "actor_exploration": txs["actor_exploration"].init(agent_state["actor_exploration"]),
            "ensembles": txs["ensembles"].init(agent_state["ensembles"]),
            "critics_exploration": {
                k: txs["critics_exploration"].init(agent_state["critics_exploration"][k]["module"])
                for k in critic_names
            },
        }
        if state_ckpt is not None:
            for name, ckpt_key in (
                ("world_model", "world_optimizer"),
                ("actor_task", "actor_task_optimizer"),
                ("critic_task", "critic_task_optimizer"),
                ("actor_exploration", "actor_exploration_optimizer"),
                ("ensembles", "ensemble_optimizer"),
            ):
                opt_states[name] = restore_opt_state(opt_states[name], state_ckpt[ckpt_key])
            for k in critic_names:
                opt_states["critics_exploration"][k] = restore_opt_state(
                    opt_states["critics_exploration"][k], state_ckpt["critics_exploration_optimizer"][k]
                )

    agent_state = runtime.shard_params(agent_state)
    opt_states = runtime.shard_params(opt_states)

    moments = {
        "task": init_moments(),
        "exploration": {k: init_moments() for k in critic_names},
    }
    if state_ckpt is not None and "moments" in state_ckpt:
        moments = jax.tree_util.tree_map(jnp.asarray, state_ckpt["moments"])

    if runtime.is_global_zero:
        save_configs(cfg, log_dir)

    aggregator = None
    if not MetricAggregator.disabled:
        aggregator: MetricAggregator = instantiate(cfg.metric.aggregator)
        # Expand the per-critic template metrics (reference: the exp config's
        # note — '<metric_key>_<critic_key>' instantiation, cli.py:168-181).
        for template in (
            "Loss/value_loss_exploration",
            "Values_exploration/predicted_values",
            "Values_exploration/lambda_values",
            "Grads/critic_exploration",
            "Rewards/intrinsic",
        ):
            if template in aggregator:
                metric = aggregator.metrics[template]
                aggregator.pop(template)
                for k in critic_names:
                    aggregator.add(f"{template}_{k}", copy.deepcopy(metric))

    buffer_size = cfg.buffer.size // int(cfg.env.num_envs * world_size) if not cfg.dry_run else 2
    rb = EnvIndependentReplayBuffer(
        buffer_size,
        n_envs=cfg.env.num_envs,
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

    train_fn = make_train_step(agent, txs, cfg, mesh)
    player_cnn_keys = tuple(cfg.algo.cnn_keys.encoder)

    def _player_step(wm, a, s, o, k):
        # PRNG split + obs normalization in-graph: ONE dispatch per env step.
        next_k, sub = jax.random.split(k)
        out = agent.dv3.player_step(
            wm, a, s, normalize_player_obs(o, player_cnn_keys), sub, greedy=False
        )
        return (*out, next_k)

    player_step_fn = jax.jit(_player_step)
    init_player_fn = jax.jit(agent.dv3.init_player_state, static_argnums=(1,))
    reset_player_fn = jax.jit(agent.dv3.reset_player_state)
    # The player follows the configured actor (reference: agent.py:213-218).
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

        pending = None
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
                    pp = placement.params()
                    actions_cat, real_actions_j, player_state, rollout_key = player_step_fn(
                        pp["world_model"], pp["actor"], player_state, np_obs, rollout_key
                    )
                # One host fetch for both arrays: each separate np.asarray
                # is a full device->host roundtrip that blocks the
                # host. Submitted at dispatch, harvested at the use site.
                pending = pipeline.fetch((actions_cat, real_actions_j), label="player_actions")

            if pending is not None:
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
                            agent_state, opt_states, moments, train_metrics, train_key = train_fn(
                                agent_state, opt_states, moments, batch, train_key,
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
                "target_critic_task": agent_state["target_critic_task"],
                "actor_exploration": agent_state["actor_exploration"],
                "critics_exploration": agent_state["critics_exploration"],
                "ensembles": agent_state["ensembles"],
                "world_optimizer": opt_states["world_model"],
                "actor_task_optimizer": opt_states["actor_task"],
                "critic_task_optimizer": opt_states["critic_task"],
                "actor_exploration_optimizer": opt_states["actor_exploration"],
                "ensemble_optimizer": opt_states["ensembles"],
                "critics_exploration_optimizer": opt_states["critics_exploration"],
                "moments": moments,
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
        # Test with the configured player actor (exploration by default).
        test(
            agent.dv3,
            {"world_model": agent_state["world_model"], "actor": agent_state[player_actor_key]},
            runtime,
            cfg,
            log_dir,
            logger,
            sample_actions=True,
        )

    guard.close()
    telemetry.close()
    if logger is not None:
        logger.close()
