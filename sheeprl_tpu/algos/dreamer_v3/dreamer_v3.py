"""DreamerV3 training loop (reference: sheeprl/algos/dreamer_v3/dreamer_v3.py).

TPU-first structure (SURVEY §3.3 / §7.2):
- Dynamic learning: the RSSM runs as ONE `lax.scan` over the sequence axis
  (the reference python-loops per-step GRU cells, dreamer_v3.py:134-145) —
  carry = (h, z), stacked outputs (h_t, z_t, logits). Its backward pass is
  its own (models/deferred_wgrad.py): the backward scan carries the
  cotangents of (h, z) and of the small leaves only, stacks every Dense's
  output cotangent over time, and each Dense kernel's gradient is one
  contraction over time and batch after the scan — not a kernel-sized
  float32 carry read and written T times. The learned initial state and its
  prior mode are computed once per sequence, outside the scan.
- Behaviour learning: imagination is a second `lax.scan` over the horizon
  starting from every (t, b) posterior flattened to one batch, with per-step
  PRNG keys for actor sampling.
- λ-returns: reverse scan (ops.compute_lambda_values); Moments state is a
  pytree threaded through the jitted step, its quantile a global reduction
  under the mesh sharding.
- The whole gradient step (world model + actor + critic, three optax
  optimizers with clipping) is ONE jitted, donated call; the target-critic
  EMA cadence stays on host (tau passed as a traced scalar, 0 = no-op).
"""

from __future__ import annotations

import copy
import os
import warnings
from functools import partial
from typing import Any, Dict, Sequence

import gymnasium as gym
import jax
import jax.numpy as jnp
import numpy as np
import optax

from sheeprl_tpu.algos.dreamer_v3.agent import (
    DV3Agent,
    WorldModel,
    actor_forward,
    build_agent,
    continuous_log_prob_and_entropy,
)
from sheeprl_tpu.algos.dreamer_v3.loss import reconstruction_loss
from sheeprl_tpu.algos.dreamer_v3.utils import normalize_player_obs, prepare_obs, test
from sheeprl_tpu.algos.ppo.agent import actions_metadata
from sheeprl_tpu.config.instantiate import instantiate, locate
from sheeprl_tpu.core.interact import InteractionPipeline
from sheeprl_tpu.core.resilience import watch
from sheeprl_tpu.core import mesh as mesh_lib
from sheeprl_tpu.core.mesh import DATA_AXIS
from sheeprl_tpu.core.player import PlayerPlacement
from sheeprl_tpu.data.infeed import ReplayInfeed
from sheeprl_tpu.data.buffers import EnvIndependentReplayBuffer, SequentialReplayBuffer
from sheeprl_tpu.data.device_buffer import DeviceReplayRing
from sheeprl_tpu.core.runtime import DispatchThrottle
from sheeprl_tpu.models.deferred_wgrad import scan_deferred_wgrad
from sheeprl_tpu.registry import register_algorithm
from sheeprl_tpu.telemetry import scopes
from sheeprl_tpu.telemetry import tracer as tracer_mod
from sheeprl_tpu.telemetry.health import health_probe, probes_enabled
from sheeprl_tpu.utils.checkpoint import load_checkpoint, restore_opt_state, save_checkpoint
from sheeprl_tpu.utils.distribution import (
    BernoulliSafeMode,
    Independent,
    MSEDistribution,
    OneHotCategorical,
    SymlogDistribution,
    TwoHotEncodingDistribution,
)
from sheeprl_tpu.utils.env import make_vector_env
from sheeprl_tpu.utils.logger import get_log_dir, get_logger
from sheeprl_tpu.utils.metric import MetricAggregator
from sheeprl_tpu.utils.ops import compute_lambda_values, init_moments, update_moments
from sheeprl_tpu.utils.timer import timer
from sheeprl_tpu.utils.utils import Ratio, save_configs


def _make_optimizer(optim_cfg: Dict[str, Any], clip: float) -> optax.GradientTransformation:
    optim_cfg = dict(optim_cfg)
    target = optim_cfg.pop("_target_")
    inner = locate(target)(**optim_cfg)
    if clip is not None and clip > 0:
        return optax.chain(optax.clip_by_global_norm(clip), inner)
    return inner


def _report_deferred_wgrad(n_kernels: int, float32_bytes: int) -> None:
    """Gauges of what the dynamics scan leaves to the contractions after its
    backward pass (models/deferred_wgrad.py). A fact of the trace, stated
    once per trace: no per-step counter exists to read."""
    tracer = tracer_mod.current()
    tracer.set_gauge("train/deferred_wgrad_leaves", float(n_kernels))
    tracer.set_gauge("train/deferred_wgrad_bytes", float(float32_bytes))


def partition_specs(mesh) -> mesh_lib.PartitionPlan:
    """DreamerV3's mesh partitioning: time-major ``[T, B, ...]`` batches are
    sharded over the batch axis (``data``), params follow the wide-param rule
    (tensor-parallel over ``model`` when enabled, replicated otherwise)."""
    from jax.sharding import PartitionSpec as P

    return mesh_lib.default_partition_plan(mesh, batch_specs={"batch": P(None, DATA_AXIS)})


def _explicit_shardings(plan, state, opt_states, data_sharding):
    """in/out_shardings for the 6-arg dreamer train jits.

    Positional layout: (state, opt_states, moments_state, data-or-ring, key,
    tau-or-taus) -> (state, opt_states, moments_state, metrics, next_key).
    Param/opt entries mirror the *actual* placement of the already-sharded
    trees so compilation never inserts a resharding copy; the moments pytree
    and PRNG keys are replicated scalars."""
    state_sh = mesh_lib.tree_shardings(state)
    opt_sh = mesh_lib.tree_shardings(opt_states)
    repl = plan.replicated()
    return dict(
        in_shardings=(state_sh, opt_sh, repl, data_sharding, repl, repl),
        out_shardings=(state_sh, opt_sh, repl, None, repl),
    )


def make_world_loss_fn(agent: DV3Agent, cfg: Dict[str, Any]):
    """Build ``world_loss_fn(wm_params, data, batch_obs, keys) -> (loss, aux)``:
    the world model's loss over a [T, B] batch (encoder, the dynamics-learning
    scan, heads and KL), the function :func:`make_step_core` differentiates."""
    wm_cfg = cfg.algo.world_model
    cnn_dec_keys = list(cfg.algo.cnn_keys.decoder)
    mlp_dec_keys = list(cfg.algo.mlp_keys.decoder)
    stochastic_size = int(wm_cfg.stochastic_size)
    discrete_size = int(wm_cfg.discrete_size)
    stoch_state_size = stochastic_size * discrete_size
    recurrent_state_size = int(wm_cfg.recurrent_model.recurrent_state_size)
    decoupled = bool(wm_cfg.decoupled_rssm)

    def world_loss_fn(wm_params, data, batch_obs, keys):
        T, B = data["rewards"].shape[:2]
        with scopes.scope(scopes.DV3_ENCODER):
            embedded = agent.wm(wm_params, batch_obs, method="embed_obs")  # [T, B, E]

        with scopes.scope(scopes.DV3_RSSM):
            batch_actions = jnp.concatenate(
                [jnp.zeros_like(data["actions"][:1]), data["actions"][:-1]], axis=0
            )
            is_first = data["is_first"].at[0].set(1.0)

            h0 = jnp.zeros((B, recurrent_state_size), embedded.dtype)
            z0 = jnp.zeros((B, stoch_state_size), embedded.dtype)
            step_keys, post_key = keys[:T], keys[T]
            # The learned initial state and its prior mode do not depend on the
            # carry: computed once here, not in every step of the scan.
            initial_states = agent.world_model.apply(wm_params, (B,), method=WorldModel.get_initial_states)

            if decoupled:
                # Decoupled RSSM (reference: dreamer_v3.py:115-130): posteriors are
                # obs-only, computed for the WHOLE sequence in one batched matmul;
                # the scan then only threads the recurrent state, feeding each step
                # the previous step's posterior.
                posteriors_logits, posteriors = agent.world_model.apply(
                    wm_params, embedded, post_key, method=WorldModel.posterior_obs_only
                )
                prev_posteriors = jnp.concatenate([jnp.zeros_like(posteriors[:1]), posteriors[:-1]], 0)

                def dstep(variables, h, x):
                    params, initial_states = variables
                    z_prev, action, first, key = x
                    h, _, prior_logits = agent.world_model.apply(
                        params, z_prev, h, action, first, key, initial_states, method=WorldModel.dynamic_decoupled
                    )
                    return h, (h, prior_logits)

                _, (recurrent_states, priors_logits) = scan_deferred_wgrad(
                    dstep,
                    (wm_params, initial_states),
                    h0,
                    (prev_posteriors, batch_actions, is_first, step_keys),
                    given={"0/params/transition_model/dense_0/kernel": lambda ys, xs: ys[0]},
                    report=_report_deferred_wgrad,
                )
            else:

                def step(variables, carry, x):
                    params, initial_states = variables
                    h, z = carry
                    action, emb, first, key = x
                    h, post, prior, post_logits, prior_logits = agent.world_model.apply(
                        params, z, h, action, emb, first, key, initial_states, method=WorldModel.dynamic
                    )
                    return (h, post), (h, post, post_logits, prior_logits)

                (_, _), (recurrent_states, posteriors, posteriors_logits, priors_logits) = scan_deferred_wgrad(
                    step,
                    (wm_params, initial_states),
                    (h0, z0),
                    (batch_actions, embedded, is_first, step_keys),
                    # The transition model reads h_t and the representation model
                    # [h_t | embedded_t]: the scan returns the one and is given the
                    # other, so neither is stacked a second time.
                    given={
                        "0/params/transition_model/dense_0/kernel": lambda ys, xs: ys[0],
                        "0/params/representation_model/dense_0/kernel": lambda ys, xs: jnp.concatenate(
                            [ys[0], xs[1]], -1
                        ),
                    },
                    report=_report_deferred_wgrad,
                )
        with scopes.scope(scopes.DV3_HEADS):
            latent_states = jnp.concatenate([posteriors, recurrent_states], -1)

            reconstructed_obs = agent.wm(wm_params, latent_states, method="decode")
            po = {
                k: MSEDistribution(reconstructed_obs[k], dims=len(reconstructed_obs[k].shape[2:]))
                for k in cnn_dec_keys
            }
            po.update(
                {
                    k: SymlogDistribution(reconstructed_obs[k], dims=len(reconstructed_obs[k].shape[2:]))
                    for k in mlp_dec_keys
                }
            )
            pr = TwoHotEncodingDistribution(agent.wm(wm_params, latent_states, method="reward_logits"), dims=1)
            pc = Independent(
                BernoulliSafeMode(logits=agent.wm(wm_params, latent_states, method="continue_logits")), 1
            )
            continues_targets = 1 - data["terminated"]

            pl = priors_logits.reshape(*priors_logits.shape[:-1], stochastic_size, discrete_size)
            pol = posteriors_logits.reshape(*posteriors_logits.shape[:-1], stochastic_size, discrete_size)
            rec_loss, kl, state_loss, reward_loss, observation_loss, continue_loss = reconstruction_loss(
                po,
                batch_obs,
                pr,
                data["rewards"],
                pl,
                pol,
                wm_cfg.kl_dynamic,
                wm_cfg.kl_representation,
                wm_cfg.kl_free_nats,
                wm_cfg.kl_regularizer,
                pc,
                continues_targets,
                wm_cfg.continue_scale_factor,
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

    return world_loss_fn


def make_step_core(agent: DV3Agent, txs: Dict[str, optax.GradientTransformation], cfg: Dict[str, Any], mesh):
    """Build the PURE single-gradient-step function over a [T, B] batch.

    Not jitted and no internal key split: :func:`make_train_step` wraps it
    into the classic one-dispatch-per-step jit, and
    :func:`make_fused_train_step` scans it over K on-device-sampled batches
    inside one jitted call. Both share this trace so they optimise the same
    math."""
    from jax.sharding import NamedSharding, PartitionSpec as P

    wm_cfg = cfg.algo.world_model
    cnn_keys = list(cfg.algo.cnn_keys.encoder)
    mlp_keys = list(cfg.algo.mlp_keys.encoder)
    stoch_state_size = int(wm_cfg.stochastic_size) * int(wm_cfg.discrete_size)
    recurrent_state_size = int(wm_cfg.recurrent_model.recurrent_state_size)
    horizon = int(cfg.algo.horizon)
    gamma = float(cfg.algo.gamma)
    lmbda = float(cfg.algo.lmbda)
    ent_coef = float(cfg.algo.actor.ent_coef)
    moments_cfg = cfg.algo.actor.moments
    spec = agent.actor_spec
    actions_dim = agent.actions_dim

    batch_sharding = NamedSharding(mesh, P(None, DATA_AXIS))
    world_loss_fn = make_world_loss_fn(agent, cfg)

    def step_core(state, opt_states, moments_state, data, key, tau):
        T, B = data["rewards"].shape[:2]
        data = jax.lax.with_sharding_constraint(data, {k: batch_sharding for k in data})
        with scopes.scope(scopes.DV3_ENCODER):
            batch_obs = {k: data[k] / 255.0 - 0.5 for k in cnn_keys}
            batch_obs.update({k: data[k] for k in mlp_keys})

        k_dyn, k_img0, k_img, k_actor = jax.random.split(key, 4)
        # T per-step keys + one extra for the decoupled whole-sequence posterior
        dyn_keys = jax.random.split(k_dyn, T + 1)

        # ---------------------------------------------- world model update
        (rec_loss, aux), wm_grads = jax.value_and_grad(world_loss_fn, has_aux=True)(
            state["world_model"], data, batch_obs, dyn_keys
        )
        with scopes.scope(scopes.DV3_OPTIM):
            wm_updates, wm_opt = txs["world_model"].update(
                wm_grads, opt_states["world_model"], state["world_model"]
            )
            state["world_model"] = optax.apply_updates(state["world_model"], wm_updates)

        # --------------------------------------------- behaviour learning
        sg = jax.lax.stop_gradient
        imagined_prior = sg(aux["posteriors"]).reshape(-1, stoch_state_size)
        recurrent_state = sg(aux["recurrent_states"]).reshape(-1, recurrent_state_size)
        latent0 = jnp.concatenate([imagined_prior, recurrent_state], -1)

        def actor_sample(actor_params, latent, k):
            pre = agent.actor.apply(actor_params, sg(latent))
            actions, _ = actor_forward(pre, spec, k, greedy=False)
            return jnp.concatenate(actions, -1)

        def imagine_loss_fn(actor_params):
            # Imagination rollout (actions re-sampled from the CURRENT actor
            # params so the pathwise gradient flows; reference does the same
            # through in-place module weights, dreamer_v3.py:219-241).
            with scopes.scope(scopes.DV3_IMAGINE):
                a0 = actor_sample(actor_params, latent0, k_img0)

                def img_step(carry, k):
                    prior, h, actions = carry
                    k_wm, k_act = jax.random.split(k)
                    prior, h = agent.world_model.apply(
                        state["world_model"], prior, h, actions, k_wm, method=WorldModel.imagination
                    )
                    latent = jnp.concatenate([prior, h], -1)
                    next_actions = actor_sample(actor_params, latent, k_act)
                    return (prior, h, next_actions), (latent, next_actions)

                img_keys = jax.random.split(k_img, horizon)
                _, (latents, img_actions) = jax.lax.scan(
                    img_step, (imagined_prior, recurrent_state, a0), img_keys
                )
                imagined_trajectories = jnp.concatenate([latent0[None], latents], 0)  # [H+1, TB, L]
                imagined_actions = jnp.concatenate([a0[None], img_actions], 0)

            with scopes.scope(scopes.DV3_ACTOR_CRITIC):
                # Predict values / rewards / continues on the imagined rollout
                predicted_values = TwoHotEncodingDistribution(
                    agent.critic_logits(state["critic"], imagined_trajectories), dims=1
                ).mean
                predicted_rewards = TwoHotEncodingDistribution(
                    agent.wm(state["world_model"], imagined_trajectories, method="reward_logits"), dims=1
                ).mean
                continues = Independent(
                    BernoulliSafeMode(
                        logits=agent.wm(state["world_model"], imagined_trajectories, method="continue_logits")
                    ),
                    1,
                ).mode
                true_continue = (1 - data["terminated"]).reshape(1, -1, 1)
                continues = jnp.concatenate([true_continue, continues[1:]], 0)

                lambda_values = compute_lambda_values(
                    predicted_rewards[1:], predicted_values[1:], continues[1:] * gamma, lmbda
                )
                discount = sg(jnp.cumprod(continues * gamma, 0) / gamma)

                # Actor objective (reference: dreamer_v3.py:262-297)
                new_moments, (offset, invscale) = update_moments(
                    moments_state,
                    lambda_values,
                    decay=moments_cfg.decay,
                    max_=moments_cfg.max,
                    percentile_low=moments_cfg.percentile.low,
                    percentile_high=moments_cfg.percentile.high,
                )
                baseline = predicted_values[:-1]
                normed_lambda_values = (lambda_values - offset) / invscale
                normed_baseline = (baseline - offset) / invscale
                advantage = normed_lambda_values - normed_baseline

                pre = agent.actor.apply(actor_params, sg(imagined_trajectories))
                _, policies = actor_forward(pre, spec, k_actor, greedy=False)
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
                policy_loss = -jnp.mean(sg(discount[:-1]) * (objective + entropy[..., None][:-1]))
            img_aux = {
                "imagined_trajectories": sg(imagined_trajectories),
                "lambda_values": sg(lambda_values),
                "discount": discount,
                "moments": new_moments,
            }
            return policy_loss, img_aux

        (policy_loss, img_aux), actor_grads = jax.value_and_grad(imagine_loss_fn, has_aux=True)(
            state["actor"]
        )
        with scopes.scope(scopes.DV3_OPTIM):
            actor_updates, actor_opt = txs["actor"].update(actor_grads, opt_states["actor"], state["actor"])
            state["actor"] = optax.apply_updates(state["actor"], actor_updates)

        # ------------------------------------------------- critic update
        traj = img_aux["imagined_trajectories"][:-1]
        lambda_values = img_aux["lambda_values"]
        discount = img_aux["discount"]
        with scopes.scope(scopes.DV3_ACTOR_CRITIC):
            predicted_target_values = TwoHotEncodingDistribution(
                agent.critic_logits(state["target_critic"], traj), dims=1
            ).mean

        def critic_loss_fn(critic_params):
            with scopes.scope(scopes.DV3_ACTOR_CRITIC):
                qv = TwoHotEncodingDistribution(agent.critic_logits(critic_params, traj), dims=1)
                value_loss = -qv.log_prob(lambda_values)
                value_loss = value_loss - qv.log_prob(sg(predicted_target_values))
                return jnp.mean(value_loss * discount[:-1].squeeze(-1))

        value_loss, critic_grads = jax.value_and_grad(critic_loss_fn)(state["critic"])
        with scopes.scope(scopes.DV3_OPTIM):
            critic_updates, critic_opt = txs["critic"].update(
                critic_grads, opt_states["critic"], state["critic"]
            )
            state["critic"] = optax.apply_updates(state["critic"], critic_updates)

            # target critic EMA (host decides tau; 0 = frozen)
            state["target_critic"] = jax.tree_util.tree_map(
                lambda p, tp: tau * p + (1 - tau) * tp, state["critic"], state["target_critic"]
            )

        opt_states = {"world_model": wm_opt, "actor": actor_opt, "critic": critic_opt}
        with scopes.scope(scopes.DV3_HEADS):
            post_entropy = Independent(OneHotCategorical(logits=aux["posteriors_logits"]), 1).entropy().mean()
            prior_entropy = Independent(OneHotCategorical(logits=aux["priors_logits"]), 1).entropy().mean()
        with scopes.scope(scopes.DV3_OPTIM):
            grad_norms = {
                "Grads/world_model": optax.global_norm(wm_grads),
                "Grads/actor": optax.global_norm(actor_grads),
                "Grads/critic": optax.global_norm(critic_grads),
            }
        metrics = {
            "Loss/world_model_loss": rec_loss,
            "Loss/observation_loss": aux["observation_loss"],
            "Loss/reward_loss": aux["reward_loss"],
            "Loss/state_loss": aux["state_loss"],
            "Loss/continue_loss": aux["continue_loss"],
            "State/kl": aux["kl"],
            "State/post_entropy": post_entropy,
            "State/prior_entropy": prior_entropy,
            "Loss/policy_loss": policy_loss,
            "Loss/value_loss": value_loss,
            **grad_norms,
        }
        if probes_enabled(cfg):
            # In-jit health probe: pure reductions over the already-live grad
            # and update trees, riding the StepTimer's coalesced interval
            # transfer (zero extra host syncs).
            metrics.update(
                health_probe(
                    params=(state["world_model"], state["actor"], state["critic"]),
                    grads=(wm_grads, actor_grads, critic_grads),
                    updates=(wm_updates, actor_updates, critic_updates),
                    aux={"kl": aux["kl"]},
                )
            )
        return state, opt_states, img_aux["moments"], metrics

    return step_core


def make_train_step(
    agent: DV3Agent,
    txs: Dict[str, optax.GradientTransformation],
    cfg: Dict[str, Any],
    mesh,
    state=None,
    opt_states=None,
):
    """Build the jitted single-gradient-step function over a [T, B] batch.

    When the already-placed ``state``/``opt_states`` trees are passed, the jit
    compiles with explicit ``in_shardings``/``out_shardings``: params/opt keep
    their recorded layouts and the [T, B] batch is sharded over ``data`` on its
    batch axis, so the gradient step is data-parallel end to end."""
    step_core = make_step_core(agent, txs, cfg, mesh)

    plan = partition_specs(mesh)
    jit_kwargs = {}
    if (
        state is not None
        and opt_states is not None
        and int(cfg.algo.per_rank_batch_size) % plan.data_size == 0
    ):
        jit_kwargs = _explicit_shardings(plan, state, opt_states, plan.sharding("batch"))

    @partial(jax.jit, donate_argnums=(0, 1, 2), **jit_kwargs)
    def train_step(state, opt_states, moments_state, data, key, tau):
        next_key, key = jax.random.split(key)
        state, opt_states, moments_state, metrics = step_core(
            state, opt_states, moments_state, data, key, tau
        )
        return state, opt_states, moments_state, metrics, next_key

    return train_step


def make_fused_train_step(
    agent: DV3Agent,
    txs: Dict[str, optax.GradientTransformation],
    cfg: Dict[str, Any],
    mesh,
    sample_fn,
    state=None,
    opt_states=None,
    ring_shardings=None,
):
    """Fuse K gradient steps (sampling included) into ONE jitted lax.scan.

    ``sample_fn`` is a :meth:`DeviceReplayRing.make_sample_fn` pure sampler:
    each scan iteration draws its own batch from the device-resident ring
    with the JAX PRNG, so the host ships zero batch bytes and pays one
    dispatch for the whole bucket. K is carried by ``taus``'s length (the
    per-step target-EMA coefficients the host already computes), so each
    power-of-two bucket compiles exactly once.
    """
    step_core = make_step_core(agent, txs, cfg, mesh)

    plan = partition_specs(mesh)
    jit_kwargs = {}
    if (
        state is not None
        and opt_states is not None
        and int(cfg.algo.per_rank_batch_size) % plan.data_size == 0
    ):
        # ring_shardings (DeviceReplayRing.state_shardings()) pins the ring
        # tree to its sharded-over-envs placement; None leaves it free.
        jit_kwargs = _explicit_shardings(plan, state, opt_states, ring_shardings)

    @partial(jax.jit, donate_argnums=(0, 1, 2), **jit_kwargs)
    def fused_train_step(state, opt_states, moments_state, ring_state, key, taus):
        next_key, key = jax.random.split(key)
        step_keys = jax.random.split(key, taus.shape[0])

        def body(carry, x):
            state, opt_states, moments_state = carry
            k, tau = x
            k_sample, k_core = jax.random.split(k)
            data = sample_fn(ring_state, k_sample)
            state, opt_states, moments_state, metrics = step_core(
                state, opt_states, moments_state, data, k_core, tau
            )
            return (state, opt_states, moments_state), metrics

        (state, opt_states, moments_state), metrics = jax.lax.scan(
            body, (state, opt_states, moments_state), (step_keys, taus)
        )
        metrics = jax.tree_util.tree_map(lambda m: m.mean(0), metrics)
        return state, opt_states, moments_state, metrics, next_key

    return fused_train_step


def _target_update_taus(cumulative: int, k: int, freq: int, tau: float) -> np.ndarray:
    """Per-step target-critic EMA coefficients for a K-step fused bucket,
    reproducing the host loop's cadence: hard copy (1.0) on the very first
    gradient step, ``tau`` every ``freq`` cumulative steps, else 0."""
    taus = np.zeros(k, np.float32)
    for i in range(k):
        c = cumulative + i
        if c % freq == 0:
            taus[i] = 1.0 if c == 0 else tau
    return taus


@register_algorithm()
def main(runtime, cfg: Dict[str, Any]):
    from sheeprl_tpu.core.fused_loop import dreamer_v3_fused_main, fused_enabled

    if fused_enabled(cfg):
        # Anakin lane: pure-JAX env, rollout AND train inside one jit
        # (core/fused_loop.py). The host-interaction path below is untouched.
        return dreamer_v3_fused_main(runtime, cfg)

    mesh = runtime.mesh
    rank = runtime.global_rank
    world_size = jax.process_count()

    state_ckpt = None
    if cfg.checkpoint.resume_from:
        state_ckpt = load_checkpoint(cfg.checkpoint.resume_from)

    # These arguments cannot be changed
    cfg.env.frame_stack = -1
    if 2 ** int(np.log2(cfg.env.screen_size)) != cfg.env.screen_size:
        raise ValueError(f"The screen size must be a power of 2, got: {cfg.env.screen_size}")

    logger = get_logger(runtime, cfg)
    if logger is not None:
        logger.log_hyperparams(cfg.as_dict() if hasattr(cfg, "as_dict") else dict(cfg))
    log_dir = get_log_dir(runtime, cfg.root_dir, cfg.run_name, logger=logger)
    runtime.print(f"Log dir: {log_dir}")
    telemetry = runtime.telemetry.open(log_dir, rank_zero=runtime.is_global_zero, device=runtime.device)
    guard = runtime.resilience.guard(rank_zero=runtime.is_global_zero)
    watchdog = runtime.resilience.watchdog
    health = runtime.health

    with telemetry.span("setup/envs", "setup"):
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
        runtime.print("Decoder CNN keys:", cfg.algo.cnn_keys.decoder)
        runtime.print("Decoder MLP keys:", cfg.algo.mlp_keys.decoder)
    obs_keys = list(cfg.algo.cnn_keys.encoder) + list(cfg.algo.mlp_keys.encoder)

    # Eager flax/optax init runs host-side (each eager dispatch pays a host-device round trip); shard_params then moves the finished trees to the mesh.
    with telemetry.span("setup/agent", "setup"):
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
                state_ckpt["target_critic"] if state_ckpt is not None else None,
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

            # Explicit mesh placement: replicated, or tensor-parallel over the model
            # axis for the wide dense stacks when fabric.model_axis > 1.
        agent_state = runtime.shard_params(agent_state)
        opt_states = runtime.shard_params(opt_states)

    # Arm per-shard goodput accounting: the observatory needs the mesh and the
    # realised param layouts to attribute MFU/imbalance per data-shard.
    telemetry.set_mesh(mesh)
    telemetry.record_param_layouts(agent_state)

    moments_state = init_moments()
    if state_ckpt is not None and "moments" in state_ckpt:
        moments_state = jax.tree_util.tree_map(jnp.asarray, state_ckpt["moments"])

    if runtime.is_global_zero:
        save_configs(cfg, log_dir)

    aggregator = None
    if not MetricAggregator.disabled:
        aggregator: MetricAggregator = instantiate(cfg.metric.aggregator)

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

    train_fn = make_train_step(agent, txs, cfg, mesh, state=agent_state, opt_states=opt_states)

    with telemetry.span("setup/replay", "setup"):
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

        # Device-resident replay ring (data/device_buffer.py): rollout rows are
        # mirrored into HBM and the fused train step samples them inside its own
        # jit — zero per-gradient-step host transfers. The host buffer stays
        # authoritative (checkpointing, fallback when the ring won't fit HBM).
        use_device_buffer = bool(cfg.buffer.get("device", False))
        fused_train_steps = max(int(cfg.algo.get("fused_train_steps", 1)), 1)
        ring = None
        fused_train_fn = None
        if use_device_buffer:
            ring = DeviceReplayRing(
                buffer_size,
                cfg.env.num_envs,
                cnn_keys=tuple(cfg.algo.cnn_keys.encoder),
                obs_keys=tuple(obs_keys),
                hbm_fraction=float(cfg.buffer.get("device_hbm_fraction", 0.4)),
                device=mesh.devices.flat[0],
                mesh=mesh,
            )
            if state_ckpt is not None and cfg.buffer.checkpoint and state_ckpt.get("rb") is not None:
                ring.load_host_buffer(rb)
            ring_sample_fn = ring.make_sample_fn(
                cfg.algo.per_rank_batch_size,
                sequence_length=cfg.algo.per_rank_sequence_length,
                time_major=True,
            )
            fused_train_fn = make_fused_train_step(
                agent,
                txs,
                cfg,
                mesh,
                ring_sample_fn,
                state=agent_state,
                opt_states=opt_states,
                ring_shardings=ring.state_shardings(),
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

    player_cnn_keys = tuple(cfg.algo.cnn_keys.encoder)

    def _player_step(wm, a, s, o, k):
        # PRNG split + obs normalization in-graph: ONE dispatch per env step.
        with scopes.scope(scopes.DV3_ACT):
            next_k, sub = jax.random.split(k)
            out = agent.player_step(
                wm, a, s, normalize_player_obs(o, player_cnn_keys), sub, greedy=False
            )
        return (*out, next_k)

    player_step_fn = jax.jit(_player_step)
    init_player_fn = jax.jit(agent.init_player_state, static_argnums=(1,))
    reset_player_fn = jax.jit(agent.reset_player_state)

    # Latency-aware player placement (core/player.py): the encoder->GRU->
    # posterior->actor per-step forward runs where dispatch is cheapest; the
    # mirror refreshes world-model+actor after every train call. Off-policy:
    # honors fabric.player_sync=async.
    with telemetry.span("setup/player", "setup"):
        placement = PlayerPlacement.resolve(
            cfg, mesh.devices.flat[0],
            params={"world_model": agent_state["world_model"], "actor": agent_state["actor"]},
        )
        placement.push({"world_model": agent_state["world_model"], "actor": agent_state["actor"]})

        rollout_key, train_key = jax.random.split(jax.random.fold_in(runtime.root_key, rank))
        rollout_key = placement.put(rollout_key)

        # Pipelined interaction (core/interact.py): per-slice policy dispatch +
        # async action fetch + double-buffered obs staging, with the recurrent
        # player latents and the rollout PRNG key held per slice. slices=1/async
        # off is bit-identical to the serial loop.
        pipeline = InteractionPipeline.from_config(cfg)
        pipeline.watchdog = watchdog
        pipeline.set_key(rollout_key)
        with placement.ctx():
            pipeline.init_state(lambda n, _rng: init_player_fn(placement.params()["world_model"], n))
    single_action_shape = envs.single_action_space.shape
    player_cnn_cfg_keys = cfg.algo.cnn_keys.encoder

    def _pipeline_policy(np_obs, state, key):
        with placement.ctx():
            pp = placement.params()
            actions_cat, real_actions_j, new_state, next_key = player_step_fn(
                pp["world_model"], pp["actor"], state, np_obs, key
            )
        # One host fetch for both arrays: each separate np.asarray is a full
        # device->host roundtrip that blocks the host.
        return (actions_cat, real_actions_j), new_state, next_key

    def _prepare_slice(obs_slice, out=None):
        n = len(next(iter(obs_slice.values())))
        return prepare_obs(obs_slice, cnn_keys=player_cnn_cfg_keys, num_envs=n, out=out)

    def _to_env_actions(host_outputs, n_envs):
        return host_outputs[1].reshape((n_envs, *single_action_shape))

    step_data = {}
    obs = pipeline.stash_obs(envs.reset(seed=cfg.seed)[0])
    for k in obs_keys:
        step_data[k] = obs[k][np.newaxis]
    step_data["rewards"] = np.zeros((1, cfg.env.num_envs, 1), np.float32)
    step_data["truncated"] = np.zeros((1, cfg.env.num_envs, 1), np.float32)
    step_data["terminated"] = np.zeros((1, cfg.env.num_envs, 1), np.float32)
    step_data["is_first"] = np.ones_like(step_data["terminated"])

    cumulative_per_rank_gradient_steps = 0
    # Bound async in-flight train dispatches (core/runtime.py: an
    # unbounded queue pins every pending call's sampled batch on host).
    dispatch_throttle = DispatchThrottle()
    # Train losses stay device-resident between log intervals; the StepTimer
    # coalesces them into ONE jax.device_get per interval and bounds the
    # interval's wall-clock with ONE block_until_ready (each sync stalls the
    # host for a full device round trip). Scalars only, so the pinned device
    # memory is negligible.
    train_timer = telemetry.step_timer("train", timer_key="Time/train_time")
    perf = telemetry.perf
    keep_train_metrics = (
        aggregator is not None and not aggregator.disabled and cfg.metric.log_level > 0
    ) or health.enabled

    # The iteration's gradient steps, factored out so the pipelined
    # interaction can dispatch them between the action-fetch submit and its
    # harvest (pipeline.overlap_train): train compute then overlaps the D2H
    # copy and the host env step, at the cost of train batches lagging the
    # buffer by one transition.
    def run_train(iter_num: int) -> None:
        nonlocal agent_state, opt_states, moments_state, train_key
        nonlocal cumulative_per_rank_gradient_steps, train_step_count
        if iter_num < learning_starts:
            return
        ratio_steps = policy_step - prefill_steps * policy_steps_per_iter
        per_rank_gradient_steps = ratio(ratio_steps / world_size)
        if per_rank_gradient_steps > 0:
            # Ship this interval's staged rollout rows in ONE donated
            # write, then (if enough history is device-resident) train
            # entirely from the ring: no host sampling, no per-step H2D.
            if ring is not None and ring.active:
                ring.flush()
            use_ring = (
                ring is not None
                and ring.active
                and ring.ready(cfg.algo.per_rank_sequence_length)
            )
            if use_ring:
                with timer("Time/train_time"):
                    remaining = per_rank_gradient_steps
                    while remaining > 0:
                        # Power-of-two buckets bound the number of fused
                        # graphs to log2(fused_train_steps).
                        k = 1 << (min(remaining, fused_train_steps).bit_length() - 1)
                        taus = _target_update_taus(
                            cumulative_per_rank_gradient_steps,
                            k,
                            cfg.algo.critic.per_rank_target_network_update_freq,
                            cfg.algo.critic.tau,
                        )
                        # Goodput accounting BEFORE the dispatch: arg shape
                        # specs must be captured while the buffers are alive
                        # (the jit donates them).
                        perf.note(
                            f"train/fused_k{k}", fused_train_fn,
                            (agent_state, opt_states, moments_state, ring.state, train_key, taus),
                            steps=k,
                        )
                        with train_timer.step(k), watch(watchdog, "train_dispatch"):
                            agent_state, opt_states, moments_state, train_metrics, train_key = fused_train_fn(
                                agent_state, opt_states, moments_state, ring.state,
                                train_key, taus,
                            )
                        # Mean losses over the bucket (the scan stacks
                        # them; one tree per dispatch keeps the flush
                        # cheap).
                        train_timer.pend(
                            agent_state["world_model"],
                            train_metrics if keep_train_metrics else None,
                        )
                        dispatch_throttle.add(train_metrics)
                        cumulative_per_rank_gradient_steps += k
                        remaining -= k
                    placement.push(
                        {"world_model": agent_state["world_model"], "actor": agent_state["actor"]}
                    )
                    train_step_count += world_size
            else:
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
                        tau_arr = np.asarray(tau, np.float32)
                        perf.note(
                            "train/step", train_fn,
                            (agent_state, opt_states, moments_state, batch, train_key, tau_arr),
                        )
                        with train_timer.step(), watch(watchdog, "train_dispatch"):
                            agent_state, opt_states, moments_state, train_metrics, train_key = train_fn(
                                agent_state, opt_states, moments_state, batch, train_key, tau_arr,
                            )
                        # Feed EVERY gradient step's losses toward the log
                        # (only sampling the last one under-reports the
                        # training signal). No sync here: the dispatch stays
                        # fully async — the StepTimer queues the scalars
                        # device-side and bounds the interval's wall-clock
                        # with ONE block at the log-interval flush.
                        train_timer.pend(
                            agent_state["world_model"],
                            train_metrics if keep_train_metrics else None,
                        )
                        dispatch_throttle.add(train_metrics)
                        cumulative_per_rank_gradient_steps += 1
                    # One mirror refresh per train call (the player only acts
                    # again after the whole gradient-step loop, so this is
                    # exactly the reference's tied-weights freshness).
                    placement.push(
                        {"world_model": agent_state["world_model"], "actor": agent_state["actor"]}
                    )
                    train_step_count += world_size
                # Sample on the main thread (no buffer race); stage the device
                # copies to overlap the next env-step phase.
                infeed.stage(per_rank_gradient_steps)

    def add_rows(data, env_idxes=None) -> None:
        with telemetry.span("replay/add", "replay"):
            rb.add(data, env_idxes, validate_args=cfg.buffer.validate_args)
            if ring is not None:
                ring.add(data, env_idxes)

    for iter_num in range(start_iter, total_iters + 1):
        policy_step += policy_steps_per_iter
        telemetry.advance(policy_step)
        guard.advance(policy_step)

        trained_in_flight = False
        with timer("Time/env_interaction_time"), perf.infeed():
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
                step_data["actions"] = actions.reshape((1, cfg.env.num_envs, -1))
                add_rows(step_data)
                next_obs, rewards, terminated, truncated, infos = envs.step(
                    real_actions.reshape(envs.action_space.shape)
                )
                next_obs = pipeline.stash_obs(next_obs)
            else:
                # Overlap the train dispatch with the action copy + env step
                # only once the buffer holds the serial order's transitions
                # (train batches then lag the buffer by one step).
                trained_in_flight = pipeline.overlap_train and iter_num > learning_starts + 1
                res = pipeline.interact(
                    envs,
                    obs,
                    _pipeline_policy,
                    prepare=_prepare_slice,
                    to_env_actions=_to_env_actions,
                    before_harvest=(lambda: run_train(iter_num)) if trained_in_flight else None,
                )
                actions, real_actions = res.outputs
                # The buffer row for step t (pre-step obs + the actions just
                # taken) is written after the pipelined env step; nothing in
                # it depends on the step's results, so the contents match the
                # serial order exactly.
                step_data["actions"] = actions.reshape((1, cfg.env.num_envs, -1))
                add_rows(step_data)
                next_obs, rewards, terminated, truncated, infos = (
                    res.obs,
                    res.rewards,
                    res.terminated,
                    res.truncated,
                    res.infos,
                )
            dones = np.logical_or(terminated, truncated).astype(np.uint8)

        step_data["is_first"] = np.zeros_like(step_data["terminated"])
        if "restart_on_exception" in infos:
            for i, agent_roe in enumerate(infos["restart_on_exception"]):
                if agent_roe and not dones[i]:
                    # Patch the broken episode's tail in the buffer: mark it
                    # truncated, restart a fresh episode
                    # (reference: dreamer_v3.py:595-608).
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
                    if ring is not None:
                        ring.amend_last(
                            i,
                            {
                                "terminated": np.zeros((1,), np.float32),
                                "truncated": np.ones((1,), np.float32),
                                "is_first": np.zeros((1,), np.float32),
                            },
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
            add_rows(reset_data, dones_idxes)

            step_data["rewards"][:, dones_idxes] = np.zeros_like(reset_data["rewards"])
            step_data["terminated"][:, dones_idxes] = np.zeros_like(step_data["terminated"][:, dones_idxes])
            step_data["truncated"][:, dones_idxes] = np.zeros_like(step_data["truncated"][:, dones_idxes])
            step_data["is_first"][:, dones_idxes] = np.ones_like(step_data["is_first"][:, dones_idxes])
            reset_mask = np.zeros((cfg.env.num_envs,), np.float32)
            reset_mask[dones_idxes] = 1.0

            def _reset_slice_state(state, slice_range):
                s0, s1 = slice_range
                with placement.ctx():
                    return reset_player_fn(
                        placement.params()["world_model"], state, jnp.asarray(reset_mask[s0:s1])
                    )

            pipeline.map_state(_reset_slice_state)

        # ------------------------------------------------------- training
        if not trained_in_flight:
            run_train(iter_num)

        # -------------------------------------------------------- logging
        should_log = cfg.metric.log_level > 0 and (
            policy_step - last_log >= cfg.metric.log_every or iter_num == total_iters
        )
        if should_log:
            # The interval's ONE bounding block + ONE coalesced device->host
            # transfer of every queued loss tree (StepTimer.flush) — the
            # pattern GL002 asks for, now owned by telemetry.
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
                "target_critic": agent_state["target_critic"],
                "world_optimizer": opt_states["world_model"],
                "actor_optimizer": opt_states["actor"],
                "critic_optimizer": opt_states["critic"],
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
    pipeline.publish()
    infeed.close()
    envs.close()
    if runtime.is_global_zero and cfg.algo.run_test and not guard.preempted:
        test(agent, agent_state, runtime, cfg, log_dir, logger)

    guard.close()
    telemetry.close()
    if logger is not None:
        logger.close()
