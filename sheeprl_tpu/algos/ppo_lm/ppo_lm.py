"""PPO over tokens: a language model as the policy (``algo=ppo_lm``).

One rollout is one generation batch. Every env is reset and hands over its
prompt; the prompts go through **prefill** (the backbone's whole-sequence
form, which fills the per-env cache and draws the first response token),
then ``rollout_steps - 1`` **decode** steps follow, one token per env per
policy step through the cache. An env whose episode has ended reports
``active = 0`` and idles; its further steps carry no loss. The update runs the
left-padded sequences ``[prompt | response]`` through the whole-sequence form
with a loss mask on the active response positions: per-token log-probability
ratio and clip, value loss, GAE per token with the reward at the episode's
last token (`algos/ppo/loss.py`, token means over the minibatch),
``update_epochs`` x ``per_rank_num_batches`` gradient steps, one jitted call
each. There is no KL term to a frozen copy of the policy.

The loop's boundary (``telemetry.advance`` / ``guard.advance``) is the policy
step, as DreamerV3's is, with the update owed at the rollout's last step and
bounded there (one block and one fetch a rollout): a preemption is honoured
within one decode step (the unfinished rollout is dropped), and an iteration
lasts as long as an observation waits for its action. The env interaction goes through `InteractionPipeline.interact`; the
player's state (where `ppo_recurrent`'s LSTM carry rides) is the backbone's
cache: the latent cache of a `deepseek_v3` decoder, or the window rings, shared
keys and values and recurrent state of a `phi4flash` one (`agent.BACKBONES`).
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

from sheeprl_tpu.algos.ppo.loss import entropy_loss, policy_loss, value_loss
from sheeprl_tpu.algos.ppo.ppo import _current_lr, make_optimizer
from sheeprl_tpu.algos.ppo_lm.agent import REST, PPOLMAgent, build_agent
from sheeprl_tpu.algos.ppo_lm.utils import test, token_gae
from sheeprl_tpu.config.instantiate import instantiate
from sheeprl_tpu.core.interact import InteractionPipeline
from sheeprl_tpu.core.player import PlayerPlacement
from sheeprl_tpu.registry import register_algorithm
from sheeprl_tpu.telemetry import scopes
from sheeprl_tpu.telemetry import tracer as tracer_mod
from sheeprl_tpu.utils.checkpoint import load_checkpoint, restore_opt_state, save_checkpoint
from sheeprl_tpu.utils.env import make_vector_env
from sheeprl_tpu.utils.logger import get_log_dir, get_logger
from sheeprl_tpu.utils.metric import MetricAggregator
from sheeprl_tpu.utils.timer import timer
from sheeprl_tpu.utils.utils import polynomial_decay, save_configs

#: What a gradient step reports beside its losses; the loop adds them to the
#: run's counters when the losses are fetched, never with a sync of their own.
STEP_COUNTERS = ("moe/routed_slots", "moe/held_slots", "moe/overflow_chunks", "moe/max_expert_tokens", "ppo_lm/loss_tokens",
                 "ppo_lm/padded_tokens", "ppo_lm/step_tokens")
#: Counted only by a backbone that has the mechanism (chunks x state-space layers of the step's selective scans).
SCAN_COUNTER = "ssm/scan_chunks"
#: Counted only where the step runs the latent-attention kernels: the (query tile, key tile) pairs they visit, and those
#: they skip as wholly left padding, over layers, passes and heads.
TILE_COUNTERS = ("mla/tile_visits", "mla/tile_visits_skipped")


def make_train_step(agent: PPOLMAgent, tx: optax.GradientTransformation, cfg: Dict[str, Any]):
    """The jitted gradient step over one minibatch of whole sequences:
    ``(params, opt_state, batch, clip_coef, ent_coef) -> (params, opt_state,
    metrics, routes)``. ``batch``: ``tokens`` [B, P+R] int32 left-padded,
    ``start`` [B], and [B, R] float32 ``logprobs``, ``values``,
    ``advantages``, ``returns``, ``mask``. ``routes`` [L, B*(P+R), k] are the
    experts every token chose in every expert layer (a diagnostic the loop drops)."""
    vf_coef = float(cfg.algo.vf_coef)
    P, R = agent.prompt_len, agent.rollout_steps
    scan_chunks = agent.scan_chunks()

    def loss_fn(params, batch, clip_coef, ent_coef):
        logits, values, stats = agent.evaluate(params, batch["tokens"], batch["start"])
        with scopes.scope(scopes.LM_HEAD_LOSS):
            mask = batch["mask"]
            loss_tokens = jnp.maximum(jnp.sum(mask), 1.0)
            mean = lambda x: jnp.sum(x * mask) / loss_tokens  # noqa: E731  the token mean over the minibatch
            logp = jax.nn.log_softmax(logits, axis=-1)
            new_logprobs = jnp.take_along_axis(logp, batch["tokens"][:, P:, None], axis=-1)[..., 0]
            entropy = -jnp.sum(jnp.exp(logp) * logp, axis=-1)
            pg_loss = mean(policy_loss(new_logprobs, batch["logprobs"], batch["advantages"], clip_coef, "none"))
            v_loss = mean(value_loss(values, batch["values"], batch["returns"], clip_coef, False, "none"))
            ent_loss = mean(entropy_loss(entropy, "none"))
            total = pg_loss + vf_coef * v_loss + ent_coef * ent_loss
        return total, (pg_loss, v_loss, ent_loss, stats)

    @partial(jax.jit, donate_argnums=(0, 1))
    def train_step(params, opt_state, batch, clip_coef, ent_coef):
        (_, (pg_loss, v_loss, ent_loss, stats)), grads = jax.value_and_grad(loss_fn, has_aux=True)(
            params, batch, clip_coef, ent_coef
        )
        with scopes.scope(scopes.LM_OPTIM):
            updates, opt_state = tx.update(grads, opt_state, params)
            params = optax.apply_updates(params, updates)
        mask, start = batch["mask"], batch["start"]
        positions = mask.shape[0] * (P + R)
        real = jnp.sum(P - start) + jnp.sum(mask)
        zero = jnp.zeros((), jnp.float32)
        metrics = {
            "policy_loss": pg_loss,
            "value_loss": v_loss,
            "entropy_loss": ent_loss,
            "moe/routed_slots": jnp.sum(stats["routed_slots"]).astype(jnp.float32) if stats else zero,
            "moe/held_slots": jnp.sum(stats["held_slots"]).astype(jnp.float32) if stats else zero,
            "moe/overflow_chunks": jnp.sum(stats["overflow_chunks"]).astype(jnp.float32) if stats else zero,
            "moe/max_expert_tokens": jnp.max(stats["expert_tokens"]).astype(jnp.float32) if stats else zero,
            "ppo_lm/loss_tokens": jnp.sum(mask),
            "ppo_lm/padded_tokens": positions - real.astype(jnp.float32),
            "ppo_lm/step_tokens": jnp.asarray(positions, jnp.float32),
        }
        if scan_chunks:
            metrics[SCAN_COUNTER] = jnp.asarray(scan_chunks, jnp.float32)
        tiles = agent.attention_tile_visits(start)
        if tiles is not None:
            metrics.update({name: count.astype(jnp.float32) for name, count in zip(TILE_COUNTERS, tiles)})
        routes = stats["chosen"] if stats else jnp.zeros((0, positions, 1), jnp.int32)
        return params, opt_state, metrics, routes

    return train_step


@register_algorithm()
def main(runtime, cfg: Dict[str, Any]):
    initial_ent_coef = float(cfg.algo.ent_coef)
    initial_clip_coef = float(cfg.algo.clip_coef)
    if cfg.algo.clip_vloss:
        raise ValueError("algo.clip_vloss is not offered by ppo_lm: the clipped value loss has no token mask")

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
    num_envs = int(cfg.env.num_envs)
    rollout_steps = int(cfg.algo.rollout_steps)
    with telemetry.span("setup/envs", "setup"):
        envs = make_vector_env(cfg, rank, log_dir)
    observation_space = envs.single_observation_space
    wanted = {"prompt", "prompt_len", "token", "active"}
    if not isinstance(observation_space, gym.spaces.Dict) or not wanted <= set(observation_space.keys()):
        raise RuntimeError(f"ppo_lm needs a token env (envs/tokens.py: observation keys {sorted(wanted)}), got {observation_space}")
    if not isinstance(envs.single_action_space, gym.spaces.Discrete):
        raise RuntimeError(f"ppo_lm needs a Discrete action space (the vocabulary), got {envs.single_action_space}")
    prompt_len = int(observation_space["prompt"].shape[0])

    # The flax init is one jitted call; it runs host-side so that the mesh
    # device sees the finished tree once.
    with telemetry.span("setup/agent", "setup"):
        with runtime.host_init():
            agent, params = build_agent(
                runtime, cfg, int(envs.single_action_space.n), prompt_len, state["agent"] if state is not None else None
            )
        tx, base_lr = make_optimizer(cfg)
        params = runtime.shard_params(params)
        opt_state = jax.jit(tx.init)(params)
        if state is not None:
            opt_state = runtime.shard_params(restore_opt_state(opt_state, state["optimizer"]))

    if runtime.is_global_zero:
        save_configs(cfg, log_dir)

    aggregator = None
    if not MetricAggregator.disabled:
        aggregator: MetricAggregator = instantiate(cfg.metric.aggregator)

    num_minibatches = max(1, int(cfg.algo.per_rank_num_batches))
    if num_envs % num_minibatches != 0:
        raise ValueError(f"env.num_envs ({num_envs}) must be a multiple of algo.per_rank_num_batches ({num_minibatches})")
    update_epochs = int(cfg.algo.update_epochs)
    steps_per_rollout = num_envs * rollout_steps * world_size
    rollouts_done = int(state["rollouts"]) if state is not None else 0
    policy_step = rollouts_done * steps_per_rollout
    last_log = state["last_log"] if state is not None else 0
    last_checkpoint = state["last_checkpoint"] if state is not None else 0
    total_rollouts = max(int(cfg.algo.total_steps) // steps_per_rollout, 1) if not cfg.dry_run else rollouts_done + 1
    if cfg.checkpoint.every % steps_per_rollout != 0:
        warnings.warn(
            f"The checkpoint.every parameter ({cfg.checkpoint.every}) is not a multiple of the policy steps of "
            f"one rollout ({steps_per_rollout}): checkpoints are saved at the first rollout's end past it."
        )

    # The cache is donated (updated in place); the rest of the player's state
    # (positions, the last logits) is small and stays readable after the call.
    def act_prefill(acting, cache, rest, prompt, lengths, key):
        return agent.prefill(acting, {**cache, **rest}, prompt, lengths, jnp.ones((prompt.shape[0],), bool), key)

    def act_decode(acting, cache, rest, token, key):
        return agent.decode(acting, {**cache, **rest}, token, key)

    prefill_fn = jax.jit(act_prefill, donate_argnums=(1,))
    decode_fn = jax.jit(act_decode, donate_argnums=(1,))

    def split(player_state):  # the backbone's cache leaves (donated), and the rest
        return ({k: v for k, v in player_state.items() if k not in REST}, {k: player_state[k] for k in REST})

    acting_fn = jax.jit(agent.acting_params)
    train_fn = make_train_step(agent, tx, cfg)

    # The player acts on a copy of the parameters in the compute dtype,
    # refreshed after every update (on-policy: the next rollout waits for it).
    with telemetry.span("setup/player", "setup"):
        placement = PlayerPlacement.resolve(cfg, runtime.mesh.devices.flat[0], params=params, force_fresh=True)
        placement.push(acting_fn(params))
        rollout_key = jax.random.fold_in(runtime.root_key, rank)
        order = np.random.default_rng(int(cfg.seed) + rank)  # the minibatches' order, drawn on the host
        pipeline = InteractionPipeline.from_config(cfg)
        if pipeline.slices != 1:
            raise ValueError("ppo_lm keeps one cache for all envs: env.pipeline_slices must be 1")
        pipeline.set_key(placement.put(rollout_key))
        with placement.ctx():
            pipeline.init_state(lambda n, _range: agent.init_state(n))

    def prefill_policy(obs, player_state, key):  # a rollout's first policy step
        with placement.ctx(), telemetry.span("player/prefill", "player"):
            return prefill_fn(placement.params(), *split(player_state), obs["prompt"], obs["prompt_len"][:, 0], key)

    def decode_policy(obs, player_state, key):  # every other one
        with placement.ctx():
            return decode_fn(placement.params(), *split(player_state), obs["token"][:, 0], key)

    def to_env_actions(host_outputs, n_envs):
        return np.asarray(host_outputs[0]).reshape(n_envs)

    cache_bytes = agent.cache_bytes(num_envs)
    fused_layers = agent.fused_attention_layers()
    fused_scans = agent.fused_scan_layers()
    train_timer = telemetry.step_timer("train", timer_key="Time/train_time")
    tracer = tracer_mod.current()
    keep_train_metrics = (aggregator is not None and not aggregator.disabled) or health.enabled or tracer.enabled

    def book(fetched) -> None:
        """The fetched gradient steps' counters into the run's counters."""
        for step_metrics in fetched:
            for name in STEP_COUNTERS:
                tracer.count(name, float(step_metrics[name]))
            for name in (SCAN_COUNTER, *TILE_COUNTERS):
                if name in step_metrics:
                    tracer.count(name, float(step_metrics[name]))

    shape = (rollout_steps, num_envs)
    tokens = np.zeros(shape, np.int32)
    logprobs, values, rewards, active, dones = (np.zeros(shape, np.float32) for _ in range(5))
    prompts = np.zeros((num_envs, prompt_len), np.int32)
    prompt_lens = np.zeros((num_envs,), np.int32)

    obs = None
    total_iters = (total_rollouts - rollouts_done) * rollout_steps
    for iter_num in range(1, total_iters + 1):
        policy_step += num_envs * world_size
        telemetry.advance(policy_step)
        guard.advance(policy_step)
        t = (iter_num - 1) % rollout_steps

        with timer("Time/env_interaction_time"):
            if t == 0:
                obs = pipeline.stash_obs(envs.reset(seed=cfg.seed + rollouts_done if rollouts_done == 0 else None)[0])
                prompts[:] = obs["prompt"]
                prompt_lens[:] = obs["prompt_len"][:, 0]
            active[t] = obs["active"][:, 0]
            res = pipeline.interact(envs, obs, prefill_policy if t == 0 else decode_policy, to_env_actions=to_env_actions)
            tokens[t], logprobs[t], values[t] = res.outputs
            rewards[t] = res.rewards
            obs = res.obs
            dones[t] = active[t] * (1.0 - obs["active"][:, 0])

        if t == rollout_steps - 1:
            rollouts_done += 1
            # ------------------------------------------------ GAE + the update
            live_values = values * active  # an idle env's value is not an estimate of anything
            returns, advantages = token_gae(rewards * active, live_values, dones, cfg.algo.gamma, cfg.algo.gae_lambda)
            data = {
                "tokens": np.concatenate([prompts, tokens.T], axis=1),
                "start": (prompt_len - prompt_lens).astype(np.int32),
                "logprobs": logprobs.T,
                "values": live_values.T,
                "advantages": advantages.T,
                "returns": returns.T,
                "mask": active.T,
            }
            with timer("Time/train_time"):
                for _ in range(update_epochs):
                    for rows in np.split(order.permutation(num_envs), num_minibatches):
                        batch = {k: np.ascontiguousarray(v[rows]) for k, v in data.items()}
                        with train_timer.step():
                            params, opt_state, train_metrics, _ = train_fn(
                                params,
                                opt_state,
                                batch,
                                np.asarray(cfg.algo.clip_coef, np.float32),
                                np.asarray(cfg.algo.ent_coef, np.float32),
                            )
                        train_timer.pend(params, train_metrics if keep_train_metrics else None)
            placement.push(acting_fn(params))
            # The update is bounded where it is owed: ONE block and ONE
            # device->host transfer a rollout (StepTimer.flush), on which the
            # losses and the step counters ride. The next rollout's first
            # action waits for the update on the device either way, so the
            # block takes nothing from the device, and the iteration of the
            # prefill lasts as long as the prefill.
            fetched = train_timer.flush()
            book(fetched)
            health.observe(policy_step, fetched, telemetry=telemetry)
            tracer.set_gauge("player/cache_tokens", float(np.sum(prompt_lens) + np.sum(active)))
            for kind, size in cache_bytes.items():
                tracer.set_gauge(f"player/cache_bytes/{kind}", float(size))
            tracer.set_gauge("lm/attention_fused", float(fused_layers))
            tracer.set_gauge("ssm/scan_fused", float(fused_scans))
            ended = dones.sum(0) > 0
            if aggregator and not aggregator.disabled:
                for step_metrics in fetched:
                    aggregator.update("Loss/policy_loss", step_metrics["policy_loss"])
                    aggregator.update("Loss/value_loss", step_metrics["value_loss"])
                    aggregator.update("Loss/entropy_loss", step_metrics["entropy_loss"])
                if ended.any():
                    aggregator.update("Rewards/rew_avg", float((rewards * active).sum(0)[ended].mean()))
                    aggregator.update("Game/ep_len_avg", float(active.sum(0)[ended].mean()))

            # ----------------------------------------------------- logging
            should_log = cfg.metric.log_level > 0 and (
                policy_step - last_log >= cfg.metric.log_every or rollouts_done == total_rollouts
            )
            if should_log:
                if aggregator and not aggregator.disabled:
                    aggregator.log_and_reset(logger, policy_step)
                telemetry.log_counters(logger, policy_step)
                if logger is not None:
                    logger.log("Info/learning_rate", _current_lr(opt_state, base_lr), policy_step)
                    logger.log("Info/clip_coef", cfg.algo.clip_coef, policy_step)
                    logger.log("Info/ent_coef", cfg.algo.ent_coef, policy_step)
                    if not timer.disabled:
                        timer_metrics = timer.compute()
                        if timer_metrics.get("Time/env_interaction_time", 0) > 0:
                            logger.log(
                                "Time/sps_env_interaction",
                                ((policy_step - last_log) / world_size) / timer_metrics["Time/env_interaction_time"],
                                policy_step,
                            )
                        timer.reset()
                last_log = policy_step

            # --------------------------------------------------- annealing
            if cfg.algo.anneal_lr:
                new_lr = polynomial_decay(rollouts_done, initial=base_lr, final=0.0, max_decay_steps=total_rollouts, power=1.0)
                opt_state.hyperparams["lr"] = jnp.asarray(new_lr, jnp.float32)
            if cfg.algo.anneal_clip_coef:
                cfg.algo.clip_coef = polynomial_decay(
                    rollouts_done, initial=initial_clip_coef, final=0.0, max_decay_steps=total_rollouts, power=1.0
                )
            if cfg.algo.anneal_ent_coef:
                cfg.algo.ent_coef = polynomial_decay(
                    rollouts_done, initial=initial_ent_coef, final=0.0, max_decay_steps=total_rollouts, power=1.0
                )

        # -------------------------------------------------------- checkpoint
        at_rollout_end = t == rollout_steps - 1
        if health.allow_save() and (
            (at_rollout_end and cfg.checkpoint.every > 0 and policy_step - last_checkpoint >= cfg.checkpoint.every)
            or (((at_rollout_end and rollouts_done == total_rollouts) or guard.preempted) and cfg.checkpoint.save_last)
        ):
            last_checkpoint = policy_step
            ckpt_state = {
                "agent": params,
                "optimizer": opt_state,
                "rollouts": rollouts_done,  # an unfinished rollout is dropped: a resumed run starts the next one
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
